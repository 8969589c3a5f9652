"""Extract&Selection: seconds of the exact PCA's eigendecomposition on the
host (LAPACK, the program's ``eigh`` spans), over the window's client
updates."""
import _spans


def read(ctx):
    spans = ctx["spans"]
    if not ctx["updates"] or not _spans.present(spans, "eigh"):
        return None
    return _spans.total(spans, "eigh") / ctx["updates"]
