"""Round loop: the time the host spends tracing, lowering and compiling,
or reading the compile cache, inside the window's rounds — the union of
the program's ``compile`` spans, over the window's rounds."""
import _compile


def read(ctx):
    if not ctx["rounds"] or not _compile.recorded(ctx):
        return None
    spans = [s for s in ctx["spans"] if s["name"] == _compile.NAME]
    return _compile.covered_s(spans) / ctx["rounds"]
