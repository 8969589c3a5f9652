"""Transport: the program's ``encode`` and ``decode`` spans (host) over
the window's client updates."""
import _spans


def read(ctx):
    spans = ctx["spans"]
    if not ctx["updates"] or not _spans.present(spans, "encode"):
        return None
    return ((_spans.total(spans, "encode") + _spans.total(spans, "decode"))
            / ctx["updates"])
