"""Extract&Selection: self time of the program's ``select`` spans, less
their ``encode``/``decode`` children, over the window's client updates."""
import _spans


def read(ctx):
    spans = ctx["spans"]
    if not ctx["updates"] or not _spans.present(spans, "select"):
        return None
    return (_spans.self_time(spans, "select", ("encode", "decode"))
            / ctx["updates"])
