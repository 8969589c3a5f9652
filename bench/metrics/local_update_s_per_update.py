"""LocalUpdate: the program's ``local_update`` spans over the window's
client updates."""
import _spans


def read(ctx):
    spans = ctx["spans"]
    if not ctx["updates"] or not _spans.present(spans, "local_update"):
        return None
    return _spans.total(spans, "local_update") / ctx["updates"]
