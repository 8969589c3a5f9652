"""Server: MetaTraining's own work — the program's ``meta_train`` spans
less the union of their ``compile`` children, over the window's rounds.
It includes tracing the scan body, which no compile event covers."""
import _compile
import _spans


def read(ctx):
    spans = ctx["spans"]
    if (not ctx["rounds"] or not _spans.present(spans, "meta_train")
            or not _compile.recorded(ctx)):
        return None
    ids = {s["id"] for s in spans if s["name"] == "meta_train"}
    kids = [s for s in spans
            if s["name"] == _compile.NAME and s["parent"] in ids]
    return ((_spans.total(spans, "meta_train") - _compile.covered_s(kids))
            / ctx["rounds"])
