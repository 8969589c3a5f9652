"""Server: the program's ``aggregate`` spans (MetaTraining, ModelCompose,
WeightAverage) over the window's rounds."""
import _spans


def read(ctx):
    spans = ctx["spans"]
    if not ctx["rounds"] or not _spans.present(spans, "aggregate"):
        return None
    return _spans.total(spans, "aggregate") / ctx["rounds"]
