"""Sums over the program's spans (``repro.obs``), shared by the span
readers: a span's duration, and its self time less named children."""


def total(spans, name):
    return sum(s["t1"] - s["t0"] for s in spans if s["name"] == name)


def self_time(spans, name, exclude):
    """Durations of ``name`` spans less the time of their direct children
    named in ``exclude``."""
    ids = {s["id"] for s in spans if s["name"] == name}
    own = total(spans, name)
    kids = sum(s["t1"] - s["t0"] for s in spans
               if s["parent"] in ids and s["name"] in exclude)
    return own - kids


def present(spans, name):
    return any(s["name"] == name for s in spans)
