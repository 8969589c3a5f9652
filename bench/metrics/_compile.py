"""The program's ``compile`` spans (JAX's tracing, lowering and backend
compiles, cache reads included, as ``repro.obs`` records them): whether a
run records them, and the time a set of them covers."""

NAME = "compile"


def recorded(ctx):
    """Whether the program records its compile work as spans: the window
    holds some, or the harness counted no backend compile there to
    record. A program that predates them gives neither."""
    return (any(s["name"] == NAME for s in ctx["spans"])
            or not ctx["compiles"])


def covered_s(spans):
    """Seconds covered by the union of the spans' intervals (a trace of a
    function nests the traces of the functions it calls)."""
    total, end = 0.0, None
    for t0, t1 in sorted((s["t0"], s["t1"]) for s in spans):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total
