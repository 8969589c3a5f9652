"""Seconds per round: the window's whole rounds (broadcast -> W_G(t)) over
their count, on the host's clock."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return ctx["window_s"] / ctx["rounds"]
