"""The device's idle share of the traced window: 100 x (1 - the union of
the device's op intervals over the window), averaged over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["op_seconds"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
