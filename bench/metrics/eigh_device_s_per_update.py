"""Extract&Selection: device seconds of the exact PCA's eigendecomposition
(XLA's ``Eigh``) in the traced window, over the window's client updates."""
import _ops

PATTERNS = ("eighjacobisweeps", "eightpu")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["updates"]:
        return None
    seconds, count = _ops.matching(tr, PATTERNS)
    if not count:
        return None
    return seconds / ctx["updates"]
