"""Kernels: the int8 quantize kernel's share of its roofline. It is bound
by memory: the least time of one client's quantize is its bytes
(``bench/flops.py``: two reads of the (slots, features) float32 tensor and
one write of its int8 levels) over the HBM bandwidth; one quantize runs per
client update; the time is the kernel's device seconds in the window."""
import _ops
import flops

PATTERNS = ("quantize",)


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not ctx["updates"]:
        return None
    seconds, count = _ops.matching(tr, PATTERNS)
    if not count or seconds <= 0:
        return None
    sh = ctx["shapes"]
    f, b = flops.quantize(sh["k"], sh["d"])
    least = max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["updates"] / seconds
