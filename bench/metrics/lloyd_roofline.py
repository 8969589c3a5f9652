"""Kernels: the fused Lloyd kernel's share of its roofline. The least time
is, per client, the Lloyd sweeps it ran (the ``lloyd_iters`` of the
program's ``select`` spans) times the larger of a sweep's operations over
the bf16 peak and its bytes over the HBM bandwidth (``bench/flops.py``;
at these shapes the bytes bound it); the time is the kernel's device
seconds in the traced window."""
import _ops
import flops

PATTERNS = ("lloyd",)


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks:
        return None
    seconds, count = _ops.matching(tr, PATTERNS)
    iters = [s["attrs"]["lloyd_iters"] for s in ctx["spans"]
             if s["name"] == "select" and "lloyd_iters" in s.get("attrs", {})
             and not s["attrs"].get("batched")]
    if not count or seconds <= 0 or not iters:
        return None
    sh = ctx["shapes"]
    f, b = flops.lloyd_step(sh["n"], sh["p"], sh["k"])
    least = max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
    return 100.0 * least * sum(iters) / seconds
