"""jit'd public wrappers around the Pallas kernels: shape padding/alignment,
interpret mode off the TPU backend (the CPU test runs; on a TPU the
kernels always compile through Mosaic), and the dispatch points the
model/selection code calls.

Each dispatch site sits in an ``obs.timed_block`` span (a no-op when
``FLConfig.observability`` is off). These wrappers usually run INSIDE a
jit trace, where ``sp.sync`` sees abstract tracers: it then skips
``block_until_ready`` and marks the span ``traced`` (the time measured is
trace/compile time, not device time — kernel spans with ``traced`` absent
are real eager dispatches, block-until-ready-synced so async device work
is counted)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.kmeans import kmeans_lloyd_kernel, kmeans_pairwise_dist_kernel
from repro.kernels.quantize import quantize_affine_kernel
from repro.obs.profile import profiled_jit

# profiled entries (module-level, so jax's jit caches live across rounds
# and a traced run's compile spans show only new signatures)
_pdist = profiled_jit(kmeans_pairwise_dist_kernel,
                      name="kmeans_pairwise_dist_kernel",
                      static_argnames=("block_n", "interpret"))
_lloyd = profiled_jit(kmeans_lloyd_kernel, name="kmeans_lloyd_kernel",
                      static_argnames=("block_n", "interpret"))
_quant = profiled_jit(quantize_affine_kernel, name="quantize_affine_kernel",
                      static_argnames=("d_true", "block_n", "block_d",
                                       "interpret"))
_flash = profiled_jit(flash_attention_kernel, name="flash_attention_kernel",
                      static_argnames=("causal", "window", "block_q",
                                       "block_k", "interpret"))
_decode = profiled_jit(flash_decode_kernel, name="flash_decode_kernel",
                       static_argnames=("block_s", "interpret"))


QUANT_BLOCK_D = 2048


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def kmeans_pairwise_dist(x: jnp.ndarray, c: jnp.ndarray,
                         block_n: int = 256) -> jnp.ndarray:
    """(N,D),(K,D) -> (N,K). Pads N to block_n, D and K to lane width 128.
    Distance is padding-invariant in D (zeros contribute 0); padded centroids
    are sliced away; padded rows dropped."""
    n, d = x.shape
    k = c.shape[0]
    if n < 64:   # tiny problems: the jnp path is faster than kernel overhead
        return ref.kmeans_pairwise_dist_ref(x, c)
    npad = _pad_to(n, block_n)
    dpad = _pad_to(d, 128)
    kpad = _pad_to(k, 128)
    xp = jnp.pad(x.astype(jnp.float32), ((0, npad - n), (0, dpad - d)))
    cp = jnp.pad(c.astype(jnp.float32), ((0, kpad - k), (0, dpad - d)))
    with obs.timed_block("kernel.kmeans_pairwise_dist",
                         n=n, d=d, k=k) as sp:
        out = sp.sync(_pdist(xp, cp, block_n=block_n,
                             interpret=_interpret()))
    return out[:n, :k]


def kmeans_lloyd_step(x: jnp.ndarray, c: jnp.ndarray, lmask: jnp.ndarray,
                      block_n: int = 256):
    """Fused Lloyd step: (N,D),(K,D),(N,K) -> (assign (N,) i32,
    mindist (N,), sums (K,D), counts (K,)). Pads N to block_n and D/K to
    lane width 128. Padding is correctness-free by construction: padded
    rows get an all-BIG mask row (zero weight, never accumulated), padded
    cluster columns get BIG for every row (never win the argmin), and
    zero-padded D contributes 0 to every distance."""
    n, d = x.shape
    k = c.shape[0]
    if n < 64:   # tiny problems: the jnp path is faster than kernel overhead
        return ref.kmeans_lloyd_ref(x, c, lmask)
    npad = _pad_to(n, block_n)
    dpad = _pad_to(d, 128)
    kpad = _pad_to(k, 128)
    xp = jnp.pad(x.astype(jnp.float32), ((0, npad - n), (0, dpad - d)))
    cp = jnp.pad(c.astype(jnp.float32), ((0, kpad - k), (0, dpad - d)))
    lp = jnp.pad(lmask.astype(jnp.float32), ((0, npad - n), (0, kpad - k)),
                 constant_values=ref.BIG)
    with obs.timed_block("kernel.kmeans_lloyd_step", n=n, d=d, k=k) as sp:
        assign, mind, sums, counts = sp.sync(_lloyd(
            xp, cp, lp, block_n=block_n, interpret=_interpret()))
    return assign[:n, 0], mind[:n, 0], sums[:k, :d], counts[0, :k]


def quantize_affine(x: jnp.ndarray, rowmask: jnp.ndarray,
                    block_n: int = 256):
    """Per-tensor affine int8 quantization of (N, D) x with (N,) row mask
    (the transport codec's pack hot path). Pads N to a multiple of the row
    tile (``block_n``, shrunk to the 32-row int8 packing for short
    payloads) and D to lane width 128; the column tile is the widest
    multiple of 128 up to ``QUANT_BLOCK_D`` that divides the padded D (a
    (256, 2048) f32 tile is 2 MiB, so the kernel fits VMEM at any D).
    Padded rows are masked out of the statistics and padded columns are guarded
    by the kernel's static d_true, so padding is correctness-free. Returns
    (q (N, D) int8, xmin f32, scale f32) exactly matching
    ``ref.quantize_affine_ref`` bit-for-bit (vmappable across a stacked
    cohort — the batch axis becomes the outermost grid dim)."""
    n, d = x.shape
    if n < 64:   # tiny payloads: the jnp path beats kernel dispatch
        return ref.quantize_affine_ref(x, rowmask)
    bn = min(block_n, _pad_to(n, 32))
    npad = _pad_to(n, bn)
    dpad = _pad_to(d, 128)
    bd = max(b for b in range(128, min(QUANT_BLOCK_D, dpad) + 1, 128)
             if dpad % b == 0)
    xp = jnp.pad(x.astype(jnp.float32), ((0, npad - n), (0, dpad - d)))
    mp = jnp.pad(rowmask.astype(jnp.float32), (0, npad - n))
    mp = jnp.broadcast_to(mp[:, None], (npad, 128))
    with obs.timed_block("kernel.quantize_affine", n=n, d=d) as sp:
        q, mm = sp.sync(_quant(xp, mp, d_true=d, block_n=bn, block_d=bd,
                               interpret=_interpret()))
    xmin, scale = ref.affine_params_from_minmax(mm[0, 0], mm[1, 0])
    return q[:n, :d], xmin, scale


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512) -> jnp.ndarray:
    """(B,S,H,D) GQA flash attention. Pads S to block multiples (key padding
    masked by seq_len inside the kernel; query padding rows sliced away) and
    D to 128 lanes (zero-padded D leaves logits unchanged)."""
    b, s, h, d = q.shape
    blk = min(block_q, block_k, _pad_to(s, 128))
    spad = _pad_to(s, blk)
    dpad = _pad_to(d, 128)
    pad4 = lambda t: jnp.pad(t, ((0, 0), (0, spad - s), (0, 0), (0, dpad - d)))
    qp, kp, vp = pad4(q), pad4(k), pad4(v)
    # scale uses original d: kernel scales by 1/sqrt(dpad) — compensate
    qp = qp * (dpad / d) ** 0.5
    with obs.timed_block("kernel.flash_attention", b=b, s=s, h=h,
                         d=d) as sp:
        out = sp.sync(_flash(
            qp, kp, vp, causal=causal, window=window,
            block_q=min(block_q, spad), block_k=min(block_k, spad),
            interpret=_interpret()))
    return out[:, :s, :, :d]


def flash_decode(q, k_cache, v_cache, valid, *, block_s: int = 1024
                 ) -> jnp.ndarray:
    """(B,1,H,D) x (B,S,KV,D) ring-buffer decode attention."""
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    blk = min(block_s, _pad_to(s, 128))
    spad = _pad_to(s, blk)
    dpad = _pad_to(d, 128)
    padc = lambda t: jnp.pad(t, ((0, 0), (0, spad - s), (0, 0), (0, dpad - d)))
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dpad - d))) * (dpad / d) ** 0.5
    kp, vp = padc(k_cache), padc(v_cache)
    vm = jnp.pad(valid, ((0, 0), (0, spad - s)))
    with obs.timed_block("kernel.flash_decode", b=b, s=s, h=h, d=d) as sp:
        out = sp.sync(_decode(qp, kp, vp, vm, block_s=blk,
                              interpret=_interpret()))
    return out[..., :d]
