"""Pallas TPU kernel for the transport layer's quantize/pack hot path.

``quantize_affine_kernel`` turns one tensor (flattened to (N, D)) into its
per-tensor affine int8 wire form: q = clip(round((x - xmin)/scale) - 128)
with (xmin, scale) computed over the VALID rows only — the row mask is how
the SelectedKnowledge codec keeps empty-cluster slots out of the statistics
(their bytes never cross the wire).

The global (xmin, xmax) must be known before any element can be quantized,
so the kernel runs a TWO-PHASE grid ``(2, N/block_n, D/block_d)``: TPU grids
execute sequentially with the last dimension fastest, so phase 0 streams
every (block_n, block_d) tile once and accumulates the masked min/max into
a block-(0,0)-pinned accumulator (the same read-modify-write-across-grid-
steps pattern as the fused Lloyd kernel's centroid sums), and phase 1
re-streams the tiles, reads the finished accumulator, and writes the int8
payload. The int8 output stays pinned to tile (0, 0) through phase 0, so
nothing is written back to HBM before phase 1 fills each tile. Two HBM reads
of x is the floor for exact per-tensor quantization; the (N, D) f32 -> int8
write is a 4x shrink, which is the point.

Tiling D is what keeps the kernel inside VMEM at the split layer's real
width (WRN-40-1: D = 16*32*32 = 16,384): one grid step holds a
(block_n, block_d) tile, never a whole (block_n, D) row block. block_n is a
multiple of 32 (the int8 output's sublane packing), block_d of 128.

Row padding rides the mask (padded rows are masked out); column padding is
handled with a static ``d_true`` closed over by the kernel body — a (1,
block_d) column guard, emitted only when D was actually padded — so
zero-padded lanes never touch the statistics. Every arithmetic step is an
exact min/max reduction or an elementwise f32 op, so the kernel is
bit-identical to ``ref.quantize_affine_ref`` at any tile shape, and the
pallas_call vmaps across a stacked cohort of clients (the batch axis
becomes the outermost — slowest — grid dimension, so each client's two
phases still run in order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import BIG, affine_params_from_minmax


def _quantize_affine_kernel(d_true, x_ref, m_ref, q_ref, mm_ref):
    phase, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = (i == 0) & (j == 0)
    x = x_ref[...]                             # (block_n, block_d)
    rowok = m_ref[...][:, :1] > 0.0            # (block_n, 1); col 0 = mask

    @pl.when(phase == 0)
    def _stats():
        valid = rowok
        if d_true is not None:                 # D was padded: guard columns
            block_d = x.shape[1]
            col = (j * block_d
                   + jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1))
            valid = rowok & (col < d_true)
        # block min/max broadcast across the lanes: full-block accumulator
        # stores (no sub-tile scalar writes on the TPU path)
        bmin = jnp.full((1, 128), jnp.min(jnp.where(valid, x, BIG)),
                        jnp.float32)
        bmax = jnp.full((1, 128), jnp.max(jnp.where(valid, x, -BIG)),
                        jnp.float32)

        @pl.when(first)
        def _init():
            mm_ref[...] = jnp.concatenate([bmin, bmax], axis=0)

        @pl.when(jnp.logical_not(first))
        def _accumulate():
            prev = mm_ref[...]
            mm_ref[...] = jnp.concatenate(
                [jnp.minimum(prev[0:1], bmin), jnp.maximum(prev[1:2], bmax)],
                axis=0)

    @pl.when(phase == 1)
    def _quantize():
        mm = mm_ref[...]
        xmin, scale = affine_params_from_minmax(mm[0, 0], mm[1, 0])
        # reciprocal multiply, matching the oracle op-for-op (see ref.py)
        q = jnp.clip(jnp.round((x - xmin) * (1.0 / scale)) - 128.0,
                     -128.0, 127.0)
        q_ref[...] = jnp.where(rowok, q, -128.0).astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("d_true", "block_n", "block_d",
                                    "interpret"))
def quantize_affine_kernel(x: jnp.ndarray, rowmask: jnp.ndarray, *,
                           d_true: int, block_n: int = 256,
                           block_d: int = 2048, interpret: bool = False):
    """x: (N, D) f32, rowmask: (N, 128) f32 (column 0 is the row's 0/1
    mask), N % block_n == 0, D % block_d == 0, D lane-aligned with the first
    ``d_true`` columns real (ops.quantize_affine handles padding). Returns
    (q (N, D) int8, minmax (2, 128) f32 with [0,0]=raw masked min and
    [1,0]=raw masked max — feed ``ref.affine_params_from_minmax``)."""
    n, d = x.shape
    assert n % block_n == 0 and d % block_d == 0, (n, d, block_n, block_d)
    assert rowmask.shape == (n, 128), rowmask.shape
    kernel = functools.partial(_quantize_affine_kernel,
                               d_true if d_true < d else None)
    return pl.pallas_call(
        kernel,
        grid=(2, n // block_n, d // block_d),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda p, i, j: (i, j)),
            pl.BlockSpec((block_n, 128), lambda p, i, j: (i, 0)),
        ],
        out_specs=[
            # pinned to tile (0, 0) during phase 0: no write-back of
            # unwritten tiles before phase 1 fills them
            pl.BlockSpec((block_n, block_d),
                         lambda p, i, j: (i * p, j * p)),
            # flcheck: disable=PAL002 (the block is the whole (2, 128) accumulator array, a shape Mosaic accepts)
            pl.BlockSpec((2, 128), lambda p, i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((2, 128), jnp.float32),
        ],
        interpret=interpret,
    )(x, rowmask)
