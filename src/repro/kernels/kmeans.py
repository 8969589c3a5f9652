"""Pallas TPU kernels for the paper's §3.1 selection hot spot (run every
round on every client over all local samples).

Two kernels:

1. ``kmeans_pairwise_dist_kernel`` — the original distance-matrix kernel:
   ||x-c||^2 = ||x||^2 + ||c||^2 - 2 x.c. The -2x.c term is a
   (block_n x D) @ (D x K) matmul on the MXU; the norms ride on the VPU.

2. ``kmeans_lloyd_kernel`` — the fused Lloyd step. One HBM pass per sweep:
   for each n-block it computes the biased distance tile
   d = ||x||^2 + ||c||^2 - 2 x.c + lmask, takes the row argmin (assignment),
   and accumulates the masked per-cluster statistics sum_j x and count_j on
   the spot — so the (N, K) distance matrix is never materialized in HBM and
   never re-read through a one_hot matmul. ``lmask`` is an additive mask
   (0 = row may join cluster, BIG = forbidden); it encodes both invalid rows
   (whole row BIG -> zero weight) and the per-class cluster structure of
   select_metadata (a row only sees its own class's cluster columns), which
   is what lets one kernel sweep replace ``num_classes`` masked sweeps.

Grid layout (both kernels): 1-D grid over n-blocks, ``grid = (N / block_n,)``.
The centroid set (K x D) is VMEM-resident across the whole grid (index_map
pins it to block (0,0)); x and lmask are streamed HBM->VMEM one n-block at a
time. The fused kernel's accumulator outputs (sums (K, D), counts (1, K))
are also pinned to block (0,0); TPU grids execute sequentially, so the
read-modify-write accumulation across grid steps is safe (initialized at
grid step 0 via ``pl.when``).

Alignment: D and K are padded by ops.py to lane multiples (128); block_n is
a sublane multiple (8 for f32, default 256). VMEM claim per grid cell of the
fused kernel, in f32 words:

    x        block_n * D
    c        K * D          (resident)
    lmask    block_n * K
    sums     K * D          (resident accumulator)
    counts   K
    assign   block_n        (int32, a (block_n, 1) column)
    mindist  block_n        (a (block_n, 1) column)
    + the (block_n, K) distance / one-hot intermediates.

At the paper-scale operating point (block_n=256, D=128, K=128 after
padding: 2500 maps, P=64 PCA dims, 10 classes x 10 clusters) that is
~0.45 MB — far under the ~16 MB/core budget, leaving room for the
pipeline's double buffering; block_n can grow to 2048 before VMEM matters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kmeans_dist_kernel(x_ref, c_ref, out_ref):
    x = x_ref[...]                           # (block_n, D)
    c = c_ref[...]                           # (K, D)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)            # (block_n, 1)
    c2 = jnp.sum(c * c, axis=1)                           # (K,)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    out_ref[...] = x2 + c2[None, :] - 2.0 * xc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_pairwise_dist_kernel(x: jnp.ndarray, c: jnp.ndarray,
                                block_n: int = 256,
                                interpret: bool = False) -> jnp.ndarray:
    """x: (N, D) f32, c: (K, D) f32, N % block_n == 0, D/K lane-aligned
    (ops.kmeans_pairwise_dist handles padding). Returns (N, K) f32."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kmeans_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # stream x blocks
            pl.BlockSpec((k, d), lambda i: (0, 0)),         # centroids resident
        ],
        out_specs=pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c)


def _kmeans_lloyd_kernel(x_ref, c_ref, m_ref,
                         assign_ref, mind_ref, sums_ref, counts_ref):
    i = pl.program_id(0)
    x = x_ref[...]                            # (block_n, D)
    c = c_ref[...]                            # (K, D)
    lm = m_ref[...]                           # (block_n, K) additive mask
    n_blk, k = lm.shape

    x2 = jnp.sum(x * x, axis=1, keepdims=True)             # (block_n, 1)
    c2 = jnp.sum(c * c, axis=1)                            # (K,)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d = x2 + c2[None, :] - 2.0 * xc + lm                   # biased distances

    assign = jnp.argmin(d, axis=1).astype(jnp.int32)
    assign_ref[...] = assign[:, None]
    mind_ref[...] = jnp.min(d, axis=1, keepdims=True)

    # a row with no admissible cluster (min mask > 0) gets zero weight
    w = (jnp.min(lm, axis=1) <= 0.0).astype(jnp.float32)   # (block_n,)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_blk, k), 1)
    onehot = (assign[:, None] == cols).astype(jnp.float32) * w[:, None]
    bsums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (K, D)
    bcounts = jnp.sum(onehot, axis=0)[None, :]             # (1, K)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = bsums
        counts_ref[...] = bcounts

    @pl.when(i > 0)
    def _accumulate():
        sums_ref[...] += bsums
        counts_ref[...] += bcounts


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_lloyd_kernel(x: jnp.ndarray, c: jnp.ndarray, lmask: jnp.ndarray,
                        block_n: int = 256, interpret: bool = False):
    """Fused Lloyd step. x: (N, D) f32, c: (K, D) f32, lmask: (N, K) f32
    additive mask, N % block_n == 0, D/K lane-aligned (ops.kmeans_lloyd_step
    handles padding). Returns (assign (N, 1) i32, mindist (N, 1) f32,
    sums (K, D) f32, counts (1, K) f32). The per-row outputs are columns,
    the layout a lane reduction produces: Mosaic refuses 1-D (block_n,)
    blocks against XLA's 1-D tiling unless block_n is a multiple of 1024."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert lmask.shape == (n, k), (lmask.shape, n, k)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kmeans_lloyd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # stream x blocks
            pl.BlockSpec((k, d), lambda i: (0, 0)),         # centroids resident
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),   # stream mask blocks
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),         # accumulator
            pl.BlockSpec((1, k), lambda i: (0, 0)),         # accumulator
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, c, lmask)
