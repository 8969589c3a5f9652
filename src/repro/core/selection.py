"""The paper's §3.1: clustered data (metadata) selection.

Pipeline (per client k):
  activation maps A_k^[j]  --flatten-->  (N, D)
  PCA to ``pca_components`` features     (N, P)      [16*32*32 -> 200 in paper]
  K-means per class, ``clusters_per_class`` clusters
  representative = sample closest (Euclidean) to each cluster centre
  D_M_k = activation maps of the representatives

Everything is pure JAX with static shapes (empty classes/clusters handled via
masks), so it jits, vmaps over clients, and lowers inside the distributed
train step. One step leaves the device on a TPU: the exact PCA's top
eigenpairs of a large matrix come from the host's LAPACK through
``jax.pure_callback`` (``_top_eigh``).

This is the system's hot path (every round, every client), so the engine is
built around one primitive: the **fused Lloyd step** — biased distances,
argmin assignment, and masked centroid sum/count accumulation in a single
pass over the data (``repro.kernels`` has the Pallas TPU kernel; the jnp
oracle in ``kernels/ref.py`` is the CPU path). Per-class clustering is a
single label-masked problem over ``num_classes * clusters_per_class``
cluster slots — one distance evaluation per sweep instead of one per class —
and Lloyd sweeps exit early once the centroids reach their fixed point
(bit-identical result to running all ``kmeans_iters`` sweeps, since a
converged sweep is a no-op). The final sweep's (assign, mindist, sums,
counts) ride the while_loop carry, so the old post-loop recompute sweep
only runs (under ``lax.cond``) when the loop dies at the iteration cap
without converging. ``select_metadata_batched`` vmaps the whole pipeline
across a stacked cohort of clients.

``select_metadata_reference`` keeps the seed implementation (per-class
``vmap`` of independent K-means runs, full distance matrices re-read through
``one_hot`` matmuls) as the identity/benchmark oracle.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from repro.kernels import ref as kref
from repro.obs.profile import profiled_jit
from repro.obs.timing import monotonic
from repro.obs.tracer import get_tracer

# the additive forbidden-column mask constant — shared with the kernel's
# oracle (ops.py pads with it too); the f32 absorption argument in
# kernels/kmeans.py depends on producer and consumer agreeing on it
BIG = kref.BIG


# --------------------------------------------------------------------------
# PCA
# --------------------------------------------------------------------------
# Rows from which a TPU takes the exact eigh on the host (``_top_eigh``).
# On a v5e, for the top 200 pairs, the device Jacobi is the faster up to
# 768 rows (34 ms against the callback's 51 ms) and the host from 1024
# (77 ms against 280 ms).
HOST_EIGH_MIN_N = 1024


class PCAState(NamedTuple):
    mean: jnp.ndarray          # (D,)
    components: jnp.ndarray    # (P, D) rows = principal axes
    explained: jnp.ndarray     # (P,) eigenvalues


def _lapack_top_eigh(a: np.ndarray, p: int):
    """Host side of ``_host_top_eigh``: LAPACK ``syevr`` (Householder
    tridiagonal reduction, then MRRR for the wanted pairs only) on each
    stacked matrix of ``a`` (..., n, n), in ``a``'s dtype. Records one
    finished ``eigh`` span under the innermost open span of the active
    tracer, from this call's own clock."""
    t0 = monotonic()
    a = np.asarray(a)
    n = a.shape[-1]
    flat = a.reshape(-1, n, n)
    evals = np.empty((flat.shape[0], p), a.dtype)
    evecs = np.empty((flat.shape[0], n, p), a.dtype)
    for i, m in enumerate(flat):
        # a standard problem with a subset: scipy calls ?syevr
        w, v = scipy.linalg.eigh(m, subset_by_index=[n - p, n - 1],
                                 check_finite=False)
        evals[i], evecs[i] = w[::-1], v[:, ::-1]
    tracer = get_tracer()
    if tracer.enabled:
        tracer.record("eigh", t0, monotonic(), n=n, p=p,
                      dtype=str(a.dtype), clients=flat.shape[0])
    return (evals.reshape(a.shape[:-2] + (p,)),
            evecs.reshape(a.shape[:-1] + (p,)))


def _host_top_eigh(a: jnp.ndarray, p: int):
    """The top ``p`` eigenpairs of the symmetric ``a`` from the host's
    LAPACK, through ``jax.pure_callback``. Under ``vmap`` the callback gets
    the stacked matrices at once (one call per batch, not per matrix)."""
    a = (a + jnp.swapaxes(a, -1, -2)) / 2           # as jnp.linalg.eigh does
    out = (jax.ShapeDtypeStruct(a.shape[:-2] + (p,), a.dtype),
           jax.ShapeDtypeStruct(a.shape[:-1] + (p,), a.dtype))
    return jax.pure_callback(functools.partial(_lapack_top_eigh, p=p), out,
                             a, vmap_method="expand_dims")


def _top_eigh(a: jnp.ndarray, p: int):
    """Top ``p`` eigenpairs of a symmetric matrix -> (eigenvalues in
    descending order, their eigenvector columns), exact on every backend.

    On a TPU a matrix of ``HOST_EIGH_MIN_N`` rows or more goes to the host's
    LAPACK (``_host_top_eigh``): XLA's native ``Eigh`` is a serial Jacobi
    loop over all n pairs (6.5 s for the 2500 x 2500 Gram of one client's
    maps on a v5e), and JAX's default TPU eigh (QDWH, traced into the
    program) takes minutes to compile at that size. A smaller matrix stays
    on the chip's Jacobi ``Eigh``: there the host trip costs more than the
    loop, a GSPMD program would gather every shard's matrices to one device
    for the callback, and a program holding a callback is never written to
    the persistent compile cache. Elsewhere it is the backend's default
    (LAPACK on the CPU) and the top ``p`` kept."""
    def full(m):
        evals, evecs = jnp.linalg.eigh(m)           # ascending
        return evals[::-1][:p], evecs[:, ::-1][:, :p]

    def jacobi(m):
        evecs, evals = jax.lax.linalg.eigh(
            m, implementation=jax.lax.linalg.EighImplementation.JACOBI)
        return evals[::-1][:p], evecs[:, ::-1][:, :p]

    host = a.shape[-1] >= HOST_EIGH_MIN_N
    return jax.lax.platform_dependent(
        a, tpu=functools.partial(_host_top_eigh, p=p) if host else jacobi,
        default=full)


def _pca_exact(xc: jnp.ndarray, cnt: jnp.ndarray, p: int):
    """Exact top-p eigenpairs: Gram trick when N <= D, else covariance."""
    n, d = xc.shape
    if n <= d:
        g = (xc @ xc.T) / cnt                       # (N, N) Gram
        evals, evecs = _top_eigh(g, p)              # descending
        safe = jnp.sqrt(jnp.maximum(evals * cnt, 1e-12))
        comps = (xc.T @ evecs) / safe               # (D, P) unit-norm cols
        comps = comps.T
    else:
        cov = (xc.T @ xc) / cnt                     # (D, D)
        evals, evecs = _top_eigh(cov, p)
        comps = evecs.T
    return evals, comps


def _pca_randomized(xc: jnp.ndarray, cnt: jnp.ndarray, p: int,
                    key: jax.Array, oversample: int, power_iters: int):
    """Randomized range finder (Halko et al.) for the top-p subspace of the
    covariance — O(N*D*(p+oversample)) matmuls instead of the O(D^3) eigh
    that dominates the selection pipeline on wide activation maps. Exact on
    any spectrum that decays within p+oversample directions (real activation
    maps do; that is why the paper's PCA works at all). The Rayleigh-quotient
    small matrix ``Q^T C Q`` applies the covariance once more for free, so
    one power iteration with a wide sketch already nails the subspace.
    Orthonormalization must stay QR — a Cholesky-QR squares the sketch's
    condition number and loses the tail directions in f32. Also returns the
    sketch projection ``b = xc @ q`` and the small-basis eigenvectors so a
    caller can form the features as ``b @ evecs`` without re-reading x."""
    n, d = xc.shape
    l = min(p + oversample, n, d)
    q = jax.random.normal(key, (d, l), xc.dtype)
    q = xc.T @ (xc @ q) / cnt

    def body(_, q):
        q, _ = jnp.linalg.qr(q)
        return xc.T @ (xc @ q) / cnt

    q = jax.lax.fori_loop(0, power_iters, body, q)
    q, _ = jnp.linalg.qr(q)                          # (D, l) orthonormal
    b = xc @ q                                       # (N, l)
    small = (b.T @ b) / cnt                          # (l, l) = Q^T C Q
    evals, evecs = _top_eigh(small, p)
    comps = (q @ evecs).T                            # (P, D)
    return evals, comps, b, evecs


def pca_fit(x: jnp.ndarray, num_components: int,
            mask: Optional[jnp.ndarray] = None, *,
            solver: str = "exact", key: Optional[jax.Array] = None,
            oversample: int = 32, power_iters: int = 1) -> PCAState:
    """PCA via the Gram trick when N < D (the paper's regime: a client's few
    thousand maps vs D=16384), else via the covariance matrix. ``mask`` marks
    valid rows; invalid rows get zero weight.

    ``solver='exact'`` (default) reproduces the seed numerics exactly.
    ``solver='randomized'`` swaps the D x D eigh for a randomized range
    finder — same subspace on fast-decaying spectra, and K-means selections
    are invariant to the basis rotation within that subspace. ``key`` seeds
    the random test matrix (a fixed default keeps it deterministic)."""
    n, d = x.shape
    p = num_components
    w = jnp.ones((n,), x.dtype) if mask is None else mask.astype(x.dtype)
    cnt = jnp.maximum(w.sum(), 1.0)
    mean = (x * w[:, None]).sum(0) / cnt
    xc = (x - mean) * w[:, None]
    if solver == "exact":
        evals, comps = _pca_exact(xc, cnt, p)
    elif solver == "randomized":
        if key is None:
            key = jax.random.PRNGKey(0x9CA)
        evals, comps, _, _ = _pca_randomized(xc, cnt, p, key, oversample,
                                             power_iters)
    else:
        raise ValueError(f"unknown PCA solver: {solver!r}")
    return PCAState(mean, comps.astype(x.dtype), evals.astype(x.dtype))


def pca_fit_transform(x: jnp.ndarray, num_components: int, *,
                      solver: str = "exact", key: Optional[jax.Array] = None,
                      oversample: int = 32, power_iters: int = 1):
    """Fit + project in one go -> (PCAState, features). For the randomized
    solver the features come straight from the sketch (``b @ evecs``), saving
    one full (N, D) read versus fit-then-transform."""
    if solver != "randomized":
        state = pca_fit(x, num_components, solver=solver, key=key)
        return state, pca_transform(state, x)
    n, d = x.shape
    mean = x.mean(0)
    xc = x - mean
    cnt = jnp.asarray(float(n), x.dtype)
    if key is None:
        key = jax.random.PRNGKey(0x9CA)
    evals, comps, b, evecs = _pca_randomized(xc, cnt, num_components, key,
                                             oversample, power_iters)
    state = PCAState(mean, comps.astype(x.dtype), evals.astype(x.dtype))
    return state, b @ evecs


def pca_transform(state: PCAState, x: jnp.ndarray) -> jnp.ndarray:
    return (x - state.mean) @ state.components.T


# --------------------------------------------------------------------------
# K-means (Lloyd, deterministic k-means++-style farthest-point init)
# --------------------------------------------------------------------------
class KMeansState(NamedTuple):
    centroids: jnp.ndarray     # (K, P)
    assignment: jnp.ndarray    # (N,) int32
    distances: jnp.ndarray     # (N,) squared dist to own centroid
    cluster_sizes: jnp.ndarray # (K,)
    iters: Optional[jnp.ndarray] = None  # () int32 Lloyd sweeps executed
    #   (early convergence exit < cap); None on paths that don't count


def _pairwise_sq_dists(x: jnp.ndarray, c: jnp.ndarray,
                       use_pallas: bool = False) -> jnp.ndarray:
    """(N,P)x(K,P) -> (N,K) squared Euclidean distances.
    ||x-c||^2 = ||x||^2 + ||c||^2 - 2 x.c — the MXU-friendly form the Pallas
    kernel implements with centroids resident in VMEM."""
    if use_pallas:
        from repro.kernels.ops import kmeans_pairwise_dist
        return kmeans_pairwise_dist(x, c)
    return kref.kmeans_pairwise_dist_ref(x, c)


def _lloyd_step(x: jnp.ndarray, c: jnp.ndarray, lmask: jnp.ndarray,
                use_pallas: bool = False):
    """One fused Lloyd sweep -> (assign, mindist, sums, counts)."""
    if use_pallas:
        from repro.kernels.ops import kmeans_lloyd_step
        return kmeans_lloyd_step(x, c, lmask)
    return kref.kmeans_lloyd_ref(x, c, lmask)


def _lloyd_iterate(x: jnp.ndarray, c0: jnp.ndarray, lmask: jnp.ndarray,
                   iters: int, use_pallas: bool):
    """Run Lloyd sweeps until the centroids reach their fixed point (or the
    ``iters`` cap). Early exit is bit-identical to running all sweeps: once
    ``new_c == c``, every later sweep recomputes exactly the same state.

    Returns (centroids, (assign, mindist, sums, counts), sweeps) — the
    final sweep's statistics ride through the while_loop carry, so callers
    get them WITHOUT a separate post-loop ``_lloyd_step``, and ``sweeps``
    is the () int32 count of Lloyd iterations actually executed (the
    early-exit telemetry the obs trace reports per client). On a convergence
    exit the carried stats were computed at centroids equal to the returned
    ones (``newc == c``), so they ARE the final stats; only a cap exit
    (non-converged after ``iters`` sweeps, whose carried stats belong to
    the penultimate centroids) pays a ``lax.cond`` recompute — bit-identical
    to the old always-recompute by construction."""

    def sweep(c):
        assign, mind, sums, counts = _lloyd_step(x, c, lmask, use_pallas)
        newc = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep empty clusters where they were (classic Lloyd behaviour);
        # the cast keeps the carry dtype-stable when x is not f32 (sums is
        # always f32 via preferred_element_type) — a no-op for f32
        newc = jnp.where(counts[:, None] > 0, newc, c).astype(c.dtype)
        return newc, (assign, mind, sums, counts)

    def cond(state):
        i, _, _, done = state
        return (i < iters) & jnp.logical_not(done)

    def body(state):
        i, c, _, _ = state
        newc, stats = sweep(c)
        return i + 1, newc, stats, jnp.all(newc == c)

    n, k = x.shape[0], c0.shape[0]
    # carry dtypes must match _lloyd_step's: mindist and counts come back
    # in x.dtype (sums is f32 via preferred_element_type)
    stats0 = (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), x.dtype),
              jnp.zeros((k, x.shape[1]), jnp.float32),
              jnp.zeros((k,), x.dtype))
    i, c, stats, done = jax.lax.while_loop(
        cond, body, (0, c0, stats0, jnp.asarray(False)))
    # cap exit (or iters == 0, where the loop never ran): the carried stats
    # lag the returned centroids by one sweep — recompute at c
    stats = jax.lax.cond(done, lambda: stats,
                         lambda: _lloyd_step(x, c, lmask, use_pallas))
    return c, stats, jnp.asarray(i, jnp.int32)


def kmeans_init(x: jnp.ndarray, k: int, key: jax.Array,
                mask: Optional[jnp.ndarray] = None,
                use_pallas: bool = False) -> jnp.ndarray:
    """k-means++-flavoured init: first centre random valid point, then
    farthest-point (deterministic given key, robust for selection use).

    The jnp path keeps a running min-distance-to-chosen-centres vector and
    evaluates one new centre per step (K x fewer FLOPs, same min in exact
    arithmetic). The Pallas path evaluates the full (N, K) tile per step via
    the VMEM-resident distance kernel — on the MXU the tile is effectively
    free and the incremental matvec would be VPU-bound."""
    n = x.shape[0]
    valid = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    logits = jnp.where(valid, 0.0, -jnp.inf)
    first = jax.random.categorical(key, logits)
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    if use_pallas:
        def body(i, c):
            d = _pairwise_sq_dists(x, c, use_pallas)     # (N, K)
            live = jnp.arange(k) < i
            d = jnp.where(live[None, :], d, BIG)
            dmin = jnp.min(d, axis=1)
            dmin = jnp.where(valid, dmin, -BIG)
            far = jnp.argmax(dmin)
            return c.at[i].set(x[far])

        return jax.lax.fori_loop(1, k, body, centroids)

    x2 = jnp.sum(x * x, axis=1)

    def dist_to(c_row):
        return x2 + jnp.sum(c_row * c_row) - 2.0 * (x @ c_row)

    def body(i, state):
        c, dmin = state
        far = jnp.argmax(jnp.where(valid, dmin, -BIG))
        c = c.at[i].set(x[far])
        return c, jnp.minimum(dmin, dist_to(x[far]))

    c, _ = jax.lax.fori_loop(1, k, body, (centroids, dist_to(x[first])))
    return c


@profiled_jit(static_argnames=("k", "iters", "use_pallas"))
def kmeans(x: jnp.ndarray, k: int, key: jax.Array, iters: int = 25,
           mask: Optional[jnp.ndarray] = None,
           use_pallas: bool = False) -> KMeansState:
    n = x.shape[0]
    valid = (jnp.ones((n,), bool) if mask is None else mask.astype(bool))
    lmask = jnp.where(valid, 0.0, BIG)[:, None] * jnp.ones((1, k), x.dtype)
    c0 = kmeans_init(x, k, key, mask, use_pallas=use_pallas)
    c, (assign, own, _, sizes), it = _lloyd_iterate(x, c0, lmask, iters,
                                                    use_pallas)
    return KMeansState(c, assign, own, sizes, it)


def representatives(x: jnp.ndarray, km: KMeansState,
                    mask: Optional[jnp.ndarray] = None,
                    use_pallas: bool = False) -> jnp.ndarray:
    """Paper: 'within each cluster choose the sample closest in Euclidean
    distance to the cluster centre'. Returns (K,) indices into x rows.

    An EMPTY cluster (``km.cluster_sizes[j] == 0``) yields the index of the
    valid point globally nearest to that cluster's centre, so every returned
    index is a sensible row of x; consumers still mask empty slots via
    ``cluster_sizes > 0``. (It used to be row 0 — the argmin of an all-BIG
    column.) With no valid rows at all every index degenerates to 0."""
    n, k = x.shape[0], km.centroids.shape[0]
    valid = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    d = _pairwise_sq_dists(x, km.centroids, use_pallas)   # (N, K)
    dvalid = jnp.where(valid[:, None], d, BIG)
    same = km.assignment[:, None] == jnp.arange(k)[None, :]
    dsame = jnp.where(same, dvalid, BIG)
    empty = km.cluster_sizes <= 0
    idx = jnp.where(empty, jnp.argmin(dvalid, axis=0),
                    jnp.argmin(dsame, axis=0))
    return idx.astype(jnp.int32)


# --------------------------------------------------------------------------
# Full §3.1 pipeline
# --------------------------------------------------------------------------
class Selection(NamedTuple):
    indices: jnp.ndarray       # (num_classes*K,) indices into the client's data
    valid: jnp.ndarray         # (num_classes*K,) bool — cluster non-empty
    features: jnp.ndarray      # (N, P) the PCA features (for diagnostics)
    lloyd_iters: Optional[jnp.ndarray] = None  # () int32 Lloyd sweeps run
    #   (always populated by select_metadata*; defaulted so 3-positional
    #   constructions keep working)


def _fit_features(acts: jnp.ndarray, pca_components: int, pca_solver: str):
    n = acts.shape[0]
    flat = acts.reshape(n, -1).astype(jnp.float32)
    p = min(pca_components, n - 1 if n > 1 else 1, flat.shape[1])
    _, feats = pca_fit_transform(flat, p, solver=pca_solver)
    return feats


@profiled_jit(static_argnames=("num_classes", "clusters_per_class",
                               "pca_components", "kmeans_iters",
                               "use_pallas", "per_class", "pca_solver"))
def select_metadata(acts: jnp.ndarray, labels: Optional[jnp.ndarray],
                    key: jax.Array, *, num_classes: int = 10,
                    clusters_per_class: int = 10, pca_components: int = 200,
                    kmeans_iters: int = 25, use_pallas: bool = False,
                    per_class: bool = True,
                    pca_solver: str = "exact") -> Selection:
    """acts: (N, ...) activation maps at split layer j (flattened internally).
    labels: (N,) int — paper clusters per class; ``per_class=False`` clusters
    all samples together (the LM generalization, no labels needed).

    Per-class clustering is one label-masked problem over
    ``num_classes * clusters_per_class`` cluster slots: a single fused Lloyd
    sweep per iteration assigns every sample among its own class's slots
    (additive BIG mask on foreign columns) and accumulates all centroid
    statistics — versus the seed path's per-class vmap that re-scanned all N
    rows once per class. The final sweep's per-row own-centroid distances
    also drive representative extraction, so no extra distance matrix is
    evaluated. ``pca_solver='randomized'`` swaps the exact eigh for the
    randomized range finder (same selections on decaying spectra)."""
    n = acts.shape[0]
    feats = _fit_features(acts, pca_components, pca_solver)

    if not per_class or labels is None:
        km = kmeans(feats, clusters_per_class, key, kmeans_iters,
                    use_pallas=use_pallas)
        idx = representatives(feats, km, use_pallas=use_pallas)
        valid = km.cluster_sizes[jnp.arange(clusters_per_class)] > 0
        return Selection(idx, valid, feats, km.iters)

    kk = clusters_per_class
    ck = num_classes * kk
    keys = jax.random.split(key, num_classes)

    # per-class farthest-point init (same keys/structure as the seed path)
    def init_one(c, k_c):
        return kmeans_init(feats, kk, k_c, mask=labels == c,
                           use_pallas=use_pallas)

    c0 = jax.vmap(init_one)(jnp.arange(num_classes), keys)   # (C, K, P)
    c0 = c0.reshape(ck, feats.shape[1])

    # single-pass label mask: row i may only join its own class's slots
    slot_class = jnp.arange(ck) // kk
    lmask = jnp.where(labels[:, None] == slot_class[None, :], 0.0,
                      BIG).astype(feats.dtype)

    c, (assign, own, _, sizes), lloyd_it = _lloyd_iterate(
        feats, c0, lmask, kmeans_iters, use_pallas)

    # representatives from the same sweep: per-slot argmin of own distance
    same = assign[:, None] == jnp.arange(ck)[None, :]
    w = jnp.min(lmask, axis=1) <= 0.0                        # row admissible
    drep = jnp.where(same & w[:, None], own[:, None], BIG)
    idx = jnp.argmin(drep, axis=0).astype(jnp.int32)

    # empty-slot contract (matches ``representatives``): the admissible row
    # nearest the slot's centre. Computed unconditionally — on the jnp path
    # the pairwise matrix is the same expression the last Lloyd sweep just
    # evaluated, so XLA CSEs it to ~zero cost (a lax.cond would block
    # that, and under vmap both branches run anyway); the Pallas path pays
    # one extra distance pass on top of the carried-sweep count.
    dfull = jnp.where(lmask <= 0.0,
                      _pairwise_sq_dists(feats, c, use_pallas), BIG)
    empty = sizes <= 0
    idx = jnp.where(empty, jnp.argmin(dfull, axis=0).astype(jnp.int32), idx)
    return Selection(idx, sizes > 0, feats, lloyd_it)


@profiled_jit(static_argnames=("num_classes", "clusters_per_class",
                               "pca_components", "kmeans_iters",
                               "use_pallas", "per_class", "pca_solver"))
def select_metadata_batched(acts: jnp.ndarray, labels: Optional[jnp.ndarray],
                            keys: jax.Array, *, num_classes: int = 10,
                            clusters_per_class: int = 10,
                            pca_components: int = 200,
                            kmeans_iters: int = 25, use_pallas: bool = False,
                            per_class: bool = True,
                            pca_solver: str = "exact") -> Selection:
    """vmap of ``select_metadata`` over a stacked cohort of clients.

    acts: (B, N, ...), labels: (B, N) or None, keys: (B,) client keys (e.g.
    ``jax.random.split(key, B)``). Returns a Selection whose fields carry a
    leading client axis. Keyword args are the static ``select_metadata``
    knobs (same defaults) and apply to every client."""
    fn = functools.partial(
        select_metadata, num_classes=num_classes,
        clusters_per_class=clusters_per_class, pca_components=pca_components,
        kmeans_iters=kmeans_iters, use_pallas=use_pallas, per_class=per_class,
        pca_solver=pca_solver)
    if labels is None:
        return jax.vmap(lambda a, k: fn(a, None, k))(acts, keys)
    return jax.vmap(fn)(acts, labels, keys)


@profiled_jit(static_argnames=("num_classes", "clusters_per_class",
                               "pca_components", "kmeans_iters",
                               "use_pallas", "per_class"))
def select_metadata_reference(acts: jnp.ndarray,
                              labels: Optional[jnp.ndarray],
                              key: jax.Array, *, num_classes: int = 10,
                              clusters_per_class: int = 10,
                              pca_components: int = 200,
                              kmeans_iters: int = 25,
                              use_pallas: bool = False,
                              per_class: bool = True) -> Selection:
    """The seed implementation, kept verbatim as the identity oracle and
    benchmark baseline: independent per-class K-means runs under ``vmap``,
    each running all ``kmeans_iters`` sweeps over the full distance matrix
    and re-reading it through a ``one_hot`` matmul, plus a separate distance
    evaluation for representative extraction."""
    n = acts.shape[0]
    flat = acts.reshape(n, -1).astype(jnp.float32)
    p = min(pca_components, n - 1 if n > 1 else 1, flat.shape[1])
    pca = pca_fit(flat, p)
    feats = pca_transform(pca, flat)

    def seed_kmeans_init(x, k, key, mask=None):
        nn = x.shape[0]
        valid = jnp.ones((nn,), bool) if mask is None else mask.astype(bool)
        logits = jnp.where(valid, 0.0, -jnp.inf)
        first = jax.random.categorical(key, logits)
        centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

        def body(i, c):
            d = _pairwise_sq_dists(x, c, use_pallas)    # full (N, K) per step
            live = jnp.arange(k) < i
            d = jnp.where(live[None, :], d, BIG)
            dmin = jnp.min(d, axis=1)
            dmin = jnp.where(valid, dmin, -BIG)
            far = jnp.argmax(dmin)
            return c.at[i].set(x[far])

        return jax.lax.fori_loop(1, k, body, centroids)

    def seed_kmeans(x, k, key, iters, mask=None):
        nn = x.shape[0]
        valid = (jnp.ones((nn,), bool) if mask is None else mask.astype(bool))
        c0 = seed_kmeans_init(x, k, key, mask)

        def step(_, c):
            d = _pairwise_sq_dists(x, c, use_pallas)
            d = jnp.where(valid[:, None], d, BIG)
            assign = jnp.argmin(d, axis=1)
            onehot = jax.nn.one_hot(assign, k, dtype=x.dtype) * valid[:, None]
            counts = onehot.sum(0)
            sums = onehot.T @ x
            newc = sums / jnp.maximum(counts, 1.0)[:, None]
            return jnp.where(counts[:, None] > 0, newc, c)

        c = jax.lax.fori_loop(0, iters, step, c0)
        d = _pairwise_sq_dists(x, c, use_pallas)
        d = jnp.where(valid[:, None], d, BIG)
        assign = jnp.argmin(d, axis=1).astype(jnp.int32)
        own = jnp.take_along_axis(d, assign[:, None], axis=1)[:, 0]
        sizes = (jax.nn.one_hot(assign, k) * valid[:, None]).sum(0)
        return KMeansState(c, assign, own, sizes)

    # the seed loop always runs all sweeps — populate lloyd_iters anyway so
    # reference and fused Selections have the same pytree structure
    ran = jnp.asarray(kmeans_iters, jnp.int32)
    if not per_class or labels is None:
        km = seed_kmeans(feats, clusters_per_class, key, kmeans_iters)
        idx = representatives(feats, km)
        valid = km.cluster_sizes[jnp.arange(clusters_per_class)] > 0
        return Selection(idx, valid, feats, ran)

    keys = jax.random.split(key, num_classes)

    def one_class(c, k_c):
        m = labels == c
        km = seed_kmeans(feats, clusters_per_class, k_c, kmeans_iters, mask=m)
        idx = representatives(feats, km, mask=m)
        return idx, km.cluster_sizes > 0

    idxs, valids = jax.vmap(one_class)(jnp.arange(num_classes), keys)
    return Selection(idxs.reshape(-1), valids.reshape(-1), feats, ran)


def selected_fraction(sel: Selection, n_total: int) -> jnp.ndarray:
    """The paper's headline metric: |D_M_k| / |D_k| (~0.8% in the paper)."""
    return sel.valid.sum() / n_total
