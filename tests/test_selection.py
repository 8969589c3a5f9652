"""Unit + property tests for the paper's §3.1 selection pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.selection import (kmeans, pca_fit, pca_transform,
                                  representatives, select_metadata,
                                  select_metadata_batched,
                                  select_metadata_reference,
                                  selected_fraction)

KEY = jax.random.PRNGKey(0)


def structured_acts(seed, n=400):
    """Low-rank mode-structured activation maps (decaying spectrum) — the
    regime real split-layer activations live in; same generator the
    selection benchmark validates against."""
    from repro.data import SyntheticActivationMaps
    ds = SyntheticActivationMaps(n, (8, 8, 4), num_classes=4,
                                 modes_per_class=3, rank=48,
                                 spectrum_decay=0.9, seed=seed,
                                 structure_seed=seed)
    return jnp.asarray(ds.x), jnp.asarray(ds.y)


class TestPCA:
    def test_reconstruction_identity_when_full_rank(self):
        x = np.random.default_rng(0).normal(size=(50, 8)).astype(np.float32)
        st_ = pca_fit(jnp.asarray(x), 8)
        z = pca_transform(st_, jnp.asarray(x))
        xr = z @ st_.components + st_.mean
        np.testing.assert_allclose(np.asarray(xr), x, atol=1e-3)

    def test_components_orthonormal(self):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 100)),
                        jnp.float32)
        st_ = pca_fit(x, 10)
        g = np.asarray(st_.components @ st_.components.T)
        np.testing.assert_allclose(g, np.eye(10), atol=1e-3)

    def test_gram_vs_cov_paths_agree(self):
        # n<d triggers the Gram trick; n>d the covariance path
        rng = np.random.default_rng(2)
        base = rng.normal(size=(300, 20)).astype(np.float32)
        st_small = pca_fit(jnp.asarray(base[:15]), 4)     # gram
        st_big = pca_fit(jnp.asarray(base), 4)            # cov
        # both must capture descending variance
        assert np.all(np.diff(np.asarray(st_small.explained)) <= 1e-4)
        assert np.all(np.diff(np.asarray(st_big.explained)) <= 1e-4)

    def test_variance_ordering_dominant_direction(self):
        rng = np.random.default_rng(3)
        x = np.concatenate([rng.normal(0, 10, (200, 1)),
                            rng.normal(0, 0.1, (200, 5))], 1).astype(np.float32)
        st_ = pca_fit(jnp.asarray(x), 2)
        c0 = np.abs(np.asarray(st_.components[0]))
        assert c0[0] > 0.99   # first component = the high-variance axis

    def test_mask_excludes_rows(self):
        x = np.zeros((10, 4), np.float32)
        x[5:] = 1000.0   # garbage rows, masked out
        mask = jnp.asarray([True] * 5 + [False] * 5)
        st_ = pca_fit(jnp.asarray(x), 2, mask=mask)
        assert float(jnp.abs(st_.mean).max()) < 1e-3


class TestKMeans:
    def test_separated_clusters_found(self):
        rng = np.random.default_rng(0)
        centers = np.array([[0, 0], [10, 10], [-10, 10]], np.float32)
        x = np.concatenate([c + rng.normal(0, .3, (50, 2)) for c in centers])
        km = kmeans(jnp.asarray(x, jnp.float32), 3, KEY, iters=20)
        # each true cluster maps to exactly one centroid
        found = np.asarray(km.centroids)
        d = np.linalg.norm(found[:, None] - centers[None], axis=-1).min(0)
        assert d.max() < 1.0
        assert np.asarray(km.cluster_sizes).sum() == 150

    def test_assignment_is_nearest(self):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 5)),
                        jnp.float32)
        km = kmeans(x, 4, KEY, iters=10)
        d = ((np.asarray(x)[:, None] - np.asarray(km.centroids)[None]) ** 2
             ).sum(-1)
        np.testing.assert_array_equal(d.argmin(1), np.asarray(km.assignment))

    def test_mask_keeps_invalid_out_of_centroids(self):
        rng = np.random.default_rng(2)
        x = np.concatenate([rng.normal(0, 1, (30, 3)),
                            np.full((10, 3), 1e4)]).astype(np.float32)
        mask = jnp.asarray([True] * 30 + [False] * 10)
        km = kmeans(jnp.asarray(x), 3, KEY, iters=10, mask=mask)
        assert float(jnp.abs(km.centroids).max()) < 100.0

    def test_representatives_belong_to_their_cluster(self):
        x = jnp.asarray(np.random.default_rng(3).normal(size=(80, 4)),
                        jnp.float32)
        km = kmeans(x, 5, KEY, iters=10)
        reps = representatives(x, km)
        for j, r in enumerate(np.asarray(reps)):
            if np.asarray(km.cluster_sizes)[j] > 0:
                assert int(np.asarray(km.assignment)[r]) == j


class TestRepresentativesEmptyClusters:
    """Contract: an empty cluster yields the index of the valid point
    globally nearest to that cluster's centre (it used to be the argmin of
    an all-BIG column — always row 0, regardless of geometry)."""

    def _check_contract(self, x, km, reps, mask=None):
        xn = np.asarray(x)
        valid = (np.ones(len(xn), bool) if mask is None
                 else np.asarray(mask, bool))
        cents = np.asarray(km.centroids)
        sizes = np.asarray(km.cluster_sizes)
        assign = np.asarray(km.assignment)
        d = ((xn[:, None] - cents[None]) ** 2).sum(-1)
        d[~valid] = np.inf
        for j, r in enumerate(np.asarray(reps)):
            if sizes[j] > 0:
                assert assign[r] == j          # old contract, unchanged
            else:
                assert d[:, j].argmin() == r   # nearest valid point

    def test_empty_cluster_yields_nearest_valid(self):
        # 3 distinct points, 6 clusters -> empty clusters guaranteed
        x = jnp.asarray(np.repeat(np.array([[0., 0.], [10., 0.], [0., 10.]],
                                           np.float32), 4, axis=0))
        km = kmeans(x, 6, KEY, iters=5)
        assert (np.asarray(km.cluster_sizes) == 0).any()
        reps = representatives(x, km)
        assert np.asarray(reps).max() < x.shape[0]
        self._check_contract(x, km, reps)

    def test_empty_cluster_masked_path(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(30, 3)), jnp.float32)
        mask = jnp.asarray([True] * 4 + [False] * 26)   # 4 valid rows, k=6
        km = kmeans(x, 6, KEY, iters=5, mask=mask)
        assert (np.asarray(km.cluster_sizes) == 0).any()
        reps = representatives(x, km, mask=mask)
        self._check_contract(x, km, reps, mask=mask)
        # every representative is a VALID row, not an arbitrary row 0
        for j, r in enumerate(np.asarray(reps)):
            assert bool(mask[r])

    def test_fused_per_class_matches_reference_with_empty_slots(self):
        """A class with fewer points than clusters forces empty slots in
        the masked per-class path; the fused engine's fallback must agree
        with the reference path's ``representatives(mask=...)``."""
        rng = np.random.default_rng(1)
        acts = rng.normal(size=(60, 12)).astype(np.float32)
        labels = np.full(60, 1, np.int64)
        labels[:2] = 0                          # class 0: 2 points, 4 slots
        kw = dict(num_classes=2, clusters_per_class=4, pca_components=6,
                  kmeans_iters=6)
        a = select_metadata(jnp.asarray(acts), jnp.asarray(labels), KEY, **kw)
        b = select_metadata_reference(jnp.asarray(acts), jnp.asarray(labels),
                                      KEY, **kw)
        assert not np.asarray(a.valid).all()    # empty slots really exist
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
        np.testing.assert_array_equal(np.asarray(a.valid),
                                      np.asarray(b.valid))
        # empty slots of class 0 point at class-0 rows (the admissible set)
        idx = np.asarray(a.indices).reshape(2, 4)
        valid = np.asarray(a.valid).reshape(2, 4)
        for j in range(4):
            if not valid[0, j]:
                assert labels[idx[0, j]] == 0


class TestSelectMetadata:
    def test_paper_shape_contract(self):
        """20 clusters/class x 10 classes -> 200 selected (Table 5 setup)."""
        rng = np.random.default_rng(0)
        acts = rng.normal(size=(500, 6, 6, 4)).astype(np.float32)
        labels = rng.integers(0, 10, 500)
        s = select_metadata(jnp.asarray(acts), jnp.asarray(labels), KEY,
                            num_classes=10, clusters_per_class=20,
                            pca_components=32, kmeans_iters=5)
        assert s.indices.shape == (200,)
        frac = float(selected_fraction(s, 500))
        assert 0 < frac <= 0.41

    def test_selected_indices_have_right_class(self):
        rng = np.random.default_rng(1)
        acts = rng.normal(size=(200, 16)).astype(np.float32)
        labels = rng.integers(0, 4, 200)
        s = select_metadata(jnp.asarray(acts), jnp.asarray(labels), KEY,
                            num_classes=4, clusters_per_class=5,
                            pca_components=8, kmeans_iters=5)
        idx = np.asarray(s.indices).reshape(4, 5)
        valid = np.asarray(s.valid).reshape(4, 5)
        for c in range(4):
            for j in range(5):
                if valid[c, j]:
                    assert labels[idx[c, j]] == c

    def test_unlabeled_mode(self):
        acts = jnp.asarray(np.random.default_rng(2).normal(size=(100, 32)),
                           jnp.float32)
        s = select_metadata(acts, None, KEY, per_class=False,
                            clusters_per_class=8, pca_components=16,
                            kmeans_iters=5)
        assert s.indices.shape == (8,)

    def test_mode_coverage_on_structured_data(self):
        """Clients with clustered data: every mode contributes a rep."""
        rng = np.random.default_rng(3)
        modes = rng.normal(0, 5, (4, 24)).astype(np.float32)
        which = rng.integers(0, 4, 400)
        acts = modes[which] + rng.normal(0, .2, (400, 24)).astype(np.float32)
        s = select_metadata(jnp.asarray(acts), None, KEY, per_class=False,
                            clusters_per_class=4, pca_components=8,
                            kmeans_iters=15)
        sel_modes = set(which[np.asarray(s.indices)])
        assert len(sel_modes) == 4   # one representative per true mode


class TestFusedEngineIdentity:
    """The fused single-pass engine must reproduce the seed implementation
    (``select_metadata_reference``) selection-for-selection."""

    def test_single_pass_equals_seed_reference(self):
        for seed in range(3):
            rng_ = np.random.default_rng(seed)
            acts = jnp.asarray(rng_.normal(size=(300, 6, 6, 4)), jnp.float32)
            labels = jnp.asarray(rng_.integers(0, 6, 300))
            key = jax.random.PRNGKey(seed)
            kw = dict(num_classes=6, clusters_per_class=5,
                      pca_components=24, kmeans_iters=10)
            a = select_metadata(acts, labels, key, **kw)
            b = select_metadata_reference(acts, labels, key, **kw)
            np.testing.assert_array_equal(np.asarray(a.indices),
                                          np.asarray(b.indices))
            np.testing.assert_array_equal(np.asarray(a.valid),
                                          np.asarray(b.valid))

    def test_unlabeled_mode_equals_seed_reference(self):
        acts = jnp.asarray(np.random.default_rng(1).normal(size=(150, 40)),
                           jnp.float32)
        kw = dict(per_class=False, clusters_per_class=8, pca_components=16,
                  kmeans_iters=8)
        a = select_metadata(acts, None, KEY, **kw)
        b = select_metadata_reference(acts, None, KEY, **kw)
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))

    def test_pallas_path_matches_jnp_path(self):
        """use_pallas=True routes init, Lloyd and representatives through
        the fused kernel (interpret mode on CPU) — same selections."""
        acts, labels = structured_acts(0, n=300)
        kw = dict(num_classes=4, clusters_per_class=5, pca_components=16,
                  kmeans_iters=6)
        a = select_metadata(acts, labels, KEY, **kw)
        b = select_metadata(acts, labels, KEY, use_pallas=True, **kw)
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
        np.testing.assert_array_equal(np.asarray(a.valid),
                                      np.asarray(b.valid))

    def test_randomized_pca_matches_on_structured_maps(self):
        """On decaying-spectrum maps the range-finder PCA spans the same
        subspace, and selections are rotation-invariant within it."""
        for seed in range(3):
            acts, labels = structured_acts(seed)
            key = jax.random.PRNGKey(seed)
            kw = dict(num_classes=4, clusters_per_class=5,
                      pca_components=16, kmeans_iters=10)
            a = select_metadata(acts, labels, key, pca_solver="randomized",
                                **kw)
            b = select_metadata_reference(acts, labels, key, **kw)
            np.testing.assert_array_equal(np.asarray(a.indices),
                                          np.asarray(b.indices))

    def test_batched_vmap_equals_sequential_loop(self):
        """select_metadata_batched over stacked clients == looping clients
        through select_metadata one at a time."""
        B = 4
        cohort = [structured_acts(s) for s in range(B)]
        acts = jnp.stack([a for a, _ in cohort])
        labels = jnp.stack([l for _, l in cohort])
        keys = jax.random.split(KEY, B)
        kw = dict(num_classes=4, clusters_per_class=5, pca_components=16,
                  kmeans_iters=8)
        batched = select_metadata_batched(acts, labels, keys, **kw)
        for i in range(B):
            one = select_metadata(acts[i], labels[i], keys[i], **kw)
            np.testing.assert_array_equal(np.asarray(batched.indices[i]),
                                          np.asarray(one.indices))
            np.testing.assert_array_equal(np.asarray(batched.valid[i]),
                                          np.asarray(one.valid))

    def test_early_exit_matches_full_sweep_budget(self):
        """Lloyd early exit is bit-identical to running the full budget:
        more iterations past convergence change nothing."""
        acts, labels = structured_acts(7)
        kw = dict(num_classes=4, clusters_per_class=3, pca_components=8)
        a = select_metadata(acts, labels, KEY, kmeans_iters=25, **kw)
        b = select_metadata(acts, labels, KEY, kmeans_iters=100, **kw)
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))


class TestRandomizedPCA:
    def test_subspace_matches_exact_on_decaying_spectrum(self):
        acts, _ = structured_acts(0, n=300)
        flat = acts.reshape(300, -1)
        ex = pca_fit(flat, 16)
        rd = pca_fit(flat, 16, solver="randomized")
        p1 = np.asarray(ex.components.T @ ex.components)
        p2 = np.asarray(rd.components.T @ rd.components)
        assert np.abs(p1 - p2).max() < 1e-2
        np.testing.assert_allclose(np.asarray(rd.explained),
                                   np.asarray(ex.explained), rtol=1e-2)

    def test_components_orthonormal(self):
        acts, _ = structured_acts(1, n=200)
        rd = pca_fit(acts.reshape(200, -1), 12, solver="randomized")
        g = np.asarray(rd.components @ rd.components.T)
        np.testing.assert_allclose(g, np.eye(12), atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(8, 60), d=st.integers(2, 30), k=st.integers(1, 6),
       seed=st.integers(0, 2 ** 16))
def test_property_kmeans_invariants(n, d, k, seed):
    """For any data: assignments in range, sizes sum to N, own-centroid
    distance is minimal among centroids."""
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)),
                    jnp.float32)
    km = kmeans(x, k, jax.random.PRNGKey(seed), iters=5)
    a = np.asarray(km.assignment)
    assert ((0 <= a) & (a < k)).all()
    assert int(np.asarray(km.cluster_sizes).sum()) == n
    d_all = ((np.asarray(x)[:, None] - np.asarray(km.centroids)[None]) ** 2
             ).sum(-1)
    np.testing.assert_allclose(d_all.min(1), np.asarray(km.distances),
                               rtol=2e-2, atol=2e-2)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(10, 50), d=st.integers(4, 40), p=st.integers(1, 8),
       seed=st.integers(0, 2 ** 16))
def test_property_pca_projection_shrinks(n, d, p, seed):
    """Projection residual never exceeds total variance; explained variances
    are non-negative and descending."""
    p = min(p, n - 1, d)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)),
                    jnp.float32)
    st_ = pca_fit(x, p)
    ev = np.asarray(st_.explained)
    assert (ev >= -1e-4).all()
    assert (np.diff(ev) <= 1e-3).all()
    z = pca_transform(st_, x)
    assert np.isfinite(np.asarray(z)).all()


class TestLloydCarriedStats:
    """The post-loop Lloyd sweep is gone: ``_lloyd_iterate`` carries
    (assign, mindist, sums, counts) through the while_loop and only
    recomputes (lax.cond) on a cap exit — both exits must be bit-identical
    to a fresh ``_lloyd_step`` at the returned centroids."""

    def _problem(self, seed=0, n=120, d=6, k=5):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        lmask = jnp.where(jnp.asarray(rng.random((n, k)) < 0.1), 1e30,
                          0.0).astype(jnp.float32)
        from repro.core.selection import kmeans_init
        c0 = kmeans_init(x, k, KEY)
        return x, c0, lmask

    @pytest.mark.parametrize("iters", [0, 1, 2, 100])
    def test_carried_stats_equal_recompute(self, iters):
        """iters in {0, 1, 2} force cap exits (including the degenerate
        never-ran loop); iters=100 converges and exits early — every case
        must hand back exactly the stats of a final-sweep recompute."""
        from repro.core.selection import _lloyd_iterate, _lloyd_step
        x, c0, lmask = self._problem()
        c, stats, sweeps = _lloyd_iterate(x, c0, lmask, iters, False)
        want = _lloyd_step(x, c, lmask, False)
        for got, ref_ in zip(stats, want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_))
        # the sweep count is the early-exit telemetry the trace reports:
        # capped runs report the cap, converged runs report fewer
        assert 0 <= int(sweeps) <= iters

    def test_kmeans_non_f32_dtype_traces(self):
        """Regression: the carry's stats0 once hardcoded f32 for mindist/
        counts, so a bf16 feature matrix (which _lloyd_step returns in
        x.dtype) crashed the while_loop with a carry-type mismatch."""
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(40, 4)), jnp.bfloat16)
        km = kmeans(x, 3, KEY, iters=5)
        assert km.assignment.shape == (40,)
        assert km.distances.dtype == jnp.bfloat16

    def test_kmeans_state_unchanged_by_carry(self):
        """kmeans() (which now consumes the carried stats) returns the
        same state as recomputing each field from its centroids."""
        from repro.core.selection import _lloyd_step
        x, _, _ = self._problem(seed=3)
        km = kmeans(x, 4, KEY, iters=25)
        lmask = jnp.zeros((x.shape[0], 4), jnp.float32)
        assign, own, _, sizes = _lloyd_step(x, km.centroids, lmask, False)
        np.testing.assert_array_equal(np.asarray(km.assignment),
                                      np.asarray(assign))
        np.testing.assert_array_equal(np.asarray(km.distances),
                                      np.asarray(own))
        np.testing.assert_array_equal(np.asarray(km.cluster_sizes),
                                      np.asarray(sizes))


def _spectrum_matrix(seed, n):
    """A symmetric n x n matrix with eigenvalues n, n-1, ..., 1 (every gap
    1) in a random basis, and its float64 eigenpairs in descending order."""
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(n, n)))
    a = (q * np.arange(n, 0, -1.0)) @ q.T
    w, v = np.linalg.eigh(a)
    return a.astype(np.float32), w[::-1], v[:, ::-1]


def _pca_pairs(monkeypatch, n, d):
    """``_pca_exact`` on the host path (Gram branch when n <= d, else the
    covariance branch) and the float64 top eigenpairs of the covariance."""
    from repro.core import selection as S
    monkeypatch.setattr(S, "_top_eigh", S._host_top_eigh)
    rng = np.random.default_rng(n * d)
    scale = np.concatenate([np.linspace(3.0, 1.5, 6),
                            np.linspace(0.3, 0.05, 6)])
    x = (rng.normal(size=(n, 12)) * scale) @ rng.normal(size=(12, d)) \
        + 1e-3 * rng.normal(size=(n, d))
    xc = x - x.mean(0)
    w, comps = jax.jit(lambda m: S._pca_exact(m, float(n), 6))(
        jnp.asarray(xc, jnp.float32))
    w64, v64 = np.linalg.eigh(xc.T @ xc / n)
    return [(w, comps.T, w64[::-1][:6], v64[:, ::-1][:, :6])]


class TestHostTopEigh:
    """The TPU's eigh (``_host_top_eigh``: the host's LAPACK through
    ``jax.pure_callback``), driven on the CPU: descending eigenvalues that
    match float64 to float32 rounding, and the same top-p subspace, under
    every transformation the selection program applies to it."""

    @pytest.mark.parametrize("case", ["jit", "vmap", "lax_map", "gram",
                                      "cov", "p_n_minus_1"])
    def test_matches_float64_eigh(self, case, monkeypatch):
        from repro.core.selection import _host_top_eigh
        n, p = 40, 7
        if case == "p_n_minus_1":
            p = n - 1
        if case in ("gram", "cov"):
            pairs = _pca_pairs(monkeypatch,
                               *((30, 50) if case == "gram" else (60, 20)))
        else:
            mats = [_spectrum_matrix(s, n) for s in range(3)]
            stack = jnp.asarray(np.stack([a for a, _, _ in mats]))
            top = lambda m: _host_top_eigh(m, p)        # noqa: E731
            if case == "vmap":
                w, v = jax.jit(jax.vmap(top))(stack)
            elif case == "lax_map":
                w, v = jax.jit(lambda s: jax.lax.map(top, s))(stack)
            else:
                w, v = jax.jit(top)(stack[0])
                w, v, mats = w[None], v[None], mats[:1]
            pairs = [(w[i], v[i], w64[:p], v64[:, :p])
                     for i, (_, w64, v64) in enumerate(mats)]
        for w, v, w64, v64 in pairs:
            w, v = np.asarray(w, np.float64), np.asarray(v, np.float64)
            assert w.shape == w64.shape and v.shape == v64.shape
            assert (np.diff(w) < 0).all()
            np.testing.assert_allclose(w, w64, rtol=0,
                                       atol=1e-5 * abs(w64[0]))
            gap = np.linalg.norm(v @ v.T - v64 @ v64.T)
            assert gap < 1e-4, gap

    def test_host_path_picks_the_default_paths_rows(self, monkeypatch):
        """``select_metadata`` with the host eigh picks exactly the rows of
        the default (``jnp.linalg.eigh``) path: eigenvector signs do not
        reach the picks, which depend only on distances in the projected
        space. The host path's ``eigh`` span shows it ran. (Seed 1 of the
        fixture holds a near-tie slot whose pick moves with float rounding:
        a float64 eigh's picks differ there from the default path's too.)"""
        from repro import obs
        from repro.core import selection as S
        acts, labels = structured_acts(0)
        kw = dict(num_classes=4, clusters_per_class=5, pca_components=16,
                  kmeans_iters=10)

        def pick():   # a fresh function each call, so jit traces anew
            return jax.jit(lambda a, l: S.select_metadata.fn(
                a, l, KEY, **kw))(acts, labels)

        want = pick()
        monkeypatch.setattr(S, "_top_eigh", S._host_top_eigh)
        tracer = obs.Tracer()
        with obs.use_tracer(tracer):
            got = pick()
        assert [s.attrs["n"] for s in tracer.spans if s.name == "eigh"] \
            == [min(acts.shape[0], acts[0].size)]
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.asarray(want.indices))
        np.testing.assert_array_equal(np.asarray(got.valid),
                                      np.asarray(want.valid))
