"""AOT compiles of the main path's Pallas kernels for a described TPU v5e,
at the widths the full-size WRN-40-1 round feeds them: a client's 2,500
split maps after PCA to 200 features, 10 classes x 10 clusters, and the
100 knowledge slots of 16x32x32 = 16,384 features each. Interpret mode
(every other kernel test) cannot see Mosaic's tiling and VMEM refusals;
the chip's compiler can, without a chip. Nothing runs, so these say
nothing about results or speed.

The topology is described inside a module-scoped fixture — never at
import — so every xdist worker collects the same tests and only the
worker given this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N, D, K = 2500, 200, 100          # one client's Lloyd problem (pre-padding)
SLOTS, FEAT = 100, 16 * 32 * 32   # one client's knowledge frame
COHORT = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer ops.py off interpret mode: the process's backend is the CPU,
    but these programs are compiled for the described TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, sharding, *shapes, batch=0):
    if batch:
        fn = jax.vmap(fn)
        shapes = [(batch,) + s for s in shapes]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [0, COHORT], ids=["client", "cohort"])
def test_lloyd_kernel_compiles(one_chip, mosaic, batch):
    hlo = _compile(ops.kmeans_lloyd_step, one_chip, (N, D), (K, D), (N, K),
                   batch=batch)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch", [0, COHORT], ids=["client", "cohort"])
def test_pairwise_kernel_compiles(one_chip, mosaic, batch):
    hlo = _compile(ops.kmeans_pairwise_dist, one_chip, (N, D), (K, D),
                   batch=batch)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch,feat", [(0, FEAT), (COHORT, FEAT), (0, 256)],
                         ids=["client", "cohort", "narrow"])
def test_quantize_kernel_compiles(one_chip, mosaic, batch, feat):
    hlo = _compile(lambda x, m: ops.quantize_affine(x, m > 0), one_chip,
                   (SLOTS, feat), (SLOTS,), batch=batch)
    assert "tpu_custom_call" in hlo


def test_exact_pca_compiles_to_native_eigh(one_chip):
    """The exact PCA of one client's maps (a 2500 x 2500 Gram) must lower
    to XLA's native Jacobi ``Eigh``: JAX's default TPU eigh (QDWH, traced
    into the program) takes minutes to compile at this size."""
    from repro.core.selection import _fit_features
    lowered = jax.jit(lambda a: _fit_features(a, 200, "exact")).lower(
        jax.ShapeDtypeStruct((N, FEAT), jnp.float32, sharding=one_chip))
    assert "custom_call @Eigh" in lowered.as_text()
    lowered.compile()
