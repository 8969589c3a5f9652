"""The persistent compilation cache is placed from outside: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path in the checkout;
and importing ``repro`` places nothing."""
import contextlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import DEFAULT_DIR, ENV, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def _restored_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_env_dir_receives_the_entries_and_nothing_else(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv(ENV, str(tmp_path))
    before = _listing(DEFAULT_DIR)
    with _restored_cache_config():
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 3.0 - 1.0)(jnp.arange(7.0)).block_until_ready()
    assert os.listdir(tmp_path), "no cache entry was written"
    assert _listing(DEFAULT_DIR) == before


def test_unset_env_uses_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(ENV, raising=False)
    with _restored_cache_config():
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert DEFAULT_DIR == type(DEFAULT_DIR)(REPO) / ".jax_cache"


def test_importing_repro_places_no_cache():
    env = {k: v for k, v in os.environ.items() if k != ENV}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import jax, repro, repro.core, repro.fl.simulation, "
            "repro.kernels.ops; print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"
