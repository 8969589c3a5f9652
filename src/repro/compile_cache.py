"""JAX's persistent compilation cache, placed for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/serve_fl.py``, ``examples/*.py``) call ``enable_compile_cache()``
once at start-up; importing ``repro`` never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
no other directory. Otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, ignored by git): the path is part of
what a later run must find again, so it is never a temp name, pid or
time."""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` or, when that is unset, at
    ``<repo>/.jax_cache``. Returns the directory."""
    import jax
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
