"""Where JAX keeps its persistent compilation cache.

Every entry point (``repro.launch.train``, ``repro.launch.serve``, the
cluster worker, ``chip_smoke.py``) calls :func:`configure_compile_cache`
before its first compile.  The cache directory is part of the cache's key,
so it must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code; JAX reads
    the variable itself.
  * otherwise: ``<checkout>/.jax_cache``, one fixed directory per checkout
    (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root is three levels up
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; return that path."""
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
