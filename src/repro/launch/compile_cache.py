"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before they touch
JAX; importing a library module never does.  A full-width step program
takes tens of seconds to compile, and a second run of the same program then
loads it from disk instead.
"""
from __future__ import annotations

import os
from pathlib import Path

# The checkout root: src/repro/launch/ -> three levels up.  The cache path is
# part of the cache key, so it stays fixed rather than following the cwd.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it lands in.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set; otherwise the cache is ``.jax_cache/`` in the
    checkout.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
