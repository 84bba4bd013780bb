"""JAX's persistent compilation cache, in one fixed place.

Entry points (`launch.train`, `launch.serve`, `benchmarks.run`,
`chip_smoke.py`) call `enable_compile_cache()` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the repository
root: the directory is part of the cache key, so it never depends on a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
