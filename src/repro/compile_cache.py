"""JAX's persistent compilation cache, placed for the entry points.

A cold mapping at 10^5 vertices compiles for tens of seconds per partition
shape. The entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``) call :func:`use_compile_cache` once at start-up so a
second run in the same checkout reads those programs back. The library
never calls it at import and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# cached entry is found by; a moving path would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the compilation-cache directory this process uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to :data:`DEFAULT_DIR`,
    and the variable is exported so spawned worker processes share it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path
