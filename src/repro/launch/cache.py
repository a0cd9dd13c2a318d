"""JAX's persistent compilation cache for the entry points.

Launchers, benchmarks and ``chip_smoke.py`` call
:func:`enable_compile_cache` from their ``main``; importing ``repro``
never does, so library users and tests keep JAX's own setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``<checkout>/src/repro/launch/cache.py``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so
    it never comes from a temp dir, a pid or the time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
