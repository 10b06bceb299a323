"""The persistent JAX compilation cache of the entry points.

``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` once at start;
library modules never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing else is set.  Otherwise the cache lives
at the fixed ``<repo>/.jax_cache``: the path is part of the cache key,
so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory it lives in."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.set_cache_dir(str(REPO_CACHE))
    return str(REPO_CACHE)
