"""JAX's persistent compilation cache for the repo's entry points.

The cache key includes its directory, so a directory that moves between
runs never hits. ``JAX_COMPILATION_CACHE_DIR`` places the cache from
outside; without it, the cache lives at the fixed ``<repo>/.jax_cache``
(git-ignored). Entry points (``chip_smoke.py``, the sweep server, the
launcher worker, the benchmark driver) call :func:`use_compile_cache`;
library imports never do, so the test suite runs without a cache unless
the variable is set.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/core/compile_cache.py -> repo root
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`REPO_CACHE`."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
