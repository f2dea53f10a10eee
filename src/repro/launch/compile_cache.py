"""JAX's persistent compilation cache for the repo's entry points.

Scripts call :func:`enable_compile_cache` once, before their first compile;
library code and tests never do. When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing else is configured; otherwise the cache is
``<checkout>/.jax_cache``. The path is part of every entry's key, so it is
fixed rather than per-run: a second process in the same checkout hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the serving path's kernels compile in well under
    # JAX's default 1 s threshold, yet a cold start recompiles all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
