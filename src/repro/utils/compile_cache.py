"""JAX's persistent compile cache, kept in one fixed place.

Entry points call :func:`enable` once, before they compile anything.
The cache directory is part of what makes a hit, so it never moves:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins
  — nothing here names another directory;
* otherwise the cache lives at ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; return its directory."""
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    # kernels compile in well under JAX's default 1 s floor; keep them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
