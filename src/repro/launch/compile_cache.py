"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

The cache key includes the directory, so a path that moves between runs
(a temp dir, a pid or a timestamp) never hits. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing; otherwise the cache lives in ``<repo>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
