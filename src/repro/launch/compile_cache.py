"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point calls `enable_compile_cache()` before it compiles.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
nothing else; otherwise the cache lives in ``<repo>/.jax_cache`` (ignored
by git).  The directory is part of what makes an entry findable again, so
it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
