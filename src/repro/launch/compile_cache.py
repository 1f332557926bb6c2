"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
