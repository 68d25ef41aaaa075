"""JAX's persistent compilation cache for the entry points.

A cold run compiles every simulator runner and fused kernel it touches;
the persistent cache lets the next run of the same checkout load them
instead. The cache key includes the directory, so it lives at a fixed
path: ``.jax_cache/`` at the checkout root (git-ignored), unless
``JAX_COMPILATION_CACHE_DIR`` places it elsewhere. Only entry points call
:func:`enable_compile_cache`; importing a module never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the default cache directory: ``.jax_cache/`` at the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps the cache
    there and nothing is set here; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
