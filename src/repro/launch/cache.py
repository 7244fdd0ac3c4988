"""JAX's persistent compilation cache for the launchers.

Call :func:`enable_compilation_cache` once at program start, never when a
module is imported.
"""
from __future__ import annotations

import os

import jax

#: the cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path
#: inside the checkout (the path is part of the cache key, so it must not
#: move between runs).
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on the persistent cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
