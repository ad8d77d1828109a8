"""JAX's persistent compilation cache, turned on by the entry points.

Call :func:`enable` from a program's ``main`` (never at import). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
names another directory; otherwise the cache lives at one fixed path inside
the checkout (``.jax_cache``, git-ignored). The path is part of the cache's
key, so it never comes from a temp name, a pid or the clock.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
#: The checkout's own cache directory (used when ``ENV`` is unset).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
