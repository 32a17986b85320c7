"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` before their first compile.
``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX reads it on
its own; otherwise the cache lives at a fixed path inside the checkout,
``<repo>/.jax_cache``.  The path is part of every cache key, so it is never
derived from a temp name, a process id or the time.  Tests do not call this.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
