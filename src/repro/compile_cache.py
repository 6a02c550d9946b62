"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` from their ``main()`` (never
at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing else is set.  Otherwise the cache lives in ``.jax_cache/``
at the repository root: a fixed path, because the directory is part of the
cache key — a temporary or per-process directory would never hit.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
