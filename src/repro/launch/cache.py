"""Persistent compilation cache for the command-line entry points.

Each entry point calls :func:`enable_compile_cache` in its ``__main__``
block, before its first compile, so importing a module (or calling its
``main`` from a test) never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout and ignored by git: the directory is part of
# the cache key, so a path that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
