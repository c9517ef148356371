"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` before the first compile.  The cache
lives in ``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise in
``<repo>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
