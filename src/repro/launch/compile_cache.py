"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile.  A directory named by ``JAX_COMPILATION_CACHE_DIR`` wins (JAX
reads that variable itself, so nothing is set here); otherwise the cache
goes to ``<checkout>/.jax_cache``.  The path is part of every entry's
key, so it is fixed: a directory named after a temp dir, a pid or the
time would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
