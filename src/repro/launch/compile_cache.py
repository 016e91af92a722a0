"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` from their ``main()`` (never at
import), before the first compile.  The cache key includes the directory,
so the path is fixed: a run from the same checkout finds what an earlier
run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: this file is <repo>/src/repro/launch/compile_cache.py
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other path.  Otherwise the cache goes to ``CACHE_DIR``
    inside the checkout (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "enable_compile_cache"]
