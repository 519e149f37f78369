"""Where JAX keeps its persistent compilation cache.

A directory given from outside in ``JAX_COMPILATION_CACHE_DIR`` wins:
JAX reads that variable itself and this module sets nothing. Otherwise
the cache goes to ``<repo>/.jax_cache`` — a fixed path, since the path
is part of what makes a later run find the entries again. Call
``use_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX at its compile cache; returns the directory in use."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
