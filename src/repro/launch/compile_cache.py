"""Persistent XLA compilation cache for the command-line entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once at start-up;
library code and tests never do.  When ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and this sets nothing.  Otherwise the cache
lives at the fixed ``<repo>/.jax_cache``: the directory is part of the
cache key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
