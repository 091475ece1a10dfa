"""JAX's persistent compilation cache for the program's entry points.

A cache entry is found again only under the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), otherwise ``.jax_cache`` at the
root of the checkout. Call ``enable()`` before the first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory it writes to."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    # Cache every program, not only those that took over a second to
    # compile: the engine's many small traces add up on a cold start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
