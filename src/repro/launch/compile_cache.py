"""JAX's persistent compilation cache, switched on by every entry point.

A fresh process otherwise recompiles every program it runs. The cache key
includes the directory, so it must not move between runs: the directory is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), else ``.jax_cache/`` at the root of
this checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compiled program and return
    its directory."""
    # Kernels and small steps compile in well under the default one-second
    # floor; caching only slow compiles would leave most of a run cold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
