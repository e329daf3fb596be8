"""Persistent XLA compilation cache at a fixed place.

A cold ResNet-50 compile is paid on every fresh process; JAX's persistent
cache pays it once per program.  The cache key includes the directory, so
the directory must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads it itself), else ``.jax_cache/`` at the root of
the checkout.  Entry points call `enable_compile_cache` from ``main()``;
nothing here runs at import time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "CACHE_DIR"]

# <checkout>/src/repro/utils/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
