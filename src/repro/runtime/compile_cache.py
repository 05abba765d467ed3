"""JAX's persistent compilation cache, one rule for every entry point.

A whole MobileNet body compiles for tens of seconds on the TPU; a second
process with the cache on skips that.  The cache key includes the cache
directory, so the directory must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, so nothing is set here), else the fixed, git-ignored
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

#: The in-checkout default (``src/repro/runtime`` -> the checkout root).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
