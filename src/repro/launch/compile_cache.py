"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`enable_compile_cache` once, before their first compile. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here. Otherwise the cache goes to one fixed directory inside the
checkout (``.jax_cache/``, git-ignored): the cache key includes the
directory, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
