"""Persistent XLA compilation cache, placed from outside or at a fixed path.

Every entry point that compiles (``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile, so a second process on the same machine reuses the
first one's executables instead of compiling from cold.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set here.
* otherwise: ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
  cache key, so it is fixed — never a temp name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
