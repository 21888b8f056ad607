"""Where JAX keeps its persistent compile cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/serve_clusters.py``) call :func:`use_compile_cache` once at
start-up; importing the package never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "DEFAULT_DIR"]

# A fixed path inside the checkout: the path is part of the cache key, so a
# directory that moved between runs would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is
    ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
