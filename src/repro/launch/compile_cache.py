"""Persistent XLA compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is changed here.  Otherwise the cache goes to ``.jax_cache``
inside the checkout: a fixed path, because the path is part of what a
later run must find again.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start;
importing ``repro`` never sets a cache.
"""

from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
