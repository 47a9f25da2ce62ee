"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/query_path.py``) call ``enable_compile_cache()`` once at start;
library code never does. The cache key includes the directory, so the path
is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
