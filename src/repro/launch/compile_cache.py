"""JAX's persistent compilation cache for the entry points that compile
real-size programs (``chip_smoke.py``, ``python -m repro.launch.train``,
``benchmarks/kernel_bench.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives in ``<repo>/.jax_cache`` —
a fixed path (the path is part of what lets a later process find the
entries), listed in ``.gitignore``.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
