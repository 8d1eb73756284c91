"""JAX's persistent compilation cache, placed from outside.

Every entry point (``chip_smoke.py``, ``launch/serve.py``, the examples,
the benchmarks) calls ``enable()`` before its first compile, so a second
run from the same checkout reads back what the first compiled. The cache
path is part of what a cached executable is found by, so it never moves
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself and nothing here overrides it), otherwise
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
