"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py`` and ``benchmarks/serving_bench.py`` call
:func:`enable` once at start-up; library code and tests never do. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
directory is set here. Otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the cache directory
is part of what a later process must find, so it is never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    cache_dir = os.environ.get(ENV_DIR) or str(DEFAULT_DIR)
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program, not only those over JAX's 1 s default: a cold
    # process compiles dozens of kernels and steps that each take less
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
