"""The Pallas-Triton (GPU) lowering's single import site.

This is the ONLY module in the repo allowed to import
``jax.experimental.pallas.triton`` — the same discipline the raw
compiler-params guard enforces for ``pltpu`` (a grep-guard test checks it).
Every Triton kernel builds its params through :func:`compiler_params` here
(usually via ``repro.kernels.backend.compiler_params(backend="gpu", ...)``).
"""
from __future__ import annotations

from typing import Any


def _plgpu():
    from jax.experimental.pallas import triton as plgpu

    return plgpu


def available() -> bool:
    """True when this JAX ships the Pallas-Triton lowering at all."""
    try:
        _plgpu()
        return True
    except ImportError:
        return False


def compiler_params(**kwargs: Any):
    """``plgpu.CompilerParams`` (``num_warps``/``num_stages``); an unknown
    field raises."""
    return _plgpu().CompilerParams(**kwargs)
