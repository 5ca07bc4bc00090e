"""Plain reference of the paper's primitives: segmented sum and inclusive
prefix sum over the last axis, in float64 on the host (numpy only).

An output's error is measured in units of float32 roundoff times the sum
of the magnitudes it adds up (``u * sum |x_j|``, the scale of the classic
bound on a float32 summation's error): a float32 sum at full precision
reads a few units, while one whose inputs were rounded to fewer bits
reads many, at the first positions of a scan above all.
"""
from __future__ import annotations

import numpy as np

U = 2.0 ** -24      # float32 unit roundoff


def reference(op: str, x: np.ndarray) -> np.ndarray:
    x64 = np.asarray(x, np.float64)
    if op == "reduce":
        return x64.sum(axis=-1)
    if op == "scan":
        return np.cumsum(x64, axis=-1)
    raise ValueError(f"unknown op {op!r}")


def err_units(op: str, x: np.ndarray, out: np.ndarray) -> float:
    """``max |out - ref| / (u * sum |x_j|)`` over the outputs, the sum
    over what each output adds up; a non-finite or misshapen output reads
    infinite."""
    want = reference(op, x)
    got = np.asarray(out, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    mag = np.abs(np.asarray(x, np.float64))
    scale = mag.sum(axis=-1) if op == "reduce" else np.cumsum(mag, axis=-1)
    return float(np.max(np.abs(got - want) / (U * np.maximum(scale, 1e-300))))


def lower_precision(op: str, x):
    """The control: the reference on the device in float32 with its input
    rounded as a ``high`` (three-pass bf16) matmul rounds it, to the sum of
    two bfloat16 parts."""
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    xr = hi.astype(jnp.float32) + lo.astype(jnp.float32)
    return jnp.sum(xr, axis=-1) if op == "reduce" else jnp.cumsum(xr, -1)
