"""Pallas-Triton kernel: matmul-form segmented inclusive scan (GPU twin of
``repro.kernels.tcu_scan``).

Paper mapping (Dakkak et al. ICS'19, Alg. 6), GPU-adapted:

* ``A @ U`` (U = upper-triangular ones) scans each fragment row — one MMA
  pass scans BLOCK_S segments x BLOCK_N elements.
* The tile-to-tile carry ``S <- Broadcast(R[last])`` stays one more matmul:
  ``carry = R @ E`` with E ones only in the last row replicates the last
  column of R across every lane (Algorithm 6 line 11 / footnote 5).
* On the V100 the paper needed decoupled-lookback machinery because the
  serial carry crosses thread blocks; here each program owns its whole
  segment rows, so the carry is a register tensor threaded through an
  in-kernel ``fori_loop`` over column chunks — CUDA grid dimensions are
  parallel and cannot carry state (unlike the TPU twin's sequential grid +
  VMEM scratch).

Grid: ``(S / block_s,)``; layout row-major ``x (s, n)``, rows = segments.
The block geometry and launch shape are caller-supplied (a resolved
``TuneSpec``); defaults live in ``repro.kernels.layout``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import default_tuning


def _scan_kernel(x_ref, o_ref, *, block_s: int, block_n: int, nchunks: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
    u = (rows <= cols).astype(jnp.float32)       # upper-triangular ones
    e = (rows == block_n - 1).astype(jnp.float32)  # ones in the last row

    def body(k, carry):
        sl = (slice(None), pl.dslice(k * block_n, block_n))
        a = x_ref[sl].astype(jnp.float32)
        au = jax.lax.dot_general(
            a, u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + carry
        o_ref[sl] = au
        # Broadcast(LastColumn(R)) as R @ E — stays on the tensor core.
        return jax.lax.dot_general(
            au, e, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((block_s, block_n), jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_n", "num_warps",
                                    "num_stages", "interpret"))
def triton_segmented_scan(x: jax.Array, *, block_s: int | None = None,
                          block_n: int | None = None,
                          num_warps: int | None = None,
                          num_stages: int | None = None,
                          interpret: bool = False) -> jax.Array:
    """Inclusive scan along the last axis: (s, n) -> (s, n) f32.

    ``s % block_s == 0`` and ``n % block_n == 0`` (wrapper pads); rows are
    independent segments.
    """
    spec = default_tuning("gpu", "scan")
    block_s = block_s or spec["block_s"]
    block_n = block_n or spec["block_n"]
    s, n = x.shape
    if s % block_s or n % block_n:
        raise ValueError(
            f"dims must be multiples of {(block_s, block_n)}, got {x.shape}")
    return pl.pallas_call(
        functools.partial(_scan_kernel, block_s=block_s, block_n=block_n,
                          nchunks=n // block_n),
        grid=(s // block_s,),
        in_specs=[pl.BlockSpec((block_s, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_s, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.float32),
        compiler_params=backend.compiler_params(
            backend="gpu",
            num_warps=num_warps or spec["num_warps"],
            num_stages=num_stages or spec["num_stages"]),
        interpret=interpret,
        name="triton_segmented_scan",
    )(x)
