"""Pallas TPU kernel: matmul-form segmented inclusive scan.

Paper mapping (Dakkak et al. ICS'19, Alg. 6 / Fig. 9), TPU-adapted:

* ``A @ U`` (U = upper-triangular ones) scans each row of a tile — one MXU
  pass scans ``block_s`` segments x ``block_n`` elements.
* The tile-to-tile carry ``S ← Broadcast(R[last])`` is one more matmul:
  ``carry = R @ E`` with ``E[n, m] = 1 iff n == last`` replicates the last
  column of R across all lanes (the paper's Broadcast(LastColumn(R)),
  Algorithm 6 line 11 / footnote 5).
* On the V100 the serial carry forced decoupled-lookback-style machinery at
  scale; TPU Pallas grids are sequential per core, so the carry is simply a
  VMEM scratch accumulator along the innermost grid dimension.

Layout: row-major ``x (s, n)``; grid (s/block_s, n/block_n) with chunks
innermost-sequential. The block geometry is caller-supplied (a resolved
``TuneSpec``); defaults live in ``repro.kernels.layout``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.layout import LANES, MXU_PRECISION, SUBLANES, default_tuning


def _scan_kernel(x_ref, o_ref, carry_ref, *, nchunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = x_ref[...]                                   # rows = segments
    bn = a.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    u = (rows <= cols).astype(a.dtype)               # upper-triangular ones
    au = jax.lax.dot_general(
        a, u, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    ) + carry_ref[...]
    o_ref[...] = au.astype(o_ref.dtype)

    @pl.when(j != nchunks - 1)
    def _carry():
        # Broadcast(LastColumn(R)): E has ones only in the last row.
        e = (rows == bn - 1).astype(jnp.float32)
        carry_ref[...] = jax.lax.dot_general(
            au, e, (((1,), (0,)), ((), ())),
            precision=MXU_PRECISION, preferred_element_type=jnp.float32
        )


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_n", "interpret"))
def tcu_segmented_scan_tn(x: jax.Array, *, block_s: int | None = None,
                          block_n: int | None = None,
                          interpret: bool = False) -> jax.Array:
    """Inclusive scan along the last axis: (s, n) -> (s, n) in f32.

    ``s % block_s == 0`` and ``n % block_n == 0`` (wrapper pads);
    ``block_s`` must be a sublane multiple and ``block_n`` a lane
    multiple; rows are independent segments.
    """
    spec = default_tuning("tpu", "scan")
    block_s = block_s or spec["block_s"]
    block_n = block_n or spec["block_n"]
    s, n = x.shape
    if block_s % SUBLANES or block_n % LANES:
        raise ValueError(
            f"blocks {(block_s, block_n)} must be multiples of "
            f"{(SUBLANES, LANES)}")
    if n % block_n or s % block_s:
        raise ValueError(
            f"dims must be multiples of {(block_s, block_n)}, got "
            f"{x.shape}")
    nchunks = n // block_n
    return pl.pallas_call(
        functools.partial(_scan_kernel, nchunks=nchunks),
        grid=(s // block_s, nchunks),
        in_specs=[pl.BlockSpec((block_s, block_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_s, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_s, block_n), jnp.float32)],
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tcu_segmented_scan",
    )(x)
