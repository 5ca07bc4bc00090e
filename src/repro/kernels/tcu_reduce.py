"""Pallas TPU kernel: work-efficient matmul-form segmented reduction.

Paper mapping (Dakkak et al. ICS'19, Alg. 3 / Fig. 7), TPU-adapted:

* The paper loads tiles **column-major** so 16 segments occupy the 16 rows of
  a WMMA fragment and one ``P @ A`` reduces all of them. Our analogue: the
  wrapper feeds the kernel ``x`` transposed to ``(n, s)`` so one VMEM block
  holds ``block_n`` elements (sublanes) x ``block_s`` segments (lanes) and
  one ``P_8 @ A`` MXU pass reduces a whole lane-row of segments at once.
* The paper's work-efficient trick — accumulate ``V_i = P·A_i + V_{i-1}``
  across tiles, one matmul each, collapsing only at the end — is the
  sequential innermost grid dimension with a VMEM scratch accumulator.
* The f32 scratch is (8, block_s): the live data is the paper's "first row
  of V"; 8 sublanes is the f32 minimum tile. The redundant 7 rows cost
  nothing (the MXU streams M=8 in one pass) — reduction stays memory-bound,
  which is the paper's central observation.

Grid: ``(S/block_s, N/block_n)`` — segments parallel, chunks sequential
(innermost). The block geometry is caller-supplied (a resolved
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


def _reduce_kernel(x_ref, o_ref, acc_ref, *, nchunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = x_ref[...]                                   # (block_n, block_s)
    # P @ A with P = ones in row 0: realised as an (8, block_n) ones LHS —
    # every result row holds the column sums; row 0 is the paper's V row.
    p = jnp.ones((SUBLANES, a.shape[0]), a.dtype)
    acc_ref[...] += jax.lax.dot_general(
        p, a, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )

    @pl.when(j == nchunks - 1)
    def _store():
        o_ref[...] = acc_ref[0:1, :].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_n", "interpret"))
def tcu_segmented_reduce_tn(xt: jax.Array, *, block_s: int | None = None,
                            block_n: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """Reduce columns of ``xt``: (n, s) -> (s,). ``s % block_s == 0`` and
    ``n % block_n == 0`` (wrapper pads); ``block_s`` must be a lane
    multiple and ``block_n`` a sublane multiple.

    ``xt`` is the transposed segment matrix (the paper's col-major
    LoadTile).
    """
    spec = default_tuning("tpu", "reduce")
    block_s = block_s or spec["block_s"]
    block_n = block_n or spec["block_n"]
    n, s = xt.shape
    if block_s % LANES or block_n % SUBLANES:
        raise ValueError(
            f"blocks {(block_s, block_n)} must be multiples of "
            f"{(LANES, SUBLANES)}")
    if n % block_n or s % block_s:
        raise ValueError(
            f"dims must be multiples of {(block_n, block_s)}, got "
            f"{xt.shape}")
    nchunks = n // block_n
    return pl.pallas_call(
        functools.partial(_reduce_kernel, nchunks=nchunks),
        grid=(s // block_s, nchunks),
        in_specs=[pl.BlockSpec((block_n, block_s), lambda i, j: (j, i))],
        # the result is a (1, s) row: a 1-D (block_s,) block does not match
        # the T(1024) tiling XLA gives a long f32 vector on TPU
        out_specs=pl.BlockSpec((1, block_s), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, block_s), jnp.float32)],
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tcu_segmented_reduce",
    )(xt)[0]
