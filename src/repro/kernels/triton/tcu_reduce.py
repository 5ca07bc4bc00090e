"""Pallas-Triton kernel: matmul-form segmented reduction (GPU twin of
``repro.kernels.tcu_reduce``).

Paper mapping (Dakkak et al. ICS'19, Alg. 3), GPU-adapted per the
tensor-core reduction follow-ups (arXiv:1903.03640, arXiv:2001.05585):

* The paper loads tiles column-major so 16 segments fill the 16 rows of a
  WMMA fragment and one ``P @ A`` pass reduces all of them. On the GPU we
  keep the natural row-major layout (rows = segments, coalesced loads) and
  put the ones vector on the *right*: ``A @ 1`` sums each fragment row —
  the transpose of the paper's P-matrix trick, same MMA work.
* The work-efficient chained accumulation ``V_i = A_i·1 + V_{i-1}`` is an
  in-kernel ``fori_loop`` over column chunks with the accumulator in
  registers. CUDA grids have no sequential-dimension semantics (unlike TPU
  Pallas grids), so the carry cannot live in a grid-walked scratch buffer —
  every chained MMA happens inside one program.
* The ones RHS is ``(BLOCK_N, 16)``: 16 lanes is the tensor-core fragment
  edge, and replicating the row sums across all 16 output lanes costs
  nothing while keeping every ``jnp.dot`` shape MMA-legal (tl.dot needs
  M, N, K >= 16).

Grid: ``(S / block_s,)`` — segment blocks parallel across CTAs. The block
geometry and launch shape (``num_warps``/``num_stages``) are
caller-supplied (a resolved ``TuneSpec``); defaults live in
``repro.kernels.layout``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import MMA_TILE as TILE
from repro.kernels.layout import default_tuning


def _reduce_kernel(x_ref, o_ref, *, block_s: int, block_n: int, nchunks: int):
    ones = jnp.ones((block_n, TILE), jnp.float32)

    def body(k, acc):
        a = x_ref[:, pl.dslice(k * block_n, block_n)]
        # A @ 1 : every output lane holds the row (segment) sums.
        return acc + jax.lax.dot_general(
            a.astype(jnp.float32), ones, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((block_s, TILE), jnp.float32))
    # all TILE lanes are identical; max-collapse is a shuffle, not arithmetic
    o_ref[...] = jnp.max(acc, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_n", "num_warps",
                                    "num_stages", "interpret"))
def triton_segmented_reduce(x: jax.Array, *, block_s: int | None = None,
                            block_n: int | None = None,
                            num_warps: int | None = None,
                            num_stages: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """Reduce rows of ``x``: (s, n) -> (s,) f32. Rows are independent
    segments; ``s % block_s == 0`` and ``n % block_n == 0`` (wrapper pads).
    """
    spec = default_tuning("gpu", "reduce")
    block_s = block_s or spec["block_s"]
    block_n = block_n or spec["block_n"]
    s, n = x.shape
    if s % block_s or n % block_n:
        raise ValueError(
            f"dims must be multiples of {(block_s, block_n)}, got {x.shape}")
    return pl.pallas_call(
        functools.partial(_reduce_kernel, block_s=block_s, block_n=block_n,
                          nchunks=n // block_n),
        grid=(s // block_s,),
        in_specs=[pl.BlockSpec((block_s, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_s,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((s,), jnp.float32),
        compiler_params=backend.compiler_params(
            backend="gpu",
            num_warps=num_warps or spec["num_warps"],
            num_stages=num_stages or spec["num_stages"]),
        interpret=interpret,
        name="triton_segmented_reduce",
    )(x)
