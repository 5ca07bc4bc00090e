"""Pallas-Triton kernel: RMSNorm with a matmul-form sum-of-squares (GPU twin
of ``repro.kernels.fused_rmsnorm``).

Same algebra as the TPU twin: the row reduction is fed through the tensor
core as ``(x∘x) @ 1`` with the all-ones RHS doubling as the lane broadcast
(every output lane holds the row's sum of squares, so no cross-lane shuffle
is needed before the elementwise normalisation — the effect the V100 paper
needed Listing-3 layout hacks for).

GPU restructure: a (128, 8192) f32 row block does not fit in a CTA's
registers, so the kernel makes two passes over the feature dim in
``block_d`` chunks — pass 1 accumulates the chained sum-of-squares MMA,
pass 2 re-reads x (L2-hot) and writes the normalised output. Unlike the TPU
twin, the feature dim may be zero-padded: the true ``d`` is a separate
static divisor, so Σx² over the padded row is exact.

Grid: ``(rows / row_block,)``. The block geometry and launch shape are
caller-supplied (a resolved ``TuneSpec``, clamped against the actual
feature dim by the glue — a ``block_d`` wider than the padded row shrinks
to fit instead of crashing); defaults live in ``repro.kernels.layout``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import MMA_TILE as TILE
from repro.kernels.layout import default_tuning


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, d: int,
                    block_d: int, nchunks: int):
    ones = jnp.ones((block_d, TILE), jnp.float32)

    def ssq_body(k, acc):
        xx = x_ref[:, pl.dslice(k * block_d, block_d)].astype(jnp.float32)
        # (x∘x) @ 1 : matmul-form row reduction, lanes replicated
        return acc + jax.lax.dot_general(
            xx * xx, ones, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    ssq = jax.lax.fori_loop(
        0, nchunks, ssq_body,
        jnp.zeros((x_ref.shape[0], TILE), jnp.float32))
    # lanes are identical; collapse without arithmetic, divide by the TRUE d
    rstd = jax.lax.rsqrt(jnp.max(ssq, axis=1, keepdims=True) / d + eps)

    def norm_body(k, _):
        sl = (slice(None), pl.dslice(k * block_d, block_d))
        xx = x_ref[sl].astype(jnp.float32)
        w = w_ref[:, sl[1]].astype(jnp.float32)  # (1, BD)
        o_ref[sl] = (xx * rstd * w).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nchunks, norm_body, 0)


@functools.partial(jax.jit, static_argnames=("eps", "d", "block_r",
                                             "block_d", "num_warps",
                                             "num_stages", "interpret"))
def triton_fused_rmsnorm(
    x: jax.Array, w: jax.Array, *, eps: float = 1e-6, d: int | None = None,
    block_r: int | None = None, block_d: int | None = None,
    num_warps: int | None = None, num_stages: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """RMSNorm rows of ``x (rows, d_pad)`` by ``w (d_pad,)``.

    ``rows % block_r == 0`` and ``d_pad % block_d == 0`` (wrapper pads the
    feature dim with zeros and passes the true feature count as ``d``).
    """
    spec = default_tuning("gpu", "rmsnorm")
    block_r = block_r or spec["row_block"]
    block_d = block_d or spec["block_d"]
    rows, d_pad = x.shape
    if d is None:
        d = d_pad
    if rows % block_r or d_pad % block_d:
        raise ValueError(
            f"shape {x.shape} must tile {(block_r, block_d)}")
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, d=d, block_d=block_d,
                          nchunks=d_pad // block_d),
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d_pad), x.dtype),
        compiler_params=backend.compiler_params(
            backend="gpu",
            num_warps=num_warps or spec["num_warps"],
            num_stages=num_stages or spec["num_stages"]),
        interpret=interpret,
        name="triton_fused_rmsnorm",
    )(x, w.reshape(1, d_pad))
