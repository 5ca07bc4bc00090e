"""Pallas-Triton kernel: blocked (flash) attention with GQA + sliding window
(GPU twin of ``repro.kernels.flash_attention``).

Same online-softmax algebra as the TPU twin — the denominator update
``l += rowsum(exp(S − m))`` rides the tensor core as ``p @ 1`` (the paper's
P-matrix reduction); only the row-max stays a vector reduction (max has no
matmul form).

GPU restructure: the TPU twin walks kv blocks along an innermost
*sequential* grid dimension with VMEM scratch carries; CUDA grids are
parallel, so here each program owns one (batch, q-head, q-block) and walks
the kv blocks with an in-kernel ``fori_loop``, carrying ``(m, l, acc)`` in
registers. Block-level causal/window skipping becomes loop-bound
arithmetic: the loop runs ``[lo, hi)`` where ``hi`` clips fully-future kv
blocks (causal) and ``lo`` clips fully-expired ones (sliding window) —
the same work-skipping as the TPU twin's ``pl.when`` visibility test.

Grid: ``(B, Hq, Lq/BLOCK_Q)``; GQA via the k/v index maps (q head h reads
kv head ``h // rep``), no repeated-KV materialisation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import MMA_TILE as TILE
from repro.kernels.layout import default_tuning

NEG_INF = float(-1e30)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                  window: int | None, bq: int, bk: int, nk: int, offs: int):
    iq = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)               # (BQ, D)
    q_lo = iq * bq + offs                            # q rows in k coordinates
    q_hi = q_lo + bq - 1

    # block-granular visibility as loop bounds (TPU twin: pl.when per block)
    hi = jnp.minimum(nk, q_hi // bk + 1) if causal else nk
    lo = jnp.maximum(0, (q_lo - window + 1) // bk) if window is not None \
        else 0

    def body(jk, carry):
        m_prev, l_prev, acc = carry
        ksl = (pl.dslice(jk * bk, bk), slice(None))
        k = k_ref[ksl].astype(jnp.float32)  # (BK, D)
        v = v_ref[ksl].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # (BQ, BK)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))      # (BQ,)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)[:, None]               # (BQ, 1)
        # l update: rowsum(p) in matmul form (p @ 1) — paper's P-reduction.
        ones = jnp.ones((bk, TILE), jnp.float32)
        psum = jax.lax.dot_general(
            p, ones, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BQ, TILE)
        l_new = corr * l_prev + psum
        acc = corr * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, TILE), jnp.float32)
    acc0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))

    l1 = jnp.max(l, axis=1, keepdims=True)           # lanes identical
    safe = jnp.where(l1 > 0.0, l1, 1.0)
    o_ref[...] = (acc / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "num_warps", "num_stages", "interpret"),
)
def triton_flash_attention(
    q: jax.Array,       # (B, Hq, Lq, D)
    k: jax.Array,       # (B, Hkv, Lk, D)
    v: jax.Array,       # (B, Hkv, Lk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    num_warps: int | None = None,
    num_stages: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    spec = default_tuning("gpu", "attention")
    block_q = block_q or spec["block_q"]
    block_k = block_k or spec["block_k"]
    bsz, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    rep = hq // hkv
    if lq % block_q or lk % block_k:
        raise ValueError(f"seq lens {(lq, lk)} must tile {(block_q, block_k)}")
    if d % TILE:
        raise ValueError(f"head dim {d} must be a multiple of {TILE}")
    scale_v = scale if scale is not None else 1.0 / (d ** 0.5)
    nk = lk // block_k
    offs = lk - lq  # align sequence ends (prefill: 0; decode chunks: >0)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale_v, causal=causal, window=window,
            bq=block_q, bk=block_k, nk=nk, offs=offs,
        ),
        grid=(bsz, hq, lq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, lk, d),
                         lambda b, h, i, rep=rep: (b, h // rep, 0, 0)),
            pl.BlockSpec((None, None, lk, d),
                         lambda b, h, i, rep=rep: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, hq, lq, d), q.dtype),
        compiler_params=backend.compiler_params(
            backend="gpu",
            num_warps=num_warps or spec["num_warps"],
            num_stages=num_stages or spec["num_stages"]),
        interpret=interpret,
        name="triton_flash_attention",
    )(q, k, v)
