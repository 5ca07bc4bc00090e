"""Pallas-Triton kernel: Mamba-2 SSD chunked scan (GPU twin of
``repro.kernels.ssd_scan``) — the paper's scan, decay-weighted.

Same algebra as the TPU twin: intra-chunk ``(C Bᵀ ∘ M) @ X`` with
``M = exp(segsum(λ))`` a weighted lower-triangle (λ ≡ 0, N = P = 1 recovers
the paper's plain tile scan), and the chunk-state recurrence
``H_k = exp(Σλ)·H_{k-1} + S_k`` as the carry.

GPU restructure: the carry cannot ride a sequential grid dimension (CUDA
grids are parallel), so each program owns one folded (batch·head) row and
walks its chunks with an in-kernel ``fori_loop``, holding H (N, P) in
registers. The within-chunk cumulative decay Λ stays matmul-form (λ @ U),
broadcast to a 16-row fragment so the MMA shape is legal (tl.dot needs
M ≥ 16); all 16 result rows are identical and collapse without arithmetic.

Grid: ``(B·H,)``; the default chunk length (two tensor-core fragments)
lives in ``repro.kernels.layout`` — registers, not VMEM, bound the chunk
size here, and the caller supplies it (a resolved ``TuneSpec``) along
with the launch shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import MMA_TILE as TILE
from repro.kernels.layout import default_tuning


def _ssd_kernel(xdt_ref, lam_ref, b_ref, c_ref, y_ref, state_ref, *,
                q: int, nchunks: int, nstate: int, hdim: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    u = (rows <= cols).astype(jnp.float32)

    def body(jc, h):
        tsl = pl.dslice(jc * q, q)
        xdt = xdt_ref[tsl, :].astype(jnp.float32)  # (Q,P)
        lam = lam_ref[tsl].astype(jnp.float32)     # (Q,)
        bmat = b_ref[tsl, :].astype(jnp.float32)   # (Q,N)
        cmat = c_ref[tsl, :].astype(jnp.float32)   # (Q,N)

        # Λ = λ @ U in matmul form, on a 16-row fragment (rows identical).
        lam16 = jnp.broadcast_to(lam[None, :], (TILE, q))
        cum16 = jax.lax.dot_general(
            lam16, u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (16, Q)
        cum = jnp.max(cum16, axis=0)                         # (Q,)
        total = jnp.sum(lam)                                 # Σ_chunk λ

        # M[t, τ] = exp(Λ_t − Λ_τ) for τ ≤ t  (weighted L+I mask)
        diff = cum[:, None] - cum[None, :]
        m = jnp.where(rows >= cols, jnp.exp(diff), 0.0)      # (Q, Q)

        # Intra-chunk: Y = ((C Bᵀ) ∘ M) @ (dt∘X)
        cb = jax.lax.dot_general(
            cmat, bmat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Q, Q)
        y = jax.lax.dot_general(
            cb * m, xdt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Q, P)

        # Inter-chunk: Y += (C ∘ exp(Λ)) @ H_prev
        y += jax.lax.dot_general(
            cmat * jnp.exp(cum)[:, None], h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y_ref[tsl, :] = y

        # State update: H = exp(Σλ)·H + (B ∘ w)ᵀ @ (dt∘X), w_τ = exp(Σλ − Λ_τ)
        bw = bmat * jnp.exp(total - cum)[:, None]            # (Q, N)
        s_new = jax.lax.dot_general(
            bw, xdt, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (N, P)
        return jnp.exp(total) * h + s_new

    h = jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((nstate, hdim), jnp.float32))
    state_ref[...] = h


@functools.partial(jax.jit, static_argnames=("q", "num_warps", "num_stages",
                                             "interpret"))
def triton_ssd_chunk_scan(
    xdt: jax.Array,     # (BH, L, P)  dt-weighted inputs, P % 16 == 0 (padded)
    lam: jax.Array,     # (BH, L)     per-step log decay  a_h · dt
    b: jax.Array,       # (BH, L, N)  N % 16 == 0 (padded)
    c: jax.Array,       # (BH, L, N)
    *,
    q: int | None = None,
    num_warps: int | None = None,
    num_stages: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (BH, L, P) f32, final_state (BH, N, P))."""
    spec = default_tuning("gpu", "ssd")
    q = q or spec["q"]
    bh, seqlen, hdim = xdt.shape
    nstate = b.shape[-1]
    if seqlen % q:
        raise ValueError(f"L={seqlen} must be a multiple of {q}")
    if nstate % TILE or hdim % TILE:
        raise ValueError(
            f"N={nstate}, P={hdim} must be multiples of {TILE} (MMA shape)")
    nchunks = seqlen // q
    return pl.pallas_call(
        functools.partial(_ssd_kernel, q=q, nchunks=nchunks,
                          nstate=nstate, hdim=hdim),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((None, seqlen, hdim), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, seqlen), lambda i: (i, 0)),
            pl.BlockSpec((None, seqlen, nstate), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, seqlen, nstate), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, seqlen, hdim), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, nstate, hdim), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seqlen, hdim), jnp.float32),
            jax.ShapeDtypeStruct((bh, nstate, hdim), jnp.float32),
        ],
        compiler_params=backend.compiler_params(
            backend="gpu",
            num_warps=num_warps or spec["num_warps"],
            num_stages=num_stages or spec["num_stages"]),
        interpret=interpret,
        name="triton_ssd_chunk_scan",
    )(xdt, lam, b, c)
