"""Pallas TPU kernel: Mamba-2 SSD chunked scan = the paper's scan, weighted.

The SSD ("state-space duality") computation is exactly the generalisation of
Dakkak et al.'s matmul-form scan from ones-triangles to decay-weighted
triangles:

* paper ``A @ U`` (intra-tile scan)   →  ``(C Bᵀ ∘ M) @ X`` with
  ``M = exp(segsum(λ))`` a *weighted* lower-triangular mask (λ = a·dt);
  with λ ≡ 0, N = P = 1, B = C = 1 this degenerates to the paper's tile scan.
* paper ``S ← Broadcast(R[last])`` (tile carry) → the chunk state recurrence
  ``H_k = exp(Σλ)·H_{k-1} + S_k`` carried in VMEM scratch along the
  sequential chunk grid dimension.
* paper grid-level scan-then-propagate → `repro.core.dist_weighted_scan`
  for sequence-parallel execution across devices (long_500k cells).

The within-chunk cumulative decay Λ is itself computed in matmul form
(``λ @ U``), so every reduction/scan in this kernel routes through the MXU.

Grid: ``(B·H, L/q)`` with chunks innermost-sequential; carry scratch (N, P)
f32 per (batch, head). The chunk length ``q`` is caller-supplied (a
resolved ``TuneSpec``; the default — one MXU edge — lives in
``repro.kernels.layout``). Second output: final state (for prefill →
decode handoff in serving).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.layout import LANES, MXU_PRECISION, default_tuning


def _ssd_kernel(xdt_ref, lam_ref, b_ref, c_ref, y_ref, state_ref, h_ref,
                *, nchunks: int, q: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xdt = xdt_ref[0].astype(jnp.float32)             # (q, P)  dt-weighted input
    lam = lam_ref[0].astype(jnp.float32)             # (1, q)  log decays
    bmat = b_ref[0].astype(jnp.float32)              # (q, N)
    cmat = c_ref[0].astype(jnp.float32)              # (q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    u = (rows <= cols).astype(jnp.float32)
    # Λ = λ @ U : inclusive cumulative log-decay, matmul-form (paper's A·U).
    cum = jax.lax.dot_general(
        lam, u, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                                # (1, q)
    total = jnp.sum(lam)                             # Σ_chunk λ (scalar)

    # M[t, τ] = exp(Λ_t − Λ_τ) for τ ≤ t  (weighted L+I mask)
    diff = cum[0][:, None] - cum[0][None, :]
    m = jnp.where(rows >= cols, jnp.exp(diff), 0.0)  # (q, q)

    # Intra-chunk: Y = ((C Bᵀ) ∘ M) @ (dt∘X)
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                                # (q, q)
    y = jax.lax.dot_general(
        cb * m, xdt, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                                # (q, P)

    # Inter-chunk: Y += (C ∘ exp(Λ)) @ H_prev
    cdec = cmat * jnp.exp(cum[0])[:, None]           # (q, N)
    y += jax.lax.dot_general(
        cdec, h_ref[...], (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32,
    )
    y_ref[0] = y.astype(y_ref.dtype)

    # State update: H = exp(Σλ)·H + (B ∘ w)ᵀ @ (dt∘X),  w_τ = exp(Σλ − Λ_τ)
    w = jnp.exp(total - cum[0])                      # (q,)
    bw = bmat * w[:, None]                           # (q, N)
    s_new = jax.lax.dot_general(
        bw, xdt, (((0,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                                # (N, P)
    h_ref[...] = jnp.exp(total) * h_ref[...] + s_new

    @pl.when(j == nchunks - 1)
    def _emit_state():
        state_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def ssd_chunk_scan(
    xdt: jax.Array,     # (BH, L, P)  dt-weighted inputs, P % 128 == 0 (padded)
    lam: jax.Array,     # (BH, L)     per-step log decay  a_h · dt
    b: jax.Array,       # (BH, L, N)  N % 8 == 0
    c: jax.Array,       # (BH, L, N)
    *,
    q: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (BH, L, P) f32, final_state (BH, N, P)).

    ``q`` is the chunk length (a lane multiple; ``L % q == 0`` — the
    wrapper pads).
    """
    q = q or default_tuning("tpu", "ssd")["q"]
    bh, seqlen, hdim = xdt.shape
    nstate = b.shape[-1]
    if q % LANES:
        raise ValueError(f"chunk q={q} must be a multiple of {LANES}")
    if seqlen % q:
        raise ValueError(f"L={seqlen} must be a multiple of {q}")
    nchunks = seqlen // q
    # lam rides as a (BH, 1, L) view: a (1, q) block of a 2-D (BH, L)
    # array breaks Mosaic's 8-sublane rule, a (1, 1, q) block of the 3-D
    # view matches the array's unit dim
    return pl.pallas_call(
        functools.partial(_ssd_kernel, nchunks=nchunks, q=q),
        grid=(bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, q, hdim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, q, nstate), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, nstate), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, hdim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, nstate, hdim), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seqlen, hdim), jnp.float32),
            jax.ShapeDtypeStruct((bh, nstate, hdim), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nstate, hdim), jnp.float32)],
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(xdt, lam[:, None, :], b, c)
