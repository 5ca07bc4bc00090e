"""MatMulScan: log-depth matmul-form scan (the ``tile_logdepth`` path).

Both existing tile paths serialize the inter-block carry — the TPU twin
threads it through a sequential grid dimension + VMEM scratch
(``tcu_scan.py``), the Triton twin through an in-kernel ``fori_loop`` —
so scan latency grows linearly in ``n / block``. MatMulScan (Zouzias &
McColl; the TCU-model follow-up to Dakkak et al.) removes that serial
chain: a radix-``s`` Brent-Kung scan whose upsweep and downsweep are
*only* batched matmuls against two constant ``s x s`` matrices:

  ``L_s`` — triangular ones. In this repo's row-vector layout it appears
  transposed as ``U_s`` (upper-triangular ones, the same constructor the
  linear kernels already build): ``t @ U_s`` is an inclusive scan of
  ``t``'s last axis, one MMA per tree node.
  ``B_s`` — the broadcast matrix (here a ``1 x s`` ones row): the
  downsweep replicates each node's exclusive carry across its children
  as ``carry[..., None] @ B_s`` — again a matmul, never a gather.

The weighted variant (``h_k = exp(logp_k) * h_{k-1} + t_k``) folds the
per-step decay into the upsweep operand: the triangular-ones matrix
becomes the 1-semiseparable ``exp(segsum(logp))`` mask — exactly the
form ``repro.core.distributed.weighted_exclusive_carry`` uses at the
mesh level and the SSD kernels use within a chunk — and the downsweep
carry is scaled by the within-group cumulative decay before the add.

Execution is split in two layers:

* The *local* (level-0) block scans run as Pallas kernels with a fully
  parallel grid — defined here for TPU (``repro.kernels.triton
  .matmul_scan`` holds the Triton twins). They are the linear kernels
  minus the carry machinery.
* The *tree combine* over per-block totals (:func:`tree_scan` /
  :func:`tree_weighted`) runs as ``O(log_radix nblocks)`` rounds of
  batched XLA ``dot_general``s against the constant matrices, shared by
  both backends' glue. XLA lowers these onto the MXU / tensor cores —
  the whole path is matmuls, with no serial dependence longer than the
  tree height.

``radix`` (tree branching factor) and ``fan_in`` (base-case width: a
remaining sequence this short is finished with one triangular matmul)
are ``KNOB_SCHEMA`` tuning knobs; their default values and sweep
candidates live in ``repro.kernels.layout`` like every other geometry
number.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.layout import LANES, MXU_PRECISION, SUBLANES


# ---------------------------------------------------------------------------
# constant-matrix constructors (traceable — iota, no host constants)


def upper_tri_ones(t: int, dtype=jnp.float32) -> jax.Array:
    """``U_t`` (the row-vector transpose of the paper family's ``L_s``):
    upper-triangular ones including the diagonal. ``a @ U_t`` is a
    row-wise inclusive scan."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return (rows <= cols).astype(dtype)


def broadcast_row(t: int, dtype=jnp.float32) -> jax.Array:
    """``B_t`` as a ``1 x t`` ones row: ``carry[..., None] @ B_t``
    replicates a per-group scalar across the group's ``t`` children —
    the downsweep broadcast, kept as a matmul."""
    return jnp.ones((1, t), dtype)


def segsum(log_a: jax.Array) -> jax.Array:
    """``out[..., i, j] = sum(log_a[..., j+1 : i+1])`` on the lower
    triangle (diagonal 0), ``-inf`` above it — so ``exp(segsum(log_a))``
    is the 1-semiseparable decay mask with exact zeros where ``j > i``.
    Mirrors ``repro.core.tiles.segsum`` (not imported: this module loads
    under ``repro.kernels`` before ``repro.core`` finishes importing)."""
    m = log_a.shape[-1]
    csum = jnp.cumsum(
        jnp.pad(log_a, [(0, 0)] * (log_a.ndim - 1) + [(1, 0)]), axis=-1)
    diff = csum[..., :, None] - csum[..., None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m + 1, m + 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m + 1, m + 1), 1)
    return jnp.where(rows >= cols, diff, -jnp.inf)[..., 1:, 1:]


def _shift_right(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive -> exclusive along ``axis``: drop the last slot, prepend
    the combine identity (0 for both + and the weighted combine)."""
    axis = axis % x.ndim
    pad = [(0, 0)] * x.ndim
    pad[axis] = (1, 0)
    sl = [slice(None)] * x.ndim
    sl[axis] = slice(None, -1)
    return jnp.pad(x, pad)[tuple(sl)]


# ---------------------------------------------------------------------------
# the log-depth tree combine (pure XLA; shared by the TPU and GPU glue)


def tree_scan(t: jax.Array, *, radix: int, fan_in: int) -> jax.Array:
    """Inclusive prefix sum of ``t (..., m)`` in ``O(log_radix m)``
    rounds of batched matmuls against ``U_radix`` / ``B_radix``.

    Each level groups ``radix`` neighbours, scans every group with one
    batched ``@ U`` (upsweep), recurses on the group totals, and adds the
    recursion's exclusive carries back via ``carry @ B`` (downsweep). A
    sequence of at most ``fan_in`` is finished with a single triangular
    matmul — the base of the recursion.
    """
    radix = max(2, int(radix))
    fan_in = max(1, int(fan_in))
    t = t.astype(jnp.float32)
    m = t.shape[-1]
    if m <= fan_in:
        return jax.lax.dot_general(
            t, upper_tri_ones(m), (((t.ndim - 1,), (0,)), ((), ())),
            precision=MXU_PRECISION, preferred_element_type=jnp.float32)
    groups = -(-m // radix)
    pad = groups * radix - m
    if pad:  # zero-padding is the scan identity: the tail never leaks back
        t = jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad)])
    tg = t.reshape(*t.shape[:-1], groups, radix)
    local = jax.lax.dot_general(                       # upsweep: @ U_radix
        tg, upper_tri_ones(radix), (((tg.ndim - 1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)
    carry = tree_scan(local[..., -1], radix=radix, fan_in=fan_in)
    exc = _shift_right(carry, -1)
    local = local + jax.lax.dot_general(               # downsweep: @ B_radix
        exc[..., None], broadcast_row(radix),
        (((exc.ndim,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)
    return local.reshape(*local.shape[:-2], groups * radix)[..., :m]


def tree_weighted(logp: jax.Array, t: jax.Array, *, radix: int,
                  fan_in: int) -> jax.Array:
    """Weighted (decayed) inclusive scan in log depth.

    Solves ``h_k = exp(logp_k) * h_{k-1} + t_k`` for ``logp (..., m)``
    and ``t (..., m, F)`` (``F`` flat trailing features — 1 for the
    scalar scans, ``N*P`` for SSD chunk states), returning ``h`` of
    ``t``'s shape. Same tree as :func:`tree_scan` with the triangular
    ones replaced by the 1-semiseparable ``exp(segsum(logp))`` mask in
    the upsweep, and the downsweep carry scaled by the within-group
    cumulative decay (itself matmul-form: ``logp @ U``) before the add.
    Zero-padding the tail is the identity here too: ``logp = 0`` is
    decay 1 and ``t = 0`` adds nothing.
    """
    radix = max(2, int(radix))
    fan_in = max(1, int(fan_in))
    logp = logp.astype(jnp.float32)
    t = t.astype(jnp.float32)
    m = logp.shape[-1]
    if m <= fan_in:
        return jnp.matmul(jnp.exp(segsum(logp)), t, precision=MXU_PRECISION)
    groups = -(-m // radix)
    pad = groups * radix - m
    if pad:
        logp = jnp.pad(logp, [(0, 0)] * (logp.ndim - 1) + [(0, pad)])
        t = jnp.pad(t, [(0, 0)] * (t.ndim - 2) + [(0, pad), (0, 0)])
    lg = logp.reshape(*logp.shape[:-1], groups, radix)
    tg = t.reshape(*t.shape[:-2], groups, radix, t.shape[-1])
    local = jnp.matmul(jnp.exp(segsum(lg)), tg,        # (..., g, radix, F)
                       precision=MXU_PRECISION)
    carry = tree_weighted(jnp.sum(lg, axis=-1), local[..., -1, :],
                          radix=radix, fan_in=fan_in)
    exc = _shift_right(carry, -2)                      # (..., g, F)
    cum = jax.lax.dot_general(                         # within-group Λ
        lg, upper_tri_ones(radix), (((lg.ndim - 1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)
    local = local + jnp.exp(cum)[..., None] * exc[..., None, :]
    return local.reshape(
        *local.shape[:-3], groups * radix, local.shape[-1])[..., :m, :]


# ---------------------------------------------------------------------------
# Pallas-TPU local kernels: the linear kernels minus the carry machinery,
# on a fully parallel grid


def _local_scan_kernel(x_ref, o_ref):
    a = x_ref[...]
    bn = a.shape[1]
    o_ref[...] = jax.lax.dot_general(
        a, upper_tri_ones(bn, a.dtype), (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_n", "interpret"))
def matmul_local_scan(x: jax.Array, *, block_s: int, block_n: int,
                      interpret: bool = False) -> jax.Array:
    """Per-block inclusive scan: (s, n) -> (s, n) f32, every
    ``block_s x block_n`` block scanned independently (no inter-block
    carry — the tree combine adds it). Both grid dimensions are parallel.
    """
    s, n = x.shape
    if block_s % SUBLANES or block_n % LANES:
        raise ValueError(
            f"blocks {(block_s, block_n)} must be multiples of "
            f"{(SUBLANES, LANES)}")
    if n % block_n or s % block_s:
        raise ValueError(
            f"dims must be multiples of {(block_s, block_n)}, got {x.shape}")
    return pl.pallas_call(
        _local_scan_kernel,
        grid=(s // block_s, n // block_n),
        in_specs=[pl.BlockSpec((block_s, block_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_s, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.float32),
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="matmul_local_scan",
    )(x)


def _local_weighted_kernel(x_ref, lam_ref, o_ref, *, q: int):
    lam = lam_ref[0].astype(jnp.float32)               # (1, q)
    x = x_ref[0].astype(jnp.float32)                   # (1, q)
    # Λ = λ @ U (matmul-form cumulative log decay), then the
    # 1-semiseparable mask M[t, τ] = exp(Λ_t − Λ_τ) for τ ≤ t
    cum = jax.lax.dot_general(
        lam, upper_tri_ones(q), (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)            # (1, q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    diff = cum[0][:, None] - cum[0][None, :]
    m = jnp.where(rows >= cols, jnp.exp(diff), 0.0)    # (q, q)
    # y_t = Σ_τ M[t, τ] x_τ, laid out (1, q): contract x's lane axis
    # against M's τ axis
    o_ref[0] = jax.lax.dot_general(
        x, m, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def matmul_local_weighted(x: jax.Array, lam: jax.Array, *, q: int,
                          interpret: bool = False) -> jax.Array:
    """Per-block weighted scan: x, lam (rows, n) -> (rows, n) f32 with
    ``h_t = exp(lam_t) h_{t-1} + x_t`` restarted at every ``q``-block
    boundary (the tree combine stitches blocks). Fully parallel grid."""
    rows, n = x.shape
    if q % LANES:
        raise ValueError(f"block q={q} must be a multiple of {LANES}")
    if n % q:
        raise ValueError(f"n={n} must be a multiple of q={q}")
    # one row per block through a (rows, 1, n) view: a (1, q) block of a
    # 2-D array breaks Mosaic's 8-sublane rule, a (1, 1, q) block of the
    # 3-D view matches the array's unit dim
    block = pl.BlockSpec((1, 1, q), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_local_weighted_kernel, q=q),
        grid=(rows, n // q),
        in_specs=[block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, 1, n), jnp.float32),
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="matmul_local_weighted",
    )(x[:, None, :], lam[:, None, :])[:, 0, :]


def _local_ssd_kernel(xdt_ref, lam_ref, b_ref, c_ref, y_ref, s_ref, *,
                      q: int):
    xdt = xdt_ref[0].astype(jnp.float32)               # (q, P)
    lam = lam_ref[0].astype(jnp.float32)               # (1, q)
    bmat = b_ref[0].astype(jnp.float32)                # (q, N)
    cmat = c_ref[0].astype(jnp.float32)                # (q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    cum = jax.lax.dot_general(
        lam, upper_tri_ones(q), (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)            # (1, q)
    total = jnp.sum(lam)

    # Intra-chunk only: Y_local = ((C Bᵀ) ∘ M) @ (dt∘X); the inter-chunk
    # H term is added by the glue after the tree combine.
    diff = cum[0][:, None] - cum[0][None, :]
    m = jnp.where(rows >= cols, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)
    y_ref[0] = jax.lax.dot_general(
        cb * m, xdt, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # Per-chunk state contribution S = (B ∘ w)ᵀ @ (dt∘X), w_τ = exp(Σλ − Λ_τ)
    bw = bmat * jnp.exp(total - cum[0])[:, None]
    s_ref[0] = jax.lax.dot_general(
        bw, xdt, (((0,), (0,)), ((), ())),
        precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(s_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def matmul_local_ssd(
    xdt: jax.Array,     # (BH, L, P)  dt-weighted inputs, P % 128 == 0
    lam: jax.Array,     # (BH, L)     per-step log decay
    b: jax.Array,       # (BH, L, N)  N % 8 == 0
    c: jax.Array,       # (BH, L, N)
    *,
    q: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Carry-free SSD chunk pass on a fully parallel grid. Returns
    ``(y_local (BH, L, P), s (BH, nchunks*N, P))`` — the intra-chunk
    outputs and every chunk's state contribution; the glue tree-combines
    the states and adds the inter-chunk term."""
    bh, seqlen, hdim = xdt.shape
    nstate = b.shape[-1]
    if q % LANES:
        raise ValueError(f"chunk q={q} must be a multiple of {LANES}")
    if seqlen % q:
        raise ValueError(f"L={seqlen} must be a multiple of {q}")
    nchunks = seqlen // q
    return pl.pallas_call(
        functools.partial(_local_ssd_kernel, q=q),
        grid=(bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, q, hdim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, 0, j)),   # (BH, 1, L)
            pl.BlockSpec((1, q, nstate), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, nstate), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, hdim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, nstate, hdim), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seqlen, hdim), jnp.float32),
            jax.ShapeDtypeStruct((bh, nchunks * nstate, hdim), jnp.float32),
        ],
        compiler_params=backend.compiler_params(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="matmul_local_ssd",
    )(xdt, lam[:, None, :], b, c)
