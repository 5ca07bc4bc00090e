"""Public wrappers for the Pallas kernels, dispatched through
``repro.kernels.backend``.

Every op registers its path entries with :func:`backend.register_op`: the
*tile* entry is the padding/layout glue in this module feeding the
shape-strict, MXU-aligned Pallas-TPU kernel (native on TPU, interpret mode
on CPU); the *tile_gpu* entry is the Pallas-Triton twin's glue
(``repro.kernels.triton.ops``, native on GPU); the scan family also
registers *tile_logdepth* entries per backend (carry-free local kernels +
the ``matmul_scan`` tree combine); the *fused* entry is the pure-jnp
oracle in ``ref.py``. The execution path is chosen
per call (``policy=`` / ``path=`` / legacy ``use_pallas=``) or by the
active ``repro.core.policy.KernelPolicy`` (whose process default follows
``REPRO_KERNEL_PATH``) — see the backend module docstring for precedence;
the stable public façade over these ops is ``repro.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend, layout, ref
from repro.kernels.backend import pallas_op
from repro.kernels.layout import LANES, SUBLANES
from repro.kernels.layout import nrows as _nrows
from repro.kernels.layout import pad_axis as _pad_axis
from repro.kernels.layout import ssd_fold, ssd_unfold

if backend.has_pallas_tpu():
    from repro.kernels import matmul_scan as _mm_scan
    from repro.kernels.flash_attention import flash_attention as _flash_kernel
    from repro.kernels.fused_rmsnorm import fused_rmsnorm as _rmsnorm_kernel
    from repro.kernels.ssd_scan import ssd_chunk_scan as _ssd_kernel
    from repro.kernels.tcu_reduce import (
        tcu_segmented_reduce_tn as _reduce_kernel)
    from repro.kernels.tcu_scan import tcu_segmented_scan_tn as _scan_kernel
else:  # pragma: no cover — JAX without the Pallas-TPU lowering
    _flash_kernel = _rmsnorm_kernel = _ssd_kernel = None
    _reduce_kernel = _scan_kernel = _mm_scan = None

if backend.has_pallas_triton():
    from repro.kernels.triton import ops as triton_ops
else:  # pragma: no cover — JAX without the Pallas-Triton lowering
    triton_ops = None


def _require_pallas(kernel, name: str):
    if kernel is None:
        raise RuntimeError(
            f"{name}: this JAX build has no Pallas-TPU lowering; only the "
            "fused path is available (path='fused')")
    return kernel


def _gpu_entry(fn_name: str):
    """The Triton glue entry, or None when this JAX has no Pallas-Triton."""
    return getattr(triton_ops, fn_name) if triton_ops is not None else None


def _knob(tuning, key: str, op: str) -> int:
    """One TPU-geometry knob from the resolved TuneSpec (or the layout
    default when no spec reached this glue — direct/legacy callers)."""
    return layout.knob(tuning, key, "tpu", op)


on_tpu = backend.on_tpu  # re-exported; historical home of this probe


# ---------------------------------------------------------------------------
# segmented reduce


def _reduce_tile(x: jax.Array, *, tuning=None,
                 interpret: bool = False) -> jax.Array:
    lead = x.shape[:-1]
    n = x.shape[-1]
    flat = x.reshape(-1, n)
    # spec geometry, clamped against the shape: segments ride the lanes,
    # elements the sublanes of the transposed LoadTile
    bs = layout.fit_block(flat.shape[0], _knob(tuning, "block_s", "reduce"),
                          LANES)
    bn = layout.fit_block(n, _knob(tuning, "block_n", "reduce"), SUBLANES)
    # col-major LoadTile: feed the kernel x^T, pad both dims to the blocks
    xt = _pad_axis(_pad_axis(flat.T, 0, bn), 1, bs)
    out = _require_pallas(_reduce_kernel, "segmented_reduce")(
        xt, block_s=bs, block_n=bn, interpret=interpret)
    return out[: flat.shape[0]].reshape(lead)


def segmented_reduce(x: jax.Array, *, policy=None, path: str | None = None,
                     use_pallas: bool | None = None) -> jax.Array:
    """Sum over the last axis of ``x (..., n)`` -> f32 ``(...,)``."""
    return pallas_op("segmented_reduce", x, policy=policy, path=path,
                     use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# segmented scan


def _scan_tile(x: jax.Array, *, tuning=None,
               interpret: bool = False) -> jax.Array:
    lead = x.shape[:-1]
    n = x.shape[-1]
    rows = _nrows(lead)
    bs = layout.fit_block(rows, _knob(tuning, "block_s", "scan"), SUBLANES)
    bn = layout.fit_block(n, _knob(tuning, "block_n", "scan"), LANES)
    flat = _pad_axis(_pad_axis(x.reshape(-1, n), 0, bs), 1, bn)
    out = _require_pallas(_scan_kernel, "segmented_scan")(
        flat, block_s=bs, block_n=bn, interpret=interpret)
    return out[:rows, :n].reshape(*lead, n)


def _scan_tile_logdepth(x: jax.Array, *, tuning=None,
                        interpret: bool = False) -> jax.Array:
    """Log-depth MatMulScan: carry-free local block scans (fully parallel
    Pallas grid) + an O(log_radix nblocks) tree combine of batched MMAs
    over the block totals (``repro.kernels.matmul_scan``)."""
    mm = _require_pallas(_mm_scan, "segmented_scan[tile_logdepth]")
    lead = x.shape[:-1]
    n = x.shape[-1]
    rows = _nrows(lead)
    bs = layout.fit_block(rows, _knob(tuning, "block_s", "scan"), SUBLANES)
    bn = layout.fit_block(n, _knob(tuning, "block_n", "scan"), LANES)
    flat = _pad_axis(_pad_axis(x.reshape(-1, n), 0, bs), 1, bn)
    local = mm.matmul_local_scan(flat, block_s=bs, block_n=bn,
                                 interpret=interpret)
    s_pad, n_pad = local.shape
    nchunks = n_pad // bn
    if nchunks > 1:
        totals = local[:, bn - 1::bn]                    # (s_pad, nchunks)
        carry = mm.tree_scan(totals,
                             radix=_knob(tuning, "radix", "scan"),
                             fan_in=_knob(tuning, "fan_in", "scan"))
        exc = jnp.pad(carry, ((0, 0), (1, 0)))[:, :-1]   # exclusive
        local = (local.reshape(s_pad, nchunks, bn)
                 + exc[..., None]).reshape(s_pad, n_pad)
    return local[:rows, :n].reshape(*lead, n)


def segmented_scan(x: jax.Array, *, policy=None, path: str | None = None,
                   use_pallas: bool | None = None) -> jax.Array:
    """Inclusive prefix-sum over the last axis -> f32, same shape."""
    return pallas_op("segmented_scan", x, policy=policy, path=path,
                     use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# weighted scan (the SSD kernel degenerated to N = P = 1, B = C = 1)


def _weighted_scan_tile(x: jax.Array, log_a: jax.Array, *, tuning=None,
                        interpret: bool = False) -> jax.Array:
    lead = x.shape[:-1]
    n = x.shape[-1]
    rows = _nrows(lead)
    q = layout.fit_block(n, _knob(tuning, "q", "weighted_scan"), LANES)
    xf = x.reshape(rows, n).astype(jnp.float32)
    la = log_a.reshape(rows, n).astype(jnp.float32)
    # state dim N=1 (pad to 8) and head dim P=1 (pad to 128): h is scalar,
    # b = c = e_1 make the recurrence y_t = h_t = exp(la_t) h_{t-1} + x_t.
    xp = _pad_axis(_pad_axis(xf[..., None], 2, LANES), 1, q)
    lap = _pad_axis(la, 1, q)      # pad with 0 ⇒ decay 1, input 0: harmless
    e1 = jnp.ones((rows, n, 1), jnp.float32)
    e1 = _pad_axis(_pad_axis(e1, 2, SUBLANES), 1, q)
    y, _ = _require_pallas(_ssd_kernel, "weighted_scan")(
        xp, lap, e1, e1, q=q, interpret=interpret)
    return y[:, :n, 0].reshape(*lead, n)


def _weighted_scan_tile_logdepth(x: jax.Array, log_a: jax.Array, *,
                                 tuning=None,
                                 interpret: bool = False) -> jax.Array:
    """Log-depth weighted scan: per-block 1-semiseparable local passes +
    a decay-folded tree combine over the block boundary states."""
    mm = _require_pallas(_mm_scan, "weighted_scan[tile_logdepth]")
    lead = x.shape[:-1]
    n = x.shape[-1]
    rows = _nrows(lead)
    q = layout.fit_block(n, _knob(tuning, "q", "weighted_scan"), LANES)
    xf = x.reshape(rows, n).astype(jnp.float32)
    la = log_a.reshape(rows, n).astype(jnp.float32)
    xp = _pad_axis(xf, 1, q)
    lap = _pad_axis(la, 1, q)      # pad with 0 ⇒ decay 1, input 0: harmless
    local = mm.matmul_local_weighted(xp, lap, q=q, interpret=interpret)
    nchunks = xp.shape[1] // q
    if nchunks > 1:
        lg = lap.reshape(rows, nchunks, q)
        # block boundary recurrence H_j = exp(Σλ_j)·H_{j-1} + h_j[last]
        carry = mm.tree_weighted(
            jnp.sum(lg, axis=-1), local[:, q - 1::q, None],
            radix=_knob(tuning, "radix", "weighted_scan"),
            fan_in=_knob(tuning, "fan_in", "weighted_scan"))[..., 0]
        exc = jnp.pad(carry, ((0, 0), (1, 0)))[:, :-1]   # (rows, nchunks)
        local = (local.reshape(rows, nchunks, q)
                 + jnp.exp(jnp.cumsum(lg, axis=-1)) * exc[..., None]
                 ).reshape(rows, -1)
    return local[:, :n].reshape(*lead, n)


def weighted_scan(x: jax.Array, log_a: jax.Array, *, policy=None,
                  path: str | None = None,
                  use_pallas: bool | None = None) -> jax.Array:
    """Decayed scan ``y_i = exp(log_a_i) * y_{i-1} + x_i`` -> f32."""
    return pallas_op("weighted_scan", x, log_a, policy=policy, path=path,
                     use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# rmsnorm (differentiable: all paths share one custom VJP)


def _rmsnorm_fits(x, w, *, tuning=None, **_) -> bool:
    """The kernel is lane-strict: d must be a multiple of 128."""
    return x.shape[-1] % LANES == 0


def _rmsnorm_tile_fwd(x, w, eps, interpret, tuning):
    lead, d = x.shape[:-1], x.shape[-1]
    rb = layout.fit_block(_nrows(lead), _knob(tuning, "row_block", "rmsnorm"),
                          SUBLANES)
    flat = _pad_axis(x.reshape(-1, d), 0, rb)
    out = _require_pallas(_rmsnorm_kernel, "rmsnorm")(
        flat, w, eps=eps, row_block=rb, interpret=interpret)
    return out[: _nrows(lead)].reshape(*lead, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4))
def _rmsnorm_dispatch(kind, x, w, eps, tuning):
    if kind == "fused":
        return ref.rmsnorm_ref(x, w, eps=eps)
    if kind == "tile_gpu":
        return triton_ops.rmsnorm_tile_gpu_fwd(x, w, eps, False, tuning)
    return _rmsnorm_tile_fwd(x, w, eps, kind == "interpret", tuning)


def _rmsnorm_vjp_fwd(kind, x, w, eps, tuning):
    return _rmsnorm_dispatch(kind, x, w, eps, tuning), (x, w)


def _rmsnorm_vjp_bwd(kind, eps, tuning, res, g):
    # backward through the reference formulation (numerically identical)
    x, w = res
    _, vjp = jax.vjp(lambda xx, ww: ref.rmsnorm_ref(xx, ww, eps=eps), x, w)
    return vjp(g)


_rmsnorm_dispatch.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)


def _rmsnorm_tile(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                  tuning=None, interpret: bool = False) -> jax.Array:
    return _rmsnorm_dispatch("interpret" if interpret else "tile", x, w,
                             eps, tuning)


def _rmsnorm_tile_gpu(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                      tuning=None, interpret: bool = False) -> jax.Array:
    if interpret:  # interpret validation runs outside the VJP wrapper too
        return triton_ops.rmsnorm_tile_gpu_fwd(x, w, eps, True, tuning)
    return _rmsnorm_dispatch("tile_gpu", x, w, eps, tuning)


def _rmsnorm_fused(x: jax.Array, w: jax.Array, *,
                   eps: float = 1e-6) -> jax.Array:
    return _rmsnorm_dispatch("fused", x, w, eps, None)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            policy=None, path: str | None = None,
            use_pallas: bool | None = None) -> jax.Array:
    """RMSNorm over the last axis (differentiable; Pallas fwd on TPU/GPU)."""
    return pallas_op("rmsnorm", x, w, eps=eps, policy=policy, path=path,
                     use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# SSD scan


def _ssd_tile(
    x: jax.Array,       # (B, L, H, P)
    dt: jax.Array,      # (B, L, H)    positive step sizes
    a: jax.Array,       # (H,)         negative decay rates
    b: jax.Array,       # (B, L, G, N)
    c: jax.Array,       # (B, L, G, N)
    *,
    return_state: bool = False,
    tuning=None,
    interpret: bool = False,
):
    bsz, seqlen, nheads, hdim = x.shape
    nstate = b.shape[3]
    q = layout.fit_block(seqlen, _knob(tuning, "q", "ssd"), LANES)
    # fold (B, H) and broadcast groups; pad P (lane dim) to 128, L to q
    xdt, lam, bb, cc = ssd_fold(x, dt, a, b, c)
    xdt = _pad_axis(_pad_axis(xdt, 2, LANES), 1, q)
    lam = _pad_axis(lam, 1, q)
    bb = _pad_axis(_pad_axis(bb, 2, SUBLANES), 1, q)
    cc = _pad_axis(_pad_axis(cc, 2, SUBLANES), 1, q)
    y, state = _require_pallas(_ssd_kernel, "ssd_scan")(
        xdt, lam, bb, cc, q=q, interpret=interpret)
    # kernel state is (B*H, N_pad, P_pad); zero-padding of b/x keeps the
    # valid block exact — slice and match ssd_chunked's (B, H, P, N)
    return ssd_unfold(y, state, bsz=bsz, nheads=nheads, seqlen=seqlen,
                      hdim=hdim, nstate=nstate, out_dtype=x.dtype,
                      return_state=return_state)


def _ssd_tile_logdepth(
    x: jax.Array,       # (B, L, H, P)
    dt: jax.Array,      # (B, L, H)    positive step sizes
    a: jax.Array,       # (H,)         negative decay rates
    b: jax.Array,       # (B, L, G, N)
    c: jax.Array,       # (B, L, G, N)
    *,
    return_state: bool = False,
    tuning=None,
    interpret: bool = False,
):
    """Log-depth SSD: carry-free per-chunk passes emit (y_local, S_j);
    the chunk-state recurrence ``H_j = exp(Σλ_j)·H_{j-1} + S_j`` runs as
    the weighted tree combine and the inter-chunk term
    ``(C ∘ exp(Λ)) @ H_{j-1}`` is one batched matmul per chunk."""
    mm = _require_pallas(_mm_scan, "ssd_scan[tile_logdepth]")
    bsz, seqlen, nheads, hdim = x.shape
    nstate = b.shape[3]
    q = layout.fit_block(seqlen, _knob(tuning, "q", "ssd"), LANES)
    xdt, lam, bb, cc = ssd_fold(x, dt, a, b, c)
    xdt = _pad_axis(_pad_axis(xdt, 2, LANES), 1, q)
    lam = _pad_axis(lam, 1, q)
    bb = _pad_axis(_pad_axis(bb, 2, SUBLANES), 1, q)
    cc = _pad_axis(_pad_axis(cc, 2, SUBLANES), 1, q)
    y, s = mm.matmul_local_ssd(xdt, lam, bb, cc, q=q, interpret=interpret)
    bh, l_pad, p_pad = xdt.shape
    n_pad = bb.shape[2]
    nchunks = l_pad // q
    lg = lam.reshape(bh, nchunks, q)
    # pad chunks have λ = 0 and S = 0: identity steps, H passes through
    h_inc = mm.tree_weighted(
        jnp.sum(lg, axis=-1), s.reshape(bh, nchunks, n_pad * p_pad),
        radix=_knob(tuning, "radix", "ssd"),
        fan_in=_knob(tuning, "fan_in", "ssd"))
    h_exc = jnp.pad(h_inc, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    h_exc = h_exc.reshape(bh, nchunks, n_pad, p_pad)
    cdec = (cc.reshape(bh, nchunks, q, n_pad)
            * jnp.exp(jnp.cumsum(lg, axis=-1))[..., None])
    y = (y.reshape(bh, nchunks, q, p_pad)
         + jnp.einsum("bjqn,bjnp->bjqp", cdec, h_exc,
                      precision=layout.MXU_PRECISION)
         ).reshape(bh, l_pad, p_pad)
    state = h_inc[:, -1].reshape(bh, n_pad, p_pad)
    return ssd_unfold(y, state, bsz=bsz, nheads=nheads, seqlen=seqlen,
                      hdim=hdim, nstate=nstate, out_dtype=x.dtype,
                      return_state=return_state)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, policy=None, path: str | None = None,
             use_pallas: bool | None = None, return_state: bool = False):
    """Mamba-2 SSD scan -> (B, L, H, P) in the input dtype; with
    ``return_state=True`` also the final state (B, H, P, N) f32."""
    return pallas_op("ssd_scan", x, dt, a, b, c, policy=policy, path=path,
                     use_pallas=use_pallas, return_state=return_state)


# ---------------------------------------------------------------------------
# attention


def _attention_blocks(q: jax.Array, k: jax.Array, tuning) -> tuple:
    # block_q rides the sublanes (the kernel accepts any 8-multiple);
    # block_k is the lane dim of the score tile and stays a 128-multiple
    bq = layout.fit_block(q.shape[2], _knob(tuning, "block_q", "attention"),
                          SUBLANES)
    bk = layout.fit_block(k.shape[2], _knob(tuning, "block_k", "attention"),
                          LANES)
    return bq, bk


def _attention_fits(q, k, v, *, tuning=None, **_) -> bool:
    """The kernel is block-strict: both lengths must fill whole blocks."""
    bq, bk = _attention_blocks(q, k, tuning)
    return q.shape[2] % bq == 0 and k.shape[2] % bk == 0


def _attention_tile(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int | None = None,
    scale: float | None = None, tuning=None, interpret: bool = False,
) -> jax.Array:
    bq, bk = _attention_blocks(q, k, tuning)
    return _require_pallas(_flash_kernel, "attention")(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=interpret)


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int | None = None,
    scale: float | None = None, policy=None, path: str | None = None,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Multi-head attention (B, Hq, Lq, D) x (B, Hkv, Lk, D) -> (B, Hq, Lq, D)."""
    return pallas_op("attention", q, k, v, causal=causal, window=window,
                     scale=scale, policy=policy, path=path,
                     use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# registry


def _diff_via_ref(kernel_fn, ref_fn):
    """Make a kernel entry differentiable: backward through the oracle.

    ``pallas_call`` has no JVP rule in interpret mode (and only partial
    autodiff support natively), so a train step that reaches a kernel
    path would crash. Every kernel agrees with its ``ref.py`` twin to
    tolerance (the dispatch-agreement tests), so the same trick rmsnorm
    already uses generalises: run the kernel forward, differentiate the
    reference formulation (numerically identical) backward. ``kwargs``
    are static per call and must be accepted by both twins —
    ``interpret``/``tuning`` steer only the kernel side (geometry changes
    how the kernel runs, never what it computes, so the oracle backward
    stays numerically identical).
    """
    if kernel_fn is None:
        return None

    @functools.wraps(kernel_fn)
    def wrapped(*args, interpret=False, tuning=None, **kwargs):
        run = jax.custom_vjp(
            lambda *arrs: kernel_fn(*arrs, interpret=interpret,
                                    tuning=tuning, **kwargs))

        def fwd(*arrs):
            return run(*arrs), arrs

        def bwd(res, g):
            _, vjp = jax.vjp(lambda *a: ref_fn(*a, **kwargs), *res)
            return vjp(g)

        run.defvjp(fwd, bwd)
        return run(*args)

    return wrapped


backend.register_op("segmented_reduce",
                    tile=_diff_via_ref(_reduce_tile,
                                       ref.segmented_reduce_ref),
                    fused=ref.segmented_reduce_ref,
                    tile_gpu=_diff_via_ref(_gpu_entry("reduce_tile_gpu"),
                                           ref.segmented_reduce_ref))
backend.register_op("segmented_scan",
                    tile=_diff_via_ref(_scan_tile, ref.segmented_scan_ref),
                    fused=ref.segmented_scan_ref,
                    tile_gpu=_diff_via_ref(_gpu_entry("scan_tile_gpu"),
                                           ref.segmented_scan_ref),
                    tile_logdepth=_diff_via_ref(_scan_tile_logdepth,
                                                ref.segmented_scan_ref),
                    tile_logdepth_gpu=_diff_via_ref(
                        _gpu_entry("scan_tile_logdepth_gpu"),
                        ref.segmented_scan_ref))
backend.register_op("weighted_scan",
                    tile=_diff_via_ref(_weighted_scan_tile,
                                       ref.weighted_scan_ref),
                    fused=ref.weighted_scan_ref,
                    tile_gpu=_diff_via_ref(
                        _gpu_entry("weighted_scan_tile_gpu"),
                        ref.weighted_scan_ref),
                    tile_logdepth=_diff_via_ref(
                        _weighted_scan_tile_logdepth,
                        ref.weighted_scan_ref),
                    tile_logdepth_gpu=_diff_via_ref(
                        _gpu_entry("weighted_scan_tile_logdepth_gpu"),
                        ref.weighted_scan_ref))
# rmsnorm carries its own custom VJP (all paths share it) — no wrapper
backend.register_op("rmsnorm", tile=_rmsnorm_tile, fused=_rmsnorm_fused,
                    tile_gpu=(_rmsnorm_tile_gpu if triton_ops is not None
                              else None),
                    fits=_rmsnorm_fits)
backend.register_op("ssd_scan",
                    tile=_diff_via_ref(_ssd_tile, ref.ssd_scan_ref),
                    fused=ref.ssd_scan_ref,
                    tile_gpu=_diff_via_ref(_gpu_entry("ssd_tile_gpu"),
                                           ref.ssd_scan_ref),
                    tile_logdepth=_diff_via_ref(_ssd_tile_logdepth,
                                                ref.ssd_scan_ref),
                    tile_logdepth_gpu=_diff_via_ref(
                        _gpu_entry("ssd_tile_logdepth_gpu"),
                        ref.ssd_scan_ref))
backend.register_op("attention",
                    tile=_diff_via_ref(_attention_tile,
                                       ref.flash_attention_ref),
                    fused=ref.flash_attention_ref,
                    tile_gpu=_diff_via_ref(_gpu_entry("attention_tile_gpu"),
                                           ref.flash_attention_ref),
                    fits=_attention_fits)
