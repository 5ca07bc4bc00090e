"""Shared layout/padding glue + the single home for kernel geometry.

Both kernel backends (the Pallas-TPU twins in this package and the
Pallas-Triton twins in ``repro.kernels.triton``) wrap the same shape-strict
kernels in the same way: flatten leading dims, zero-pad to the backend's
tile multiples, run, slice the valid block back out. The padding algebra is
backend-independent — only the multiples differ (128-lane MXU tiles vs
16-wide tensor-core MMA fragments) — so it lives here once.

Since the TuneSpec refactor this module is also the ONLY place allowed to
spell out block/chunk/warp numbers (a grep-guard test bans literal geometry
constants in every other kernel file):

* :data:`LANES` / :data:`SUBLANES` / :data:`MMA_TILE` — *hardware*
  constants (MXU lane count, f32 sublane tile, tensor-core fragment edge).
  These are facts about the silicon, not tuning knobs.
* :data:`DEFAULT_TUNING` — the per-(backend, op) default knob values the
  kernels ran with before geometry became caller-supplied. Consumed by
  ``repro.core.policy.KernelPolicy.tuning_for`` as the base layer every
  resolved :class:`~repro.core.policy.TuneSpec` starts from.
* :data:`CANDIDATE_TUNING` — the candidate specs ``python -m
  repro.core.autotune --write`` sweeps per op (>= 2 each; the winning spec
  is persisted in the v3 table).
* :func:`fit_block` — clamp a caller-supplied block size to the hardware
  multiple and the (padded) extent of the axis it tiles, so a swept or
  hand-written spec can never crash a kernel on a small or unaligned shape
  (it shrinks to fit instead).

The knob *names* are validated against ``repro.core.policy.KNOB_SCHEMA``
(the policy layer owns validation, the way ``op_paths`` validates against
``KNOWN_OPS``); this module owns the *values*.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Hardware constants (not tuning knobs):
LANES = 128      # TPU MXU/VPU lane count — the systolic-array edge
SUBLANES = 8     # TPU f32 sublane tile (min second-to-last dim)
MMA_TILE = 16    # GPU tensor-core MMA fragment edge (WMMA 16x16x16)
# The reduce/scan/SSD kernels and their XLA combines contract f32 operands
# at full f32 precision: the TPU default (Mosaic's and XLA's alike) is one
# bf16 pass, which rounds every element, and every carried running sum, to
# 8 mantissa bits.
MXU_PRECISION = jax.lax.Precision.HIGHEST

# KV-cache page height for the paged serving pool (serving/kvpool.py):
# a power-of-two multiple of SUBLANES so a page is a whole number of
# sublane tiles and divides every pow2-bucketized ring capacity. Like
# the constants above this is geometry, so it lives here and nowhere
# else (callers import it; the grep-guard bans literal copies).
KV_PAGE_ROWS = 2 * SUBLANES

# Per-(backend, op) default tuning — the values the kernels hard-coded
# before the TuneSpec refactor. Keys must stay within
# repro.core.policy.KNOB_SCHEMA (test-enforced). The "tpu" section also
# covers the interpret path (it runs the Pallas-TPU kernel body).
DEFAULT_TUNING = {
    "tpu": {
        "reduce": {"block_s": 128, "block_n": 128},
        "scan": {"block_s": 128, "block_n": 128,
                 "radix": 16, "fan_in": 16},
        "weighted_scan": {"q": 128, "radix": 16, "fan_in": 16},
        "rmsnorm": {"row_block": 128},
        "attention": {"block_q": 128, "block_k": 128},
        "ssd": {"q": 128, "radix": 16, "fan_in": 16},
        "ragged_reduce": {},
        "ragged_scan": {},
    },
    "gpu": {
        "reduce": {"block_s": 32, "block_n": 64,
                   "num_warps": 4, "num_stages": 2},
        "scan": {"block_s": 32, "block_n": 64, "radix": 16, "fan_in": 16,
                 "num_warps": 4, "num_stages": 2},
        "weighted_scan": {"q": 64, "radix": 16, "fan_in": 16,
                          "num_warps": 4, "num_stages": 2},
        "rmsnorm": {"row_block": 16, "block_d": 128,
                    "num_warps": 8, "num_stages": 2},
        "attention": {"block_q": 64, "block_k": 64,
                      "num_warps": 4, "num_stages": 2},
        "ssd": {"q": 64, "radix": 16, "fan_in": 16,
                "num_warps": 4, "num_stages": 2},
        "ragged_reduce": {},
        "ragged_scan": {},
    },
}

# Candidate specs the autotune sweep times per op (the first entry is the
# default geometry so the sweep always covers the status quo). Ragged ops
# have no Pallas kernel yet, hence no candidates.
CANDIDATE_TUNING = {
    "tpu": {
        "reduce": ({"block_s": 128, "block_n": 128},
                   {"block_s": 128, "block_n": 256},
                   {"block_s": 256, "block_n": 128}),
        "scan": ({"block_s": 128, "block_n": 128},
                 {"block_s": 128, "block_n": 256}),
        "weighted_scan": ({"q": 128}, {"q": 256}),
        "rmsnorm": ({"row_block": 128}, {"row_block": 256}),
        "attention": ({"block_q": 128, "block_k": 128},
                      {"block_q": 128, "block_k": 256}),
        "ssd": ({"q": 128}, {"q": 256}),
    },
    "gpu": {
        "reduce": ({"block_s": 32, "block_n": 64,
                    "num_warps": 4, "num_stages": 2},
                   {"block_s": 64, "block_n": 64,
                    "num_warps": 4, "num_stages": 2},
                   {"block_s": 32, "block_n": 128,
                    "num_warps": 8, "num_stages": 3}),
        "scan": ({"block_s": 32, "block_n": 64,
                  "num_warps": 4, "num_stages": 2},
                 {"block_s": 16, "block_n": 128,
                  "num_warps": 8, "num_stages": 2}),
        "weighted_scan": ({"q": 64, "num_warps": 4, "num_stages": 2},
                          {"q": 128, "num_warps": 4, "num_stages": 2}),
        "rmsnorm": ({"row_block": 16, "block_d": 128,
                     "num_warps": 8, "num_stages": 2},
                    {"row_block": 32, "block_d": 64,
                     "num_warps": 4, "num_stages": 2}),
        "attention": ({"block_q": 64, "block_k": 64,
                       "num_warps": 4, "num_stages": 2},
                      {"block_q": 128, "block_k": 64,
                       "num_warps": 8, "num_stages": 2}),
        "ssd": ({"q": 64, "num_warps": 4, "num_stages": 2},
                {"q": 128, "num_warps": 4, "num_stages": 2}),
    },
}


# Candidate specs for the log-depth MatMulScan contender (its own table:
# the linear sweep's clamp-dedupe compares executed dicts, and mixing
# radix/fan_in into CANDIDATE_TUNING would make identical linear
# geometries look distinct and get timed as phantoms). radix is the tree
# branching factor, fan_in the base-case width finished with one
# triangular matmul — both sized around the MMA fragment edge.
LOGDEPTH_CANDIDATE_TUNING = {
    "tpu": {
        "scan": ({"block_s": 128, "block_n": 128,
                  "radix": 16, "fan_in": 16},
                 {"block_s": 128, "block_n": 128,
                  "radix": 16, "fan_in": 64}),
        "weighted_scan": ({"q": 128, "radix": 16, "fan_in": 16},
                          {"q": 128, "radix": 32, "fan_in": 32}),
        "ssd": ({"q": 128, "radix": 16, "fan_in": 16},
                {"q": 128, "radix": 32, "fan_in": 32}),
    },
    "gpu": {
        "scan": ({"block_s": 32, "block_n": 64, "radix": 16, "fan_in": 16,
                  "num_warps": 4, "num_stages": 2},
                 {"block_s": 32, "block_n": 64, "radix": 16, "fan_in": 64,
                  "num_warps": 4, "num_stages": 2}),
        "weighted_scan": ({"q": 64, "radix": 16, "fan_in": 16,
                           "num_warps": 4, "num_stages": 2},
                          {"q": 64, "radix": 32, "fan_in": 32,
                           "num_warps": 4, "num_stages": 2}),
        "ssd": ({"q": 64, "radix": 16, "fan_in": 16,
                 "num_warps": 4, "num_stages": 2},
                {"q": 64, "radix": 32, "fan_in": 32,
                 "num_warps": 4, "num_stages": 2}),
    },
}


def default_tuning(backend: str, op: str) -> dict:
    """The default knob values for ``op`` on ``backend`` (a fresh dict)."""
    return dict(DEFAULT_TUNING.get(backend, {}).get(op, {}))


def candidate_tuning(backend: str, op: str) -> list[dict]:
    """The sweepable candidate specs for ``op`` on ``backend``."""
    return [dict(c) for c in CANDIDATE_TUNING.get(backend, {}).get(op, ())]


def logdepth_candidate_tuning(backend: str, op: str) -> list[dict]:
    """The sweepable candidate specs for ``op``'s ``tile_logdepth``
    contender on ``backend`` (empty for families without one)."""
    return [dict(c)
            for c in LOGDEPTH_CANDIDATE_TUNING.get(backend, {}).get(op, ())]


# Which hardware multiple each clampable block knob carries, split by the
# call-shape axis it tiles: "n" knobs tile the very axis the autotune
# table buckets by (segment size / chunk length / feature dim) and can be
# clamped as soon as n is known — at resolve time, so the reported
# TuneSpec IS the geometry that runs; "rows" knobs tile the flattened
# batch axis only the glue sees and are clamped there. Attention's blocks
# tile two sequence axes that may differ (decode), so only the glue
# clamps them.
N_AXIS_KNOBS = {
    "tpu": {"reduce": {"block_n": SUBLANES}, "scan": {"block_n": LANES},
            "weighted_scan": {"q": LANES}, "ssd": {"q": LANES}},
    "gpu": {"reduce": {"block_n": MMA_TILE}, "scan": {"block_n": MMA_TILE},
            "weighted_scan": {"q": MMA_TILE}, "ssd": {"q": MMA_TILE},
            "rmsnorm": {"block_d": MMA_TILE}},
}
ROW_AXIS_KNOBS = {
    "tpu": {"reduce": {"block_s": LANES}, "scan": {"block_s": SUBLANES},
            "rmsnorm": {"row_block": SUBLANES}},
    "gpu": {"reduce": {"block_s": MMA_TILE}, "scan": {"block_s": MMA_TILE},
            "rmsnorm": {"row_block": MMA_TILE}},
}


def clamp_spec(backend: str, op: str, knobs: dict, *,
               n: int | None = None, rows: int | None = None) -> dict:
    """Clamp block knobs against the known call shape (see
    :data:`N_AXIS_KNOBS`/:data:`ROW_AXIS_KNOBS`); unknown extents pass
    the knob through unchanged. Used by ``KernelPolicy.tuning_for`` (n
    only) so the resolved spec reports what actually runs, and by the
    autotune sweep (n and rows) so candidates that collapse onto the same
    executed geometry are deduplicated instead of timed as phantoms."""
    out = dict(knobs)
    for ext, table in ((n, N_AXIS_KNOBS), (rows, ROW_AXIS_KNOBS)):
        if ext is None:
            continue
        for knob, mult in table.get(backend, {}).get(op, {}).items():
            if knob in out:
                out[knob] = fit_block(ext, out[knob], mult)
    return out


def fit_block(size: int, block: int, multiple: int) -> int:
    """Clamp a caller-supplied block size against the axis it tiles.

    Rounds ``block`` down to the hardware ``multiple`` (never below it) and
    caps it at the padded extent of ``size``, so a swept/hand-written spec
    cannot request a block the shape can't supply: the wrapper then pads
    the axis to a multiple of the fitted block and divisibility holds by
    construction.
    """
    b = max(multiple, (int(block) // multiple) * multiple)
    ext = -(-max(int(size), 1) // multiple) * multiple
    return min(b, ext)


def knob(tuning, key: str, backend: str, op: str) -> int:
    """One knob value from a TuneSpec-or-None, else the backend default.

    ``tuning`` is anything with ``.get`` (a ``TuneSpec`` or a plain dict);
    None falls through to :func:`default_tuning` — how direct kernel-glue
    callers that predate the policy plumbing keep working.
    """
    if tuning is not None:
        v = tuning.get(key)
        if v is not None:
            return int(v)
    return int(DEFAULT_TUNING[backend][op][key])


def pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to the next multiple of ``multiple``."""
    rem = (-x.shape[axis]) % multiple
    if not rem:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def nrows(lead: tuple[int, ...]) -> int:
    """Product of the leading (batch-like) dims a wrapper flattens away."""
    rows = 1
    for s in lead:
        rows *= s
    return rows


def ssd_fold(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array):
    """Model layout -> kernel layout for the SSD chunk-scan kernels.

    Folds ``(B, H)`` into one grid axis, broadcasts the G kv-like groups
    over the H heads, and pre-weights the inputs: ``xdt = dt * x``,
    ``lam = dt * a``. Returns ``(xdt (BH, L, P), lam (BH, L),
    bb (BH, L, N), cc (BH, L, N))`` in f32, unpadded — the caller applies
    its backend's tile-multiple padding (zero-padding is harmless: lam = 0
    means decay 1 and input 0).
    """
    bsz, seqlen, nheads, hdim = x.shape
    ngroups, nstate = b.shape[2], b.shape[3]
    rep = nheads // ngroups
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    xdt = jnp.moveaxis(xdt, 2, 1).reshape(bsz * nheads, seqlen, hdim)
    lam = (dt.astype(jnp.float32) * a.astype(jnp.float32))
    lam = jnp.moveaxis(lam, 2, 1).reshape(bsz * nheads, seqlen)
    bb = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    cc = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    bb = jnp.moveaxis(bb, 2, 1).reshape(bsz * nheads, seqlen, nstate)
    cc = jnp.moveaxis(cc, 2, 1).reshape(bsz * nheads, seqlen, nstate)
    return xdt, lam, bb, cc


def ssd_unfold(y: jax.Array, state: jax.Array, *, bsz: int, nheads: int,
               seqlen: int, hdim: int, nstate: int, out_dtype,
               return_state: bool):
    """Kernel layout back to model layout; slices padding off.

    ``y`` is (BH, L_pad, P_pad), ``state`` (BH, N_pad, P_pad); the
    zero-padding of b/x keeps the valid state block exact, so slicing is
    enough. Returns ``y (B, L, H, P)`` (cast to ``out_dtype``) and, when
    requested, the final state ``(B, H, P, N)`` f32 (matching
    ``ssd_chunked``).
    """
    y = y[:, :seqlen, :hdim].reshape(bsz, nheads, seqlen, hdim)
    y = jnp.moveaxis(y, 1, 2).astype(out_dtype)
    if not return_state:
        return y
    st = state[:, :nstate, :hdim].reshape(bsz, nheads, nstate, hdim)
    return y, jnp.swapaxes(st, -1, -2)
