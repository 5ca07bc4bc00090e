"""Device-level ("grid-level", paper §4.3/§5.3) reduction and scan.

The paper's grid level uses multiple kernel launches with partials in global
memory. The TPU-native analogue is a mesh collective: within-device partials
are produced by the tile/block levels (repro.core.reduce / .scan), and the
cross-device combination is expressed with jax collectives inside
``shard_map``. The scan follows the paper's *scan-then-propagate* strategy:

  kernel 1: per-device segmented scan          -> local scan + local total
  kernel 2: scan of the per-device totals      -> matmul-form over the axis
  kernel 3: uniform add of the exclusive carry -> one fused add

Kernel 2 is itself in matmul form: the gathered totals vector is hit with a
strictly-lower-triangular ones matrix — the same L as the tile level, with
the mesh axis playing the role of the tile row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.scan import tcu_scan, tcu_weighted_scan
from repro.kernels.layout import MXU_PRECISION


def dist_reduce(x_local: jax.Array, axis_name: str) -> jax.Array:
    """Grid-level full reduction: local matmul-form partials + psum."""
    from repro.core.reduce import tcu_reduce

    return jax.lax.psum(tcu_reduce(x_local), axis_name)


def dist_exclusive_carry(local_total: jax.Array, axis_name: str) -> jax.Array:
    """Exclusive scan of per-device totals over a mesh axis, matmul-form.

    all_gather the totals (one scalar-ish leaf per device), multiply with the
    strictly-lower triangular ones matrix, and select this device's row —
    the paper's grid-level "scan of partials" with the matmul executing
    redundantly-but-locally on every device (cheaper than a second collective
    round for the axis sizes used here).
    """
    gathered = jax.lax.all_gather(local_total, axis_name)          # (ndev, ...)
    ndev = gathered.shape[0]
    idx = jax.lax.axis_index(axis_name)
    rows = jax.lax.broadcasted_iota(jnp.int32, (ndev, ndev), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (ndev, ndev), 1)
    l_mask = (rows > cols).astype(gathered.dtype)
    flat = gathered.reshape(ndev, -1)
    carries = jnp.matmul(l_mask, flat,
                         precision=MXU_PRECISION)                 # (ndev, -1)
    return carries[idx].reshape(gathered.shape[1:])


def dist_scan(x_local: jax.Array, axis_name: str) -> jax.Array:
    """Grid-level inclusive scan: the last axis of the *global* array is
    sharded over ``axis_name``; returns the correctly-carried local shard."""
    local = tcu_scan(x_local)
    carry = dist_exclusive_carry(local[..., -1], axis_name)
    return local + carry[..., None]


def weighted_exclusive_carry(
    total: jax.Array, log_decay: jax.Array, axis_name: str
) -> jax.Array:
    """Weighted exclusive scan of per-device (total, log-decay) pairs.

    Solves the cross-device recurrence ``H_i = exp(L_i) * H_{i-1} + T_i``
    over mesh axis ``axis_name`` and returns this device's *incoming* carry
    ``H_{i-1}`` (zeros on device 0). ``total`` may carry extra trailing
    state dims beyond ``log_decay``'s shape — ``log_decay`` broadcasts over
    them (the SSD case: totals are ``(B, H, P, N)`` states decayed by a
    per-``(B, H)`` scalar; the weighted-scan case has no extra dims).

    Matmul form throughout: all_gather both, hit the totals with the decay
    matrix ``exp(segsum(L))`` — the same 1-semiseparable mask as the tile
    level, with the mesh axis playing the role of the tile row — and select
    this device's row of the shifted result.
    """
    from repro.core.tiles import segsum

    if total.shape[:log_decay.ndim] != log_decay.shape:
        raise ValueError(
            f"log_decay shape {log_decay.shape} must prefix total shape "
            f"{total.shape}")
    gathered_t = jax.lax.all_gather(total, axis_name)              # (ndev, ...)
    gathered_d = jax.lax.all_gather(log_decay, axis_name)
    ndev = gathered_t.shape[0]
    idx = jax.lax.axis_index(axis_name)

    d = jnp.moveaxis(gathered_d, 0, -1)                 # (*D, ndev)
    m = jnp.exp(segsum(d))                              # (*D, ndev, ndev)
    # flatten the extra state dims so the combine is one batched matmul
    t = jnp.moveaxis(gathered_t.reshape((ndev,) + log_decay.shape + (-1,)),
                     0, -2)                             # (*D, ndev, extra)
    s = jnp.matmul(m, t, precision=MXU_PRECISION)       # inclusive H_i
    excl = jnp.concatenate(
        [jnp.zeros_like(s[..., :1, :]), s[..., :-1, :]], axis=-2)
    return jnp.take(excl, idx, axis=-2).reshape(total.shape)


def dist_weighted_scan(
    x_local: jax.Array, log_a_local: jax.Array, axis_name: str
) -> jax.Array:
    """Grid-level decayed scan (sequence-parallel SSD carry propagation).

    Local chunks compute their weighted scan and total decay; the cross-
    device carry is the weighted exclusive scan of (totals, decays) over the
    mesh axis, then propagated through each position's prefix decay.
    """
    acc = jnp.float32
    local = tcu_weighted_scan(x_local, log_a_local)
    carry = weighted_exclusive_carry(
        local[..., -1], jnp.sum(log_a_local.astype(acc), axis=-1), axis_name)
    prefix = jnp.cumsum(log_a_local.astype(acc), axis=-1)
    return local + carry[..., None] * jnp.exp(prefix)
