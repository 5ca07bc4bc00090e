"""shard_map'd kernel dispatch — sharded arrays stay on tile kernels.

The paper's grid level (§4.3/§5.3) combines per-processor partials that
were themselves produced by the tile/block levels. ``core.distributed``
expresses that combine as mesh collectives *inside* ``shard_map``; this
module is the missing outer half: given an **eager, committed** array
whose bucket axis is sharded over a mesh axis of the active
:class:`~repro.parallel.mesh_context.MeshContext`, wrap the normal
``core.dispatch`` call in ``shard_map`` so that

* each device runs the policy-resolved kernel on its **shard** (under
  :func:`~repro.parallel.mesh_context.shard_local_scope`, so the policy's
  shard-shape division is not applied a second time to the already-local
  shape), and
* the cross-device carry is the matmul-form combine from
  ``core.distributed`` (psum for reduce, the strictly-lower-triangular
  ones matmul for scan, the 1-semiseparable decay matmul for
  weighted-scan/SSD).

Routing is deliberately conservative: these helpers return ``None``
(caller falls back to plain dispatch) unless the call is eager (not under
a trace — inside jit, GSPMD already partitions the fused forms), the
array's sharding is a ``NamedSharding`` over the context's mesh, the
bucket axis is actually sharded, and the shard is even. ``repro.ops``
consults them; ``core.dispatch`` itself stays mesh-free.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.distributed import (
    dist_exclusive_carry,
    weighted_exclusive_carry,
)
from repro.kernels.layout import MXU_PRECISION
from repro.obs import runtime as _obs
from repro.parallel.compat import shard_map
from repro.parallel.mesh_context import (
    current_mesh_context,
    shard_local_scope,
)

__all__ = ["plan", "sharded_reduce", "sharded_scan",
           "sharded_weighted_scan", "sharded_ssd"]


@dataclasses.dataclass(frozen=True)
class Plan:
    """One routed call: ``fn(*args)`` is the shard_map program it runs
    (``jax.jit(fn).lower(*args)`` shows its collectives)."""
    op: str
    fn: Callable
    args: tuple
    ctx: object
    dim: int
    axes: tuple

    def run(self):
        if _obs.ACTIVE is not None:
            _emit_route(self.op, self.args[0], self.dim, self.ctx,
                        self.axes)
        return self.fn(*self.args)


def plan(op: str, *args, **kwargs) -> "Plan | None":
    """The :class:`Plan` a ``repro.ops`` call of ``op`` (``"reduce"``,
    ``"scan"``, ``"weighted_scan"`` or ``"ssd"``) would run on these
    arguments, or None when it falls back to plain dispatch."""
    return _PLANS[op](*args, **kwargs)


def _emit_route(op: str, x, dim: int, ctx, axes) -> None:
    """One ``sharded_dispatch`` event when a shard_map route is taken
    (only called when an obs session is active) — the audit record that a
    call left plain dispatch for the mesh path, and over which axes."""
    sess = _obs.ACTIVE
    if sess is None:
        return
    sizes = ctx.axis_sizes
    nshards = 1
    for a in axes:
        nshards *= sizes.get(a, 1)
    sess.emit("sharded_dispatch", op=op, n=int(x.shape[dim]),
              dim=int(dim), mesh_axes=list(axes), nshards=int(nshards))
    sess.counter(
        "repro_sharded_dispatch_total",
        "calls routed through shard_map by op").inc(op=op)


def _routing_ctx(x, dim: int):
    """The (ctx, full-rank spec, bucket-axis names) triple when ``x``'s
    ``dim`` is sharded under the active MeshContext, else None."""
    ctx = current_mesh_context()
    if ctx is None or ctx.mesh is None:
        return None
    if isinstance(x, jax.core.Tracer):       # in-jit: GSPMD's job
        return None
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding) or sharding.mesh != ctx.mesh:
        return None
    spec = _full_spec(sharding.spec, x.ndim)
    axes = spec[dim]
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    sizes = ctx.axis_sizes
    nshards = 1
    for a in axes:
        nshards *= sizes.get(a, 1)
    if nshards <= 1 or x.shape[dim] % nshards != 0:
        return None
    return ctx, spec, axes


def _full_spec(spec, ndim: int) -> tuple:
    spec = tuple(spec)
    return spec + (None,) * (ndim - len(spec))


def _plan_reduce(x, *, policy=None):
    route = _routing_ctx(x, x.ndim - 1)
    if route is None:
        return None
    ctx, spec, axes = route
    from repro.core import dispatch

    def body(xs):
        with shard_local_scope():
            part = dispatch.reduce(xs, policy=policy)
        return jax.lax.psum(part, axes)

    fn = shard_map(body, mesh=ctx.mesh, in_specs=(P(*spec),),
                   out_specs=P(*spec[:-1]), check_vma=False)
    return Plan("reduce", fn, (x,), ctx, x.ndim - 1, axes)


def sharded_reduce(x, *, policy=None):
    """Last-axis reduce of a sharded array: per-shard kernel + psum.
    Returns None when the call should fall back to plain dispatch."""
    p = _plan_reduce(x, policy=policy)
    return None if p is None else p.run()


def _plan_scan(x, *, policy=None, exclusive: bool = False):
    if exclusive:
        return None
    route = _routing_ctx(x, x.ndim - 1)
    if route is None:
        return None
    ctx, spec, axes = route
    if len(axes) != 1:
        return None  # multi-axis bucket sharding: fall back
    from repro.core import dispatch

    def body(xs):
        with shard_local_scope():
            local = dispatch.scan(xs, policy=policy)
        carry = dist_exclusive_carry(local[..., -1], axes[0])
        return local + carry[..., None]

    fn = shard_map(body, mesh=ctx.mesh, in_specs=(P(*spec),),
                   out_specs=P(*spec), check_vma=False)
    return Plan("scan", fn, (x,), ctx, x.ndim - 1, axes)


def sharded_scan(x, *, policy=None, exclusive: bool = False):
    """Last-axis inclusive scan of a sharded array: per-shard kernel +
    exclusive carry of shard totals (scan-then-propagate). The exclusive
    variant needs a cross-shard element shift, so it falls back."""
    p = _plan_scan(x, policy=policy, exclusive=exclusive)
    return None if p is None else p.run()


def _plan_weighted_scan(x, log_a, *, policy=None):
    route = _routing_ctx(x, x.ndim - 1)
    if route is None:
        return None
    ctx, spec, axes = route
    if len(axes) != 1:
        return None
    la_sh = getattr(log_a, "sharding", None)
    if not isinstance(la_sh, NamedSharding) \
            or _full_spec(la_sh.spec, log_a.ndim) != spec:
        return None
    from repro.core import dispatch

    def body(xs, las):
        with shard_local_scope():
            local = dispatch.weighted_scan(xs, las, policy=policy)
        log_decay = jnp.sum(las.astype(jnp.float32), axis=-1)
        carry = weighted_exclusive_carry(local[..., -1], log_decay, axes[0])
        prefix = jnp.cumsum(las.astype(jnp.float32), axis=-1)
        return local + carry[..., None] * jnp.exp(prefix)

    fn = shard_map(body, mesh=ctx.mesh, in_specs=(P(*spec), P(*spec)),
                   out_specs=P(*spec), check_vma=False)
    return Plan("weighted_scan", fn, (x, log_a), ctx, x.ndim - 1, axes)


def sharded_weighted_scan(x, log_a, *, policy=None):
    """Last-axis decayed scan of a sharded array: per-shard kernel + the
    1-semiseparable carry combine, propagated through prefix decays."""
    p = _plan_weighted_scan(x, log_a, policy=policy)
    return None if p is None else p.run()


def _plan_ssd(x, dt, a, b, c, *, policy=None, chunk=None,
              matmul_dtype=None, return_state: bool = False):
    route = _routing_ctx(x, 1)
    if route is None:
        return None
    ctx, spec, axes = route
    if len(axes) != 1:
        return None
    axis = axes[0]
    specs = {"dt": dt, "b": b, "c": c}
    arg_specs = []
    for name, arr in specs.items():
        sh = getattr(arr, "sharding", None)
        if not isinstance(sh, NamedSharding) or sh.mesh != ctx.mesh:
            return None
        s = _full_spec(sh.spec, arr.ndim)
        if s[1] != spec[1] or s[0] != spec[0]:
            return None
        arg_specs.append(s)
    if getattr(a, "sharding", None) is not None and \
            isinstance(a.sharding, NamedSharding) and \
            any(e is not None for e in _full_spec(a.sharding.spec, a.ndim)):
        return None
    dt_spec, b_spec, c_spec = arg_specs
    from repro.core import dispatch

    nd = ctx.axis_sizes[axis]
    heads = x.shape[2]
    groups = b.shape[2]

    def body(xs, dts, a_r, bs, cs):
        with shard_local_scope():
            y, h_last = dispatch.ssd(
                xs, dts, a_r, bs, cs, policy=policy, chunk=chunk,
                matmul_dtype=matmul_dtype, return_state=True)
        # shard-level recurrence: H_i = exp(L_i) H_{i-1} + h_last_i
        lam = dts.astype(jnp.float32) * a_r.astype(jnp.float32)  # (B, Ll, H)
        log_decay = jnp.sum(lam, axis=1)                         # (B, H)
        h_in = weighted_exclusive_carry(h_last, log_decay, axis)
        # inject the incoming state into every position of this shard:
        # y_l += C_l · (prod_{k<=l} exp(lam_k)) h_in
        cdec = jnp.repeat(cs, heads // groups, axis=2).astype(jnp.float32) \
            * jnp.exp(jnp.cumsum(lam, axis=1))[..., None]        # (B,Ll,H,N)
        y = y + jnp.einsum("blhn,bhpn->blhp", cdec, h_in,
                           precision=MXU_PRECISION).astype(y.dtype)
        if not return_state:
            return y
        h_fin = jnp.exp(log_decay)[..., None, None] * h_in + h_last
        last = jax.lax.axis_index(axis) == nd - 1
        h_glob = jax.lax.psum(
            jnp.where(last, h_fin, jnp.zeros_like(h_fin)), axis)
        return y, h_glob

    out_specs = P(*spec) if not return_state else (P(*spec), P())
    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(*spec), P(*dt_spec), P(), P(*b_spec), P(*c_spec)),
        out_specs=out_specs, check_vma=False)
    return Plan("ssd", fn, (x, dt, a, b, c), ctx, 1, axes)


def sharded_ssd(x, dt, a, b, c, *, policy=None, chunk=None,
                matmul_dtype=None, return_state: bool = False):
    """Sequence-sharded SSD: per-shard chunked scan + cross-device state
    carry (the same recurrence one level up: shard finals are chunk finals).

    ``x (B, L, H, P)`` sharded on L (dim 1); ``dt (B, L, H)``, ``b``/``c``
    ``(B, L, G, N)`` must be sharded identically on L; ``a (H,)`` is
    host-replicated. The returned final state is replicated.
    """
    p = _plan_ssd(x, dt, a, b, c, policy=policy, chunk=chunk,
                  matmul_dtype=matmul_dtype, return_state=return_state)
    return None if p is None else p.run()


_PLANS = {"reduce": _plan_reduce, "scan": _plan_scan,
          "weighted_scan": _plan_weighted_scan, "ssd": _plan_ssd}
