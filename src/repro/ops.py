"""``repro.ops`` — the stable public API for the paper's ops.

This façade is the documented entry point for running any of the repo's
reduce/scan-family operations under a :class:`~repro.core.policy.
KernelPolicy`. Every op accepts ``policy=``:

* ``None`` (default) — the active policy (:func:`get_policy`; its process
  default is built from ``REPRO_KERNEL_PATH``/``REPRO_AUTOTUNE*``),
* a :class:`KernelPolicy`,
* a string shorthand — a bare path label (``"fused"``, ``"tile"``,
  ``"baseline"``, ...), an ``op=path,op=path`` per-op override list, or a
  JSON object of policy fields.

Scoped overrides compose through :func:`using_policy` /
:func:`set_policy`::

    import repro.ops as ops
    from repro.ops import KernelPolicy, using_policy

    ops.reduce(x)                          # active policy (usually auto)
    ops.scan(x, policy="baseline")         # exactly this path
    with using_policy(KernelPolicy(path="auto",
                                   op_paths={"attention": "fused"})):
        ops.attention(q, k, v)             # per-op override beats global

Kernel *geometry* is part of the policy too: :class:`TuneSpec` carries
per-op block/chunk knobs and ``KernelPolicy(op_tuning={"ssd": {"q":
64}})`` (or the ``"tile,ssd.q=64"`` string shorthand) overrides how the
tile kernels run, not just which path does.

:func:`dist_weighted_scan` is the multi-device composition of
``weighted_scan`` (the paper's grid-level scan-then-propagate) for use
inside ``shard_map``; it takes an axis name instead of a policy.

The exported surface is exactly ``__all__``; a CI test pins it. The
``path=`` kwarg is a deprecated alias for a bare-label policy and warns
once per process.

Every op is observed (``repro.obs``): while spans are on it runs inside an
``ops/<op>`` span with stats ``call`` (a per-process sequence number that
its ``shard_ops/<op>`` span, compile counts and events share), ``n`` (the
bucket-axis length) and ``routed`` (whether ``shard_ops`` took it); while
a session is active it counts ``repro_ops_calls_total{op, routed}`` and
attributes JAX's compile events to ``op``. Under ``jax.jit`` all of this
happens at trace time only. With observability off the wrapper costs two
module-global loads.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect

import jax

from repro.core import dispatch as _dispatch
from repro.core import policy as _policy
from repro.core.distributed import \
    dist_weighted_scan  # noqa: F401  (re-exported API)
from repro.core.policy import (  # noqa: F401  (re-exported API)
    KernelPolicy,
    TuneSpec,
    get_policy,
    set_policy,
    using_policy,
)
from repro.kernels import ops as _kops
from repro.obs import profiling as _prof
from repro.obs import runtime as _obs

__all__ = [
    "KernelPolicy",
    "TuneSpec",
    "attention",
    "dist_weighted_scan",
    "get_policy",
    "ragged_reduce",
    "ragged_scan",
    "reduce",
    "rmsnorm",
    "scan",
    "set_policy",
    "ssd",
    "using_policy",
    "weighted_scan",
]


@dataclasses.dataclass
class _OpCall(_obs.Call):
    """An observed op call; ``routed`` turns true when ``shard_ops`` took
    it (:func:`_routed`)."""
    routed: bool = False


def _observed(axis: int):
    """Observe a public op whose first argument's ``axis`` is the bucket
    axis (see the module docstring)."""
    def wrap(fn):
        op = fn.__name__
        first = next(iter(inspect.signature(fn).parameters))

        @functools.wraps(fn)
        def observed(*args, **kwargs):
            if not _prof._TRACING and _obs.ACTIVE is None:
                return fn(*args, **kwargs)
            x = args[0] if args else kwargs[first]
            return _observe(op, x.shape[axis], fn, args, kwargs)
        return observed
    return wrap


def _observe(op: str, n: int, fn, args, kwargs):
    """Run ``fn`` as an observed call of ``op``: in an ``ops/<op>`` span
    (stats ``call``, ``n``, ``routed``) when spans are on, marked as the
    current call for the compile counter and the call's events, and
    counted in ``repro_ops_calls_total{op, routed}`` when a session is
    active."""
    c = _OpCall(op)
    _obs.begin_call(c)
    try:
        if _prof._TRACING:
            ann = jax.profiler.TraceAnnotation(f"ops/{op}", call=c.id,
                                               n=int(n))
            with ann:
                out = fn(*args, **kwargs)
                ann.set_metadata(routed=c.routed)
        else:
            out = fn(*args, **kwargs)
    finally:
        _obs.end_call(c)
    sess = _obs.ACTIVE
    if sess is not None:
        sess.counter("repro_ops_calls_total",
                     "repro.ops calls by op, and whether shard_ops routed "
                     "them").inc(op=op, routed=str(c.routed).lower())
    return out


def _routed(out):
    """Mark the current observed op call as taken by ``shard_ops``."""
    c = _obs.current_call()
    if c is not None:
        c.routed = True
    return out


def _policy_arg(policy, path):
    """Fold the deprecated ``path=`` alias into ``policy`` (warns once)."""
    if path is not None:
        _policy.warn_once(
            "deprecated:repro.ops.path",
            "the path= kwarg on repro.ops is deprecated; pass policy= "
            "(a KernelPolicy or a string shorthand like policy='fused')",
            stacklevel=4)
        if policy is None:
            policy = path
    return policy


def _shard_ops():
    """The shard_map routing layer (deferred: ``parallel`` imports core).

    Under an active :class:`~repro.parallel.mesh_context.MeshContext`,
    eager committed arrays whose bucket axis is sharded over the context's
    mesh run the per-shard kernel inside ``shard_map`` with the grid-level
    carry combine; everything else (tracers included — GSPMD partitions
    the fused forms in-jit) falls back to plain dispatch.
    """
    from repro.parallel import shard_ops

    return shard_ops


@_observed(-1)
def reduce(x: jax.Array, *, policy=None, path: str | None = None
           ) -> jax.Array:
    """Segmented sum over the last axis of ``x (..., n)`` -> f32
    ``(...,)``."""
    policy = _policy_arg(policy, path)
    out = _shard_ops().sharded_reduce(x, policy=policy)
    if out is not None:
        return _routed(out)
    return _dispatch.reduce(x, policy=policy)


@_observed(-1)
def scan(x: jax.Array, *, policy=None, exclusive: bool = False,
         path: str | None = None) -> jax.Array:
    """Prefix sum over the last axis -> f32, same shape
    (``exclusive=True`` shifts in a leading zero)."""
    policy = _policy_arg(policy, path)
    out = _shard_ops().sharded_scan(x, policy=policy, exclusive=exclusive)
    if out is not None:
        return _routed(out)
    return _dispatch.scan(x, policy=policy, exclusive=exclusive)


@_observed(-1)
def weighted_scan(x: jax.Array, log_a: jax.Array, *, policy=None,
                  path: str | None = None) -> jax.Array:
    """Decayed scan ``y_i = exp(log_a_i) * y_{i-1} + x_i`` -> f32."""
    policy = _policy_arg(policy, path)
    out = _shard_ops().sharded_weighted_scan(x, log_a, policy=policy)
    if out is not None:
        return _routed(out)
    return _dispatch.weighted_scan(x, log_a, policy=policy)


@_observed(-1)
def ragged_reduce(x: jax.Array, seg_ids: jax.Array, n_segments: int, *,
                  policy=None, path: str | None = None) -> jax.Array:
    """Bucketed segmented sum: ``x (..., n)`` + ``seg_ids`` -> f32
    ``(..., n_segments)``."""
    return _dispatch.ragged_reduce(x, seg_ids, n_segments,
                                   policy=_policy_arg(policy, path))


@_observed(-1)
def ragged_scan(x: jax.Array, seg_ids: jax.Array, n_segments: int, *,
                policy=None, debug: bool = False,
                path: str | None = None) -> jax.Array:
    """Within-segment inclusive prefix sum -> f32, same shape as ``x``
    (``seg_ids`` must be non-decreasing; ``debug=True`` validates)."""
    return _dispatch.ragged_scan(x, seg_ids, n_segments,
                                 policy=_policy_arg(policy, path),
                                 debug=debug)


@_observed(-1)
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            policy=None, path: str | None = None) -> jax.Array:
    """RMSNorm over the last axis (differentiable; MXU Σx² on the kernel
    paths)."""
    return _kops.rmsnorm(x, w, eps=eps, policy=_policy_arg(policy, path))


@_observed(1)
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              scale: float | None = None, policy=None,
              path: str | None = None) -> jax.Array:
    """Multi-head attention in model layout: ``q (B, Sq, Hq, D)``,
    ``k``/``v`` ``(B, Sk, Hkv, D)`` -> ``(B, Sq, Hq, D)``."""
    return _dispatch.attention(q, k, v, causal=causal, window=window,
                               scale=scale,
                               policy=_policy_arg(policy, path))


@_observed(1)
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, *, policy=None, chunk: int | None = None,
        matmul_dtype=None, return_state: bool = False,
        path: str | None = None):
    """Mamba-2 SSD scan -> ``y (B, L, H, P)``; with ``return_state=True``
    also the final state ``(B, H, P, N)`` f32."""
    policy = _policy_arg(policy, path)
    out = _shard_ops().sharded_ssd(x, dt, a, b, c, policy=policy,
                                   chunk=chunk, matmul_dtype=matmul_dtype,
                                   return_state=return_state)
    if out is not None:
        return _routed(out)
    return _dispatch.ssd(x, dt, a, b, c, policy=policy, chunk=chunk,
                         matmul_dtype=matmul_dtype,
                         return_state=return_state)
