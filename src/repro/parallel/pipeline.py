"""GPipe-style pipeline parallelism over an optional ``stage`` mesh axis.

The assigned production meshes (16x16 and 2x16x16) have no stage axis — the
big archs fit with TP x FSDP — but clusters that prefer PP over FSDP (e.g.
when the data axis is consumed by long-sequence SP) can wrap any scanned
homogeneous block stack in ``pipeline_apply``:

  * layers are split into S contiguous stages; stage s holds layers
    [s*L/S, (s+1)*L/S) — parameters sharded over the ``stage`` axis by the
    leading stage dim;
  * the batch is split into M microbatches; the classic GPipe schedule
    runs S + M - 1 ticks, each tick a step where every stage processes one
    microbatch and hands its activation to the next stage with
    ``jax.lax.ppermute`` — the collective the paper's grid level maps to
    on a ring;
  * bubble fraction = (S-1)/(S+M-1), reported by ``pipeline_stats``.

This module is deliberately self-contained (used by tests and the PP
example) rather than wired into every model: on the assigned meshes the
dry-run exercises TPxFSDP, and PP composes with the same block functions.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.compat import shard_map


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    axis: str = "stage"


def pipeline_stats(cfg: PipelineConfig) -> dict:
    s, m = cfg.n_stages, cfg.n_microbatches
    return {"ticks": s + m - 1, "bubble_fraction": (s - 1) / (s + m - 1)}


def pipeline_apply(
    block_fn: Callable,      # (stage_params, x) -> y   one stage's layers
    stage_params,            # pytree, leading dim = n_stages
    x: jax.Array,            # (B, ...) global batch
    cfg: PipelineConfig,
    mesh: jax.sharding.Mesh,
) -> jax.Array:
    """Run the GPipe schedule. ``block_fn`` must be shape-preserving
    (residual-block semantics), which all our layer stacks are."""
    s, m = cfg.n_stages, cfg.n_microbatches
    b = x.shape[0]
    assert b % m == 0, (b, m)
    mb = b // m
    xq = x.reshape(m, mb, *x.shape[1:])          # microbatch queue

    def run(params_local, xq_local):
        idx = jax.lax.axis_index(cfg.axis)
        take = lambda t: t[0]                     # strip the stage dim
        p_loc = jax.tree.map(take, params_local)
        buf0 = jnp.where(idx == 0, xq_local[0], jnp.zeros_like(xq_local[0]))
        outq0 = jnp.zeros_like(xq_local)
        # mark the carries as stage-varying for shard_map's VMA tracking
        # (buf0 already varies through idx; outq0 is a plain zeros tensor)
        outq0 = jax.lax.pcast(outq0, (cfg.axis,), to="varying")

        def tick_step(state, tick):
            buf, outq = state
            y = block_fn(p_loc, buf)
            # the last stage finishes microbatch (tick - (S-1)) at this tick
            done_mb = tick - (s - 1)
            upd = jax.lax.dynamic_update_slice_in_dim(
                outq, y[None], jnp.maximum(done_mb, 0), axis=0)
            emit = jnp.logical_and(idx == s - 1, done_mb >= 0)
            outq = jnp.where(emit, upd, outq)
            # hand activations down the ring: stage i -> i+1
            y_next = jax.lax.ppermute(
                y, cfg.axis, [(i, (i + 1) % s) for i in range(s)])
            # stage 0 pulls the next microbatch from the queue
            nxt = tick + 1
            feed = jax.lax.dynamic_slice_in_dim(
                xq_local, jnp.clip(nxt, 0, m - 1), 1, axis=0)[0]
            feed = jnp.where(nxt < m, feed, jnp.zeros_like(feed))
            buf = jnp.where(idx == 0, feed, y_next)
            return (buf, outq), None

        (_, outq), _ = jax.lax.scan(tick_step, (buf0, outq0),
                                    jnp.arange(s + m - 1))
        # only the last stage holds real outputs; gather via masked psum
        mask = (idx == s - 1).astype(outq.dtype)
        return jax.lax.psum(outq * mask, cfg.axis)

    out = shard_map(
        run, mesh=mesh,
        in_specs=(P(cfg.axis), P()),
        out_specs=P(),
    )(stage_params, xq)
    return out.reshape(b, *x.shape[1:])
