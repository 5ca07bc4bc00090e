"""The paper's grid level as users reach it: ``repro.ops.reduce`` and
``repro.ops.scan`` (inclusive) called eagerly on arrays whose last (bucket)
axis is sharded over the configuration's mesh, under an active
``MeshContext``, so ``parallel/shard_ops`` routes each call through
``shard_map``: per-shard kernels, then a psum (reduce) or an all-gather
and carry (scan). Default ``KernelPolicy`` with ``interpret_fallback=
"error"``.

The window, its record, the sampling of outputs and the check are those of
``bench/systems/ops.py``; only the calls differ: eager, not jitted, so the
program's per-call host work (routing, tracing, compiling) is inside the
window. In a traced run the window also holds a ``repro.obs`` session, and
its metrics go into the record under ``obs`` for the per-layer readers.
"""
from __future__ import annotations

import functools

from bench.lib import traffic as trafficlib
from bench.systems import ops


def setup(run):
    return ShardedOps(run)


class ShardedOps(ops.Ops):
    def __init__(self, run):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        import repro.ops as rops
        from repro.core.policy import KernelPolicy
        from repro.parallel.mesh_context import make_context

        tr = run.traffic
        self.calls = trafficlib.op_calls(tr)
        total = int(tr["total_elements"])
        self.variants = int(tr["inputs_per_shape"])
        self.keep = int(tr["checked_per_program"])
        self.in_flight = int(tr["in_flight"])
        self.rows = {seg: total // seg for _, seg in self.calls}
        self.ctx = make_context(run.config["mesh"])
        sharding = NamedSharding(self.ctx.mesh,
                                 P(None, self.ctx.mesh.axis_names[0]))
        policy = KernelPolicy(interpret_fallback="error",
                              backend="cpu" if run.rehearsal else None)
        segs = sorted(self.rows)
        dtype = jnp.dtype(run.config["dtype"])

        def make(key):
            keys = jax.random.split(key, len(segs) * self.variants)
            return [jax.random.normal(keys[i * self.variants + v],
                                      (self.rows[s], s), dtype)
                    for i, s in enumerate(segs) for v in range(self.variants)]

        seed_key = jax.random.key(trafficlib.rng_for(run.seed, 5).integers(
            2 ** 31))
        made = jax.jit(make, out_shardings=sharding)(seed_key)
        self.inputs = {s: made[i * self.variants:(i + 1) * self.variants]
                       for i, s in enumerate(segs)}
        self.fns = {(op, seg): functools.partial(getattr(rops, op),
                                                 policy=policy)
                    for op, seg in self.calls}
        with self.ctx:                                 # warm every call
            for (op, seg), fn in self.fns.items():
                for x in self.inputs[seg]:
                    jax.block_until_ready(fn(x))
        self.kept = None

    def window(self, run) -> dict:
        with self.ctx:
            if not run.trace:
                return super().window(run)
            from repro import obs

            with obs.using_obs() as sess:
                record = super().window(run)
        record["obs"] = sess.metrics.snapshot()
        return record
