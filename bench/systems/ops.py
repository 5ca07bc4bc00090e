"""The paper's primitives as users call them: ``repro.ops.reduce`` and
``repro.ops.scan`` (inclusive), each jitted as one program per segment
size, under the default ``KernelPolicy`` with ``interpret_fallback=
"error"`` (the shape-aware ``auto`` choice is what gets measured).

The window issues the traffic's round of calls again and again, on inputs
made on the device from the seed, with up to the traffic's ``in_flight``
calls queued on the device behind the one being waited on: a host stall
shorter than that queue leaves the chip busy. Per program it keeps a
seeded uniform sample of outputs (reservoir sampling); once the window
has closed they are compared with a float64 reference on the host.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

from bench.lib import traffic as trafficlib
from bench.lib import yardstick
from bench.lib.harness import Check


def program_name(op: str, seg: int) -> str:
    """The jitted function's name; the trace shows ``jit_<name>(...)``."""
    return f"bench_{op}_s{seg}"


def setup(run):
    return Ops(run)


class Ops:
    def __init__(self, run):
        import jax
        import jax.numpy as jnp

        import repro.ops as rops
        from repro.core.policy import KernelPolicy

        tr = run.traffic
        self.calls = trafficlib.op_calls(tr)
        total = int(tr["total_elements"])
        self.variants = int(tr["inputs_per_shape"])
        self.keep = int(tr["checked_per_program"])
        self.in_flight = int(tr["in_flight"])
        self.rows = {seg: total // seg for _, seg in self.calls}
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
        made = jax.jit(make)(seed_key)
        self.inputs = {s: made[i * self.variants:(i + 1) * self.variants]
                       for i, s in enumerate(segs)}
        self.fns = {}
        for op, seg in self.calls:
            def call(x, _op=getattr(rops, op)):
                return _op(x, policy=policy)
            call.__name__ = call.__qualname__ = program_name(op, seg)
            self.fns[(op, seg)] = jax.jit(call)
        for (op, seg), fn in self.fns.items():        # warm every program
            for x in self.inputs[seg]:
                jax.block_until_ready(fn(x))
        self.kept = None

    def window(self, run) -> dict:
        rng = trafficlib.rng_for(run.seed, 6)
        kept = {key: [] for key in self.fns}        # (index, variant, out)
        seen = {key: 0 for key in self.fns}
        counts = {key: 0 for key in self.fns}
        pending = deque()
        k = 0
        t0 = time.perf_counter()
        deadline = t0 + run.seconds
        while time.perf_counter() < deadline:
            key = self.calls[k % len(self.calls)]
            variant = (k // len(self.calls)) % self.variants
            out = self.fns[key](self.inputs[key[1]][variant])
            # reservoir sample of this program's outputs
            seen[key] += 1
            slot = len(kept[key]) if len(kept[key]) < self.keep \
                else int(rng.integers(seen[key]))
            if slot < self.keep:
                item = (k, variant, out)
                if slot == len(kept[key]):
                    kept[key].append(item)
                else:
                    kept[key][slot] = item
            pending.append(out)
            if len(pending) > self.in_flight:
                pending.popleft().block_until_ready()
            counts[key] += 1
            k += 1
        while pending:      # all that was sent counts, over all the time
            pending.popleft().block_until_ready()
        run.window_s = time.perf_counter() - t0
        run.attempted, run.failed = k, 0
        self.kept = kept
        nbytes = sum(yardstick.op_bytes(op, self.rows[seg], seg) * n
                     for (op, seg), n in counts.items())
        run.log(f"window: {k} calls in {run.window_s:.3f} s "
                f"({', '.join(f'{op} seg={s}: {n}' for (op, s), n in counts.items())})")
        return {"calls": {program_name(op, seg): {"op": op, "seg": seg,
                                                  "rows": self.rows[seg],
                                                  "count": n}
                          for (op, seg), n in counts.items()},
                "bytes": nbytes}

    def release(self) -> None:
        self.fns.clear()

    def check(self, run, record, outputs=None) -> list:
        """Per op, the widest error of its sampled outputs against a
        float64 reference, in units of float32 roundoff times the summed
        magnitudes (``reference.err_units``). ``outputs(op, x)`` replaces
        the program's output (the control)."""
        ref = run.cell.reference()
        worst = {}
        for (op, seg), items in self.kept.items():
            for _, variant, out in items:
                x = self.inputs[seg][variant]
                if outputs is not None:
                    out = outputs(op, x)
                err = ref.err_units(op, np.asarray(x), np.asarray(out))
                worst[op] = max(worst.get(op, 0.0), err)
        limits = run.traffic["limits"]
        return [Check(f"{op}_err", worst[op], float(limits[f"{op}_err"]))
                for op in sorted(worst)]
