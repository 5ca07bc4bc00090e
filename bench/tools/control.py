#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's sound runs and the
control's, seed by seed, in one process at the cell's own size and load.

    python3 bench/tools/control.py --workload tcu-ops-f32.small-seg \\
        --seconds 5 --seeds 1 2 3 ... --control-seeds 1 2 3

For each seed it sets the cell up, drives a window of ``--seconds`` at
the cell's load, and prints the number ``correct`` compares (the
program's reading); for a control seed also the control's reading on
the same inputs: the reference with its input rounded as a three-pass
bf16 (``high``) matmul rounds it, which has to come out as not correct.
The limit lies between the program's largest reading and the control's
smallest (``PERF.md`` gives the readings). The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.lib import harness, spec
    from repro.launch import compile_cache

    compile_cache.enable()
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell(ROOT, args.workload)
    ref = cell.reference()
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, rehearsal=False, device={},
                          peaks=None)
        sut = cell.system().setup(run)
        rec = sut.window(run)
        sut.release()
        control = seed in args.control_seeds
        out = {"workload": cell.name, "seed": seed}
        out["program"] = {c.name: c.value for c in sut.check(run, rec)}
        if control:
            checks = sut.check(run, rec, outputs=ref.lower_precision)
            out["control"] = {c.name: c.value for c in checks}
            out["control_correct"] = all(c.ok for c in checks)
        out["failed"] = run.failed
        out["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(out), flush=True)
        del sut
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
