#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run makes
its data and weights on the device from ``--seed``, warms up every shape
the cell's traffic uses (that is ``setup_s``), measures for ``--seconds``
and then checks what the timed path produced against a plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced); the numbers compared for ``correct`` and their limits come last
in it, under ``checks``, and again as the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.lib import harness

    return harness.main(ROOT, args.workload, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
