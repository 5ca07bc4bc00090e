"""Benchmark harness plumbing: wall-clock timing of jit'd callables on this
CPU host plus derived model-level metrics.

Wall-clock numbers on a CPU container do not reproduce the paper's V100
throughput; what they DO establish (and what each benchmark asserts) is the
*shape* of the paper's claims: matmul-form vs element-form op counts, the
bandwidth-boundedness of reduction/scan, and the HLO-level ALU-mix proxy
for the power results. Every benchmark prints a CSV block
``name,<cols>`` followed by rows, and is one-to-one with a paper figure.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

# Which backend's contender set this run wants: "auto" = whatever the host
# resolves natively. Set by ``benchmarks.run --backend``.
BENCH_BACKEND = "auto"

# Where each benchmark's BENCH_<name>.json lands ("." = cwd). Set by
# ``benchmarks.run --json-dir`` so a sweep collects its machine-readable
# rows in one place for CI artifact upload.
BENCH_JSON_DIR = "."


def set_bench_backend(backend: str) -> None:
    global BENCH_BACKEND
    BENCH_BACKEND = backend


def set_bench_json_dir(directory: str) -> None:
    global BENCH_JSON_DIR
    BENCH_JSON_DIR = directory


def write_bench_json(bench: str, rows: list, meta: dict | None = None) -> str:
    """Persist one benchmark's rows as ``BENCH_<bench>.json`` under
    :data:`BENCH_JSON_DIR`. ``rows`` is a list of dicts with a stable
    per-benchmark schema (CI checks the serving one); ``meta`` merges into
    the top level alongside ``bench``/``rows``."""
    os.makedirs(BENCH_JSON_DIR, exist_ok=True)
    path = os.path.join(BENCH_JSON_DIR, f"BENCH_{bench}.json")
    with open(path, "w") as f:
        json.dump({"bench": bench, **(meta or {}), "rows": rows}, f,
                  indent=2)
    print(f"# wrote {path} ({len(rows)} rows)")
    return path


def select_paths(labels: dict[str, str]) -> dict[str, str]:
    """Filter contender rows to dispatch paths resolvable on this host.

    ``labels`` maps row name -> ``repro.core.dispatch`` path label. Rows
    that cannot run here are skipped with a printed note instead of
    crashing the sweep: labels that raise on resolution (``tile_gpu`` on a
    CPU host), labels for a backend other than the one ``--backend``
    requested, and the generic ``tile`` when it would silently downgrade
    to the Pallas interpreter (orders of magnitude slower than anything it
    would be compared against — a downgraded row is noise, not data).
    """
    import dataclasses

    from repro.core import policy as kpolicy

    # probe under interpret_fallback="silent": resolution only, nothing
    # runs, and the one-time downgrade warning stays unconsumed for a
    # later genuine path="tile" execution
    probe = dataclasses.replace(kpolicy.get_policy(),
                                interpret_fallback="silent")
    out = {}
    for name, path in labels.items():
        try:
            resolved = probe.resolve(explicit=path)
        except (RuntimeError, ValueError):
            print(f"# skip {name}: path={path!r} unresolvable on this host "
                  f"(backend={jax.default_backend()})")
            continue
        if BENCH_BACKEND != "auto" and resolved in ("tile_tpu", "tile_gpu") \
                and resolved != f"tile_{BENCH_BACKEND}":
            print(f"# skip {name}: path={path!r} resolves to {resolved!r}, "
                  f"not in the requested --backend {BENCH_BACKEND} "
                  "contender set")
            continue
        if resolved == "interpret" and path != "interpret":
            print(f"# skip {name}: path={path!r} downgrades to the Pallas "
                  "interpreter here (no native lowering)")
            continue
        if resolved == "tile_logdepth":
            # the label survives resolution even off-accelerator (only its
            # local block kernels drop to the interpreter), so the
            # downgrade is detected by re-probing under the strict policy
            try:
                dataclasses.replace(
                    probe, interpret_fallback="error").resolve(explicit=path)
            except RuntimeError:
                print(f"# skip {name}: path={path!r} runs its local block "
                      "kernels through the Pallas interpreter here (no "
                      "native lowering)")
                continue
        out[name] = path
    return out


def tuning_label(path: str, op: str, n: int | None = None,
                 dtype=None) -> str:
    """The TuneSpec the active policy resolves for one contender row.

    Compact ``"knob=value;..."`` form for the benchmark's ``tuning=``
    column; ``"-"`` for rows whose path runs no Pallas kernel (the XLA
    forms have no block geometry) or cannot resolve on this host. This is
    the same resolution pass the kernel call will make — including the
    bucket-axis clamp — so the segment-axis knobs shown are the geometry
    that ran (row-axis knobs can still shrink inside the glue when the
    batch is smaller than the block).
    """
    import dataclasses

    from repro.core import policy as kpolicy

    probe = dataclasses.replace(kpolicy.get_policy(),
                                interpret_fallback="silent")
    try:
        # the "auto" rows execute with policy=None (ambient resolution),
        # so their label must probe the same way — an explicit "auto"
        # would ignore the active policy's path/op_paths
        resolved = probe.resolve(op=op, n=n, dtype=dtype,
                                 explicit=None if path == "auto" else path)
    except (RuntimeError, ValueError):
        return "-"
    if resolved not in ("tile_tpu", "tile_gpu", "tile_logdepth",
                        "interpret"):
        return "-"
    spec = resolved.tuning
    return spec.label() if spec is not None else "-"


def time_stats(fn, *args, iters: int = 5, warmup: int = 2) -> dict:
    """Wall-clock statistics per call of an already-jit'd fn.

    The ``warmup`` calls run first and are *discarded* — they absorb the
    jit compile and any first-touch allocation, so the measured ``iters``
    time steady state only. Reports the median with the interquartile
    range (p25/p75) rather than a bare mean: serving-container wall
    clocks have heavy-tailed noise, and every bench row records the
    ``iters``/``warmup`` that produced it so two runs are comparable.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    p25, p50, p75 = (float(x) for x in np.percentile(ts, (25, 50, 75)))
    return {"median_s": p50, "p25_s": p25, "p75_s": p75,
            "iqr_s": p75 - p25, "iters": iters, "warmup": warmup}


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall seconds per call of an already-jit'd fn (compile and
    warmup discarded — see :func:`time_stats`)."""
    return time_stats(fn, *args, iters=iters, warmup=warmup)["median_s"]


def elems_per_sec(n_elems: int, seconds: float) -> float:
    return n_elems / max(seconds, 1e-12)


# ---------------------------------------------------------------------------
# bandwidth / roofline model
#
# Reduction and scan are bandwidth-bound (the paper's premise): the useful
# work per element is O(1), so the honest cross-machine metric is achieved
# memory bandwidth against the device's published peak
# (``repro.launch.peaks``, keyed by device kind; an unknown kind raises).


def peak_gbps() -> float:
    """This device's published peak memory bandwidth in GB/s."""
    from repro.launch.peaks import peaks_for

    return peaks_for().hbm_bytes_s / 1e9


def bandwidth_model(bytes_moved: int, seconds: float) -> dict:
    """Roofline annotation for one timed kernel call: achieved GB/s for
    ``bytes_moved`` (the op's minimal read+write traffic) against this
    host's :func:`peak_gbps`."""
    peak = peak_gbps()
    achieved = bytes_moved / max(seconds, 1e-12) / 1e9
    return {"bytes_moved": int(bytes_moved),
            "achieved_gbps": round(achieved, 4),
            "peak_gbps": peak,
            "pct_peak": round(100.0 * achieved / peak, 3)}


def hlo_op_mix(fn, *args) -> dict:
    """Loop-aware op-mix from the compiled HLO (the paper's §6.3 proxy:
    count matmul-form vs vector-ALU work)."""
    import sys
    sys.path.insert(0, "src")
    from repro.launch.hlo_analysis import (ELEMWISE_1, _instr_flops,
                                           parse_computations, analyse)

    compiled = jax.jit(fn).lower(*args).compile()
    txt = compiled.as_text()
    h = analyse(txt)
    comps = parse_computations(txt)
    dot_flops = 0.0
    vpu_flops = 0.0
    for comp in comps.values():
        for instr in comp.instrs:
            f = _instr_flops(instr, comp)
            if instr.opcode in ("dot", "convolution"):
                dot_flops += f
            else:
                vpu_flops += f
    return {"total_flops": h["flops"], "dot_flops": dot_flops,
            "vpu_flops": vpu_flops, "memory_bytes": h["memory_bytes"]}


def print_csv(name: str, cols: list, rows: list) -> None:
    print(f"\n# {name}")
    print(",".join(cols))
    for row in rows:
        print(",".join(str(x) for x in row))
