"""One run of one cell: set-up, the measured window, the trace, the
metrics, the check against the reference, and the result line.

A cell's system (``bench/systems/<system>.py``) provides ``setup(run)``,
which makes the data or weights on the device and warms up every shape
the traffic uses, and returns an object with ``window(run)`` (drive the
program for ``run.seconds``, set ``run.window_s``, ``run.attempted`` and
``run.failed``; return the run's record for the metric readers),
``release()`` (free the program's state, keeping what the window
produced) and ``check(run, record)`` (compare that with the plain
reference; a list of :class:`Check`).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench.lib import spec, yardstick

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


@dataclasses.dataclass
class Check:
    """One number compared with the reference and its limit; the run is
    correct only where every value is at or below its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit   # NaN fails


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    device: dict
    peaks: yardstick.Peaks | None
    config: dict = None           # the configuration as this run uses it
    traffic: dict = None
    setup_s: float = 0.0
    window_s: float = 0.0         # set by the system's window
    record: dict = None
    trace_summary: object = None  # bench.lib.trace.Summary
    attempted: int = 0
    failed: int = 0

    def __post_init__(self):
        pick = spec.rehearsal if self.rehearsal else dict
        self.config = pick(self.cell.config)
        self.traffic = pick(self.cell.traffic)

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX traces and backend compiles (JAX monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.n += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class Tracer:
    """A profiler trace of the whole window, with the program's own spans
    on."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t0 = self.t1 = None

    def begin(self) -> None:
        """Called at the window's start."""
        import jax

        from repro.obs import profiling

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls would swamp the host
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        profiling._TRACING = True         # the program's spans record now
        self.t0 = time.perf_counter()

    def close(self) -> None:
        """Called at the window's end."""
        import jax

        from repro.obs import profiling

        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        profiling._TRACING = False
        jax.profiler.stop_trace()

    def remove(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: bool = False) -> dict:
    """Set up, measure, check; the result object of the run's last line."""
    import jax

    from bench.lib import trace as tracelib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peaks = None if rehearsal else yardstick.peaks_for(dev.device_kind)
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              rehearsal=rehearsal, device=device, peaks=peaks)
    run.log(f"cell {cell.name}: config {cell.config_name}, traffic "
            f"{cell.traffic_name}, seed {seed}, seconds {seconds}, "
            f"device {device}" + (" (CPU rehearsal: not a device "
                                  "measurement)" if rehearsal else ""))
    counter = CompileCounter()
    try:
        sut = cell.system().setup(run)
        run.setup_s = time.perf_counter() - t_start
        run.log(f"set-up: {run.setup_s:.3f} s, {counter.n} compilations")
        tracer = Tracer() if trace else None
        before = counter.n
        if tracer:
            tracer.begin()
        try:
            record = sut.window(run)
        finally:
            if tracer:
                tracer.close()
        run.log(f"compilations inside the window: {counter.n - before}")
    finally:
        counter.close()
    run.record = record
    device["memory_peak_bytes"] = memory_peak_bytes()
    if tracer:
        try:
            run.trace_summary = tracelib.reduce(
                tracer.dir, window_s=tracer.t1 - tracer.t0)
        finally:
            tracer.remove()
        device["busy_s"] = run.trace_summary.busy_s
        device["window_s"] = run.trace_summary.window_s
        for name, p in sorted(run.trace_summary.programs.items(),
                              key=lambda kv: -kv[1].device_s)[:8]:
            run.log(f"trace: {name} x{p.count}, device {p.device_s:.6f} s,"
                    f" kernels {p.kernel_s:.6f} s")

    sut.release()
    gc.collect()
    checks = sut.check(run, record)
    del sut
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.load_module(
            cell.root / "bench" / "metrics" / f"{m['name']}.py").read(run)
        if value is None:
            run.log(f"metric {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": run.failed == 0 and all(c.ok for c in checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace_summary is not None:
        out["breakdown"] = run.trace_summary.breakdown()
    if rehearsal:
        out["rehearsal"] = True      # toy sizes on the CPU: no measurement
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def emit(result: dict) -> None:
    """The result's checks as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(root: Path, workload: str, *, seed: int, seconds: float,
         trace: bool, t_start: float) -> int:
    cell = spec.load_cell(root, workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program at {src}/repro", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    for var in [v for v in os.environ if v.startswith("REPRO_")]:
        del os.environ[var]          # the committed program only
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {devices[0].platform}); this "
              "benchmark measures the chip and does not fall back",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: the cell needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    emit(run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=t_start))
    return 0
