"""The metric readers: rates over all the work and all the window, and
shares of the traced device time."""
import json
from types import SimpleNamespace

import pytest

from bench.lib import spec, trace, yardstick
from bench.tests.conftest import ROOT

V5E = yardstick.peaks_for("TPU v5 lite")


def metric(name):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_every_metric_in_benchmark_json_has_a_reader():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(metric(m["name"]).read), m["name"]


def test_gb_s_is_all_bytes_over_all_the_window():
    run = SimpleNamespace(record={"bytes": 3e9}, window_s=1.5)
    assert metric("gb_s").read(run) == pytest.approx(2.0)


def test_trace_metrics_are_silent_without_a_trace():
    for name in ("tcu_roofline", "glue_share.tcu", "idle_share.kernel"):
        run = SimpleNamespace(trace_summary=None, record={"calls": {}},
                              peaks=V5E)
        assert metric(name).read(run) is None, name


def _traced(programs, busy_s=1.0, window_s=1.0):
    """A run whose trace holds the given ``{name: Program}``."""
    calls = {"bench_reduce_s16": {"op": "reduce", "seg": 16, "rows": 2 ** 22},
             "bench_scan_s16": {"op": "scan", "seg": 16, "rows": 2 ** 22}}
    summary = SimpleNamespace(
        program=lambda name: programs.get(name, trace.Program()),
        busy_s=busy_s, window_s=window_s, chips=1)
    return SimpleNamespace(trace_summary=summary, peaks=V5E,
                           record={"calls": calls})


def test_roofline_is_least_time_of_the_traced_calls_over_their_time():
    progs = {"jit_bench_reduce_s16": trace.Program(3, 0.03, 0.02),
             "jit_bench_scan_s16": trace.Program(2, 0.05, 0.01)}
    least = (3 * yardstick.op_bytes("reduce", 2 ** 22, 16)
             + 2 * yardstick.op_bytes("scan", 2 ** 22, 16)) / 819e9
    got = metric("tcu_roofline").read(_traced(progs))
    assert got == pytest.approx(100 * least / 0.08)


def test_glue_share_is_program_time_outside_the_kernels():
    progs = {"jit_bench_reduce_s16": trace.Program(3, 0.03, 0.02),
             "jit_bench_scan_s16": trace.Program(2, 0.05, 0.01)}
    got = metric("glue_share.tcu").read(_traced(progs))
    assert got == pytest.approx(100 * (0.08 - 0.03) / 0.08)


def test_shares_are_silent_where_no_call_ran_on_the_device():
    run = _traced({})
    assert metric("tcu_roofline").read(run) is None
    assert metric("glue_share.tcu").read(run) is None


def test_idle_share_is_the_window_outside_the_busy_union():
    run = _traced({}, busy_s=45.0, window_s=50.0)
    assert metric("idle_share.kernel").read(run) == pytest.approx(10.0)
    run.trace_summary.window_s = 0.0
    assert metric("idle_share.kernel").read(run) is None
