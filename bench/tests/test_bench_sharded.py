"""The four-chip sharded cell: a CPU rehearsal on four forced host devices
(in a subprocess, since the device count is fixed when JAX starts), and
the arithmetic of its per-layer readers, which read the program's own
spans and counters and stay silent where the program has none."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from bench.lib import spec
from bench.tests.conftest import ROOT

CELL = "tcu-ops-f32-mesh4.sharded"
NEW = ("dispatch_share.sharded", "compile_share.sharded",
       "compiles_per_call.sharded")


def metric(name):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def rehearsals():
    """An untraced and a traced rehearsal of the cell, as result objects."""
    code = textwrap.dedent(f"""
        import json, os, sys, time
        from pathlib import Path
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        from bench.lib import harness, spec
        cell = spec.load_cell(Path({str(ROOT)!r}), {CELL!r})
        # windows of 2 s: the round's first call, an eager routed reduce,
        # takes up to about 0.4 s on a loaded CPU, and only a call started
        # before the deadline runs, so a shorter window can miss the scan
        for trace in (False, True):
            out = harness.run_cell(cell, seed=2 ** 40 + 7, seconds=2.0,
                                   trace=trace, t_start=time.perf_counter(),
                                   rehearsal=True)
            print("RESULT " + json.dumps(out))
        # the control: the reference with its input rounded as a three-pass
        # bf16 matmul rounds it, in place of the program's outputs
        run = harness.Run(cell=cell, seed=2 ** 33 + 9, seconds=2.0,
                          trace=False, rehearsal=True, device=dict(), peaks=None)
        sut = cell.system().setup(run)
        rec = sut.window(run)
        sut.release()
        checks = dict(program=sut.check(run, rec),
                      control=sut.check(run, rec, outputs=cell.reference()
                                        .lower_precision))
        print("CONTROL " + json.dumps(dict(
            (k, [[c.name, c.value, c.ok] for c in v])
            for k, v in checks.items())))
    """)
    # a clean environment but the run's own HOME and TMPDIR, so the traced
    # rehearsal's profiler directory stays where the test's files go
    env = {"PATH": "/usr/bin:/bin"}
    for key in ("JAX_PLATFORMS", "HOME", "TMPDIR"):
        if key in os.environ:
            env[key] = os.environ[key]
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    return ([json.loads(line.split(" ", 1)[1]) for line in lines
             if line.startswith("RESULT ")], proc.stderr,
            json.loads(lines[-1].split(" ", 1)[1]))


def test_rehearsal_routes_over_four_devices_and_is_correct(rehearsals):
    (plain, traced), err, _ = rehearsals
    for out in (plain, traced):
        assert out["correct"] is True and out["failed"] == 0
        assert out["device"]["count"] == 4
        assert set(out["checks"]) == {"reduce_err", "scan_err"}
    cell = spec.load_cell(ROOT, CELL)
    assert set(plain["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"gb_s", "setup_s"}
    # the program rebuilds its shard_map program on every eager call: the
    # window compiles, which is what the cell's per-layer metrics read
    assert "compilations inside the window: 0" not in err


def test_traced_rehearsal_reads_the_programs_spans_and_counters(
        rehearsals):
    (_, traced), _, _ = rehearsals
    got = traced["metrics"]
    assert set(got) == set(NEW)     # the CPU has no device trace to idle
    assert 0 < got["dispatch_share.sharded"]["value"] <= 100
    assert 0 < got["compile_share.sharded"]["value"] <= 100
    assert got["compiles_per_call.sharded"]["value"] >= 1


def test_control_in_lower_precision_is_not_correct(rehearsals):
    *_, checks = rehearsals
    assert all(ok for _, _, ok in checks["program"])
    assert not all(ok for _, _, ok in checks["control"])
    prog = dict((n, v) for n, v, _ in checks["program"])
    ctl = dict((n, v) for n, v, _ in checks["control"])
    assert ctl["scan_err"] > 3 * prog["scan_err"]


def _series(**named):
    return {name: {"kind": "counter", "help": "", "series": [
        {"labels": labels, "value": v} for labels, v in series]}
        for name, series in named.items()}


def _run(obs=None, spans=None, window_s=2.0):
    summary = None if spans is None else SimpleNamespace(
        spans=spans, window_s=window_s)
    record = {"calls": {}, "bytes": 1}
    if obs is not None:
        record["obs"] = obs
    return SimpleNamespace(trace_summary=summary, record=record,
                           window_s=window_s)


def test_dispatch_share_is_the_union_of_ops_spans_over_the_window():
    ms = 1_000_000
    spans = {"ops/scan": [(0, 500 * ms), (400 * ms, 900 * ms)],
             "shard_ops/scan": [(100 * ms, 800 * ms)],
             "ops/reduce": [(1000 * ms, 1100 * ms)],
             "serving/block_step": [(1200 * ms, 2000 * ms)]}
    got = metric("dispatch_share.sharded").read(_run(spans=spans))
    assert got == pytest.approx(100 * 1.0 / 2.0)


def test_compile_share_is_repro_ops_compile_seconds_over_the_window():
    obs = _series(repro_compile_seconds_total=[
        ({"op": "scan", "stage": "trace"}, 0.1),
        ({"op": "scan", "stage": "lower"}, 0.2),
        ({"op": "scan", "stage": "compile"}, 0.4),
        ({"op": "scan", "stage": "cache_fetch"}, 0.3),   # inside compile
        ({"op": "reduce", "stage": "compile"}, 0.3),
        ({"op": "other", "stage": "compile"}, 5.0)])
    got = metric("compile_share.sharded").read(_run(obs=obs))
    assert got == pytest.approx(100 * 1.0 / 2.0)


def test_compiles_per_call_is_repro_ops_lowerings_over_calls():
    obs = _series(
        repro_compile_events_total=[
            ({"op": "scan", "stage": "lower"}, 60),
            ({"op": "scan", "stage": "compile"}, 60),
            ({"op": "reduce", "stage": "lower"}, 10),
            ({"op": "other", "stage": "lower"}, 7)],
        repro_ops_calls_total=[({"op": "scan", "routed": "true"}, 5),
                               ({"op": "reduce", "routed": "true"}, 5)])
    got = metric("compiles_per_call.sharded").read(_run(obs=obs))
    assert got == pytest.approx(7.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_are_silent_where_the_program_has_nothing(name):
    """The parent commit's program has no ops spans and no compile
    counters: the readers return None, untraced or traced."""
    read = metric(name).read
    assert read(_run()) is None
    assert read(_run(obs=_series(repro_sharded_dispatch_total=[
        ({"op": "scan"}, 3)]), spans={"serving/block_step": [(0, 5)]})) \
        is None
