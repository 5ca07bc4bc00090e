"""The trace reduction, on a trace recorded on a TPU v5e: two calls each
of the reduce (seg 16) and scan (seg 16 and 256) programs of
``repro.ops`` over 2^20 float32 elements, each inside a ``bench/call``
host span, with the program's spans on and Python tracing off."""
import pytest

from bench.lib import trace
from bench.tests.conftest import ROOT

PB = ROOT / "bench" / "tests" / "data" / "ops_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(PB), window_s=0.1)


def test_programs_and_their_executions(summary):
    assert summary.chips == 1
    assert {n: p.count for n, p in summary.programs.items()} == {
        "jit_bench_reduce_s16": 2, "jit_bench_scan_s16": 2,
        "jit_bench_scan_s256": 2}


def test_kernel_time_lies_inside_program_time(summary):
    for p in summary.programs.values():
        assert 0 < p.kernel_s <= p.device_s + 1e-12


def test_scan_at_seg16_pays_for_its_glue(summary):
    """The 16-wide rows are padded to 128 lanes and sliced back outside
    the kernel; the seg 256 scan needs no glue."""
    s16 = summary.program("jit_bench_scan_s16")
    s256 = summary.program("jit_bench_scan_s256")
    assert (s16.device_s - s16.kernel_s) / s16.device_s > 0.2
    assert (s256.device_s - s256.kernel_s) / s256.device_s < 0.01
    ops = dict(summary.top_ops)
    assert ops["jit_bench_scan_s16/pad.0"] > 0


def test_program_prefix_sums_every_match(summary):
    both = summary.program("jit_bench_scan")
    assert both.count == 4
    assert both.device_s == pytest.approx(
        summary.programs["jit_bench_scan_s16"].device_s
        + summary.programs["jit_bench_scan_s256"].device_s)


def test_busy_time_and_host_spans(summary):
    device = sum(p.device_s for p in summary.programs.values())
    assert 0 < summary.busy_s <= device + 1e-9
    assert len(summary.spans["bench/call"]) == 6
    assert all(e > s for s, e in summary.spans["bench/call"])


def test_breakdown_is_short_and_sorted(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0].endswith("tcu_segmented_scan.1")
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0


def test_union_of_intervals():
    assert trace.union_s([(0, 10), (5, 20), (30, 40)]) == 30e-9
    assert trace.union_s([]) == 0.0
    assert trace.union_s([(0, 100), (10, 20)]) == 100e-9


def test_names():
    assert trace.module_base("jit_block_step(1234567)") == "jit_block_step"
    assert trace.module_base("jit_f") == "jit_f"
    assert trace.op_base("%fusion.79 = bf16[16] fusion(x)") == "fusion.79"
    assert trace.is_kernel('%k.1 = f32[8] custom-call(x), '
                           'custom_call_target="tpu_custom_call"')
    assert not trace.is_kernel("%copy.1 = f32[8] copy(x)")


def test_idle_gap_is_named_by_the_host_event_that_covers_it():
    host = [("outer", 0, 1000), ("inner", 100, 300), ("late", 2000, 3000)]
    assert trace._gap_label((150, 250), host) == "inner"
    assert trace._gap_label((500, 600), host) == "outer"
    assert trace._gap_label((1500, 1600), host) == "no host event"


def test_a_directory_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.reduce(str(tmp_path), window_s=1.0)
