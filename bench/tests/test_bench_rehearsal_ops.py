"""CPU rehearsal of the kernel cells at toy sizes (interpreted kernels):
the whole run but the look for a chip. Its numbers are not device
measurements and are never printed as such."""
import time

import pytest

from bench.lib import harness, spec
from bench.tests.conftest import ROOT

CELLS = ["tcu-ops-f32.small-seg", "tcu-ops-f32.large-seg"]


def rehearse(name, trace=False, seed=2 ** 40 + 3):
    cell = spec.load_cell(ROOT, name)
    return cell, harness.run_cell(cell, seed=seed, seconds=0.5, trace=trace,
                                  t_start=time.perf_counter(),
                                  rehearsal=True)


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_is_correct_and_reports_its_metrics(name, capsys):
    cell, out = rehearse(name)
    err = capsys.readouterr().err
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "compilations inside the window: 0" in err
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"reduce_err", "scan_err"}


def test_traced_rehearsal_reads_its_trace(capsys):
    _, out = rehearse(CELLS[0], trace=True)
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device trace: the per-layer readers stay silent
    assert out["metrics"] == {}


def test_an_altered_answer_is_not_correct(monkeypatch):
    import repro.ops as rops

    real = rops.scan

    def altered(x, **kw):
        out = real(x, **kw)
        return out.at[0, -1].add(1.0)     # one answer changed at the source
    monkeypatch.setattr(rops, "scan", altered)
    _, out = rehearse(CELLS[0])
    assert out["correct"] is False
    scan = out["checks"]["scan_err"]
    assert scan["value"] > scan["limit"]


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 1, 2 ** 40 + 5])
def test_control_in_lower_precision_reads_far_above_the_program(seed):
    """The control (the reference with its input rounded as a three-pass
    bf16 matmul rounds it) against the program, at the rehearsal size."""
    cell = spec.load_cell(ROOT, CELLS[0])
    run = harness.Run(cell=cell, seed=seed, seconds=0.3, trace=False,
                      rehearsal=True, device={}, peaks=None)
    sut = cell.system().setup(run)
    rec = sut.window(run)
    sut.release()
    program = sut.check(run, rec)
    control = sut.check(run, rec, outputs=cell.reference().lower_precision)
    for p, c in zip(program, control):
        assert p.ok and not c.ok, (p, c)
        assert c.value > 3 * p.value, (p, c)
