"""A later change adds a cell, a configuration, a traffic mix and a
metric as files and entries only: the harness finds them by name."""
import json
import time

import pytest

from bench.lib import harness, spec

TOY_SYSTEM = '''
import time
from bench.lib.harness import Check


def setup(run):
    return Toy(run)


class Toy:
    def __init__(self, run):
        self.n = run.traffic["n"]

    def window(self, run):
        t0 = time.perf_counter()
        total = sum(range(self.n))
        run.window_s = time.perf_counter() - t0 + 1e-9
        run.attempted, run.failed = 1, 0
        self.total = total
        return {"items": self.n}

    def release(self):
        pass

    def check(self, run, record):
        want = run.cell.reference().total(self.n)
        return [Check("abs_err", abs(self.total - want), 0.0)]
'''


def _drop_files(root):
    (root / "bench" / "systems").mkdir(parents=True)
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    (root / "bench" / "systems" / "toy.py").write_text(TOY_SYSTEM)
    (root / "bench" / "configs" / "toy_ref.py").write_text(
        "def total(n):\n    return n * (n - 1) // 2\n")
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"system": "toy", "reference": "toy_ref"}))
    (root / "bench" / "traffic" / "few.json").write_text(json.dumps(
        {"n": 1000, "rehearsal": {"n": 10}}))
    (root / "bench" / "metrics" / "items_s.py").write_text(
        "def read(run):\n    return run.record['items'] / run.window_s\n")
    (root / "bench" / "metrics" / "silent.py").write_text(
        "def read(run):\n    return None\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.few", "config": "toy",
                       "traffic": "few", "chips": 1}],
        "end_to_end": [{"name": "items_s", "unit": "items/s"},
                       {"name": "other", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "silent", "unit": "%"}]}))


def test_a_cell_made_of_dropped_files_runs(tmp_path):
    _drop_files(tmp_path)
    cell = spec.load_cell(tmp_path, "toy.few")
    assert [m["name"] for m in cell.end_to_end] == ["items_s"]
    out = harness.run_cell(cell, seed=5, seconds=0.1, trace=False,
                           t_start=time.perf_counter(), rehearsal=True)
    assert out["correct"] is True
    assert list(out["metrics"]) == ["items_s"]
    assert out["metrics"]["items_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"] == {"abs_err": {"value": 0, "limit": 0.0}}


def test_rehearsal_sizes_lay_over_the_file():
    assert spec.rehearsal({"n": 1000, "m": 2, "rehearsal": {"n": 10}}) == \
        {"n": 10, "m": 2}


def test_an_unknown_cell_is_refused(tmp_path):
    _drop_files(tmp_path)
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell(tmp_path, "toy.many")


def test_a_missing_file_is_named(tmp_path):
    _drop_files(tmp_path)
    (tmp_path / "bench" / "systems" / "toy.py").unlink()
    cell = spec.load_cell(tmp_path, "toy.few")
    with pytest.raises(FileNotFoundError, match="toy.py"):
        cell.system()
