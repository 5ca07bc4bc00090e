"""The command refuses to measure anything but the chip."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["--workload", "tcu-ops-f32.small-seg", "--seed", str(2 ** 40 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], capture_output=True, text=True,
                          env=env, timeout=300, cwd=root)


def test_no_tpu_exits_non_zero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
