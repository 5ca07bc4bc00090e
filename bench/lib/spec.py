"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration and traffic. Everything else is found from those names:

* ``configs[].file``: the configuration as run. Its ``system`` names
  ``bench/systems/<system>.py`` (the code that drives the program), its
  ``reference`` a plain reference beside it in ``bench/configs/``;
* ``bench/traffic/<traffic>.json``: the traffic parameters;
* ``bench/metrics/<metric>.py``: one reader per metric, for every
  end-to-end and per-layer metric of ``BENCHMARK.json`` whose
  ``workloads`` lists the cell (or that has no ``workloads``).

So a later change adds a cell, a traffic mix, a configuration or a
metric by adding files and entries, without editing a file here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # BENCHMARK.json metric entries of this cell
    per_layer: list
    root: Path

    def module(self, kind: str, name: str) -> ModuleType:
        """``bench/<kind>/<name>.py`` of this checkout."""
        return load_module(self.root / "bench" / kind / f"{name}.py")

    def system(self) -> ModuleType:
        return self.module("systems", self.config["system"])

    def reference(self) -> ModuleType:
        return self.module("configs", self.config["reference"])


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
        root=root)


def rehearsal(params: dict) -> dict:
    """A configuration or traffic file with its ``rehearsal`` block laid
    over it: the toy sizes of the CPU rehearsal."""
    out = {k: v for k, v in params.items() if k != "rehearsal"}
    out.update(params.get("rehearsal", {}))
    return out
