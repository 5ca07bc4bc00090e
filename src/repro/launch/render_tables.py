"""Render the EXPERIMENTS.md §Dry-run and §Roofline tables from the
dry-run artifacts, replacing the <!-- DRYRUN_TABLE --> and
<!-- ROOFLINE_TABLE --> markers in place.

  PYTHONPATH=src python -m repro.launch.render_tables
"""
from __future__ import annotations

import glob
import json
import os

from repro.launch.peaks import PEAKS

# the dry-run's production meshes are v5e pods
_V5E = PEAKS["TPU v5 lite"]

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
ART = os.path.join(ROOT, "artifacts", "dryrun")

LEVERS = {
    "compute_s": "fewer remat dots / bigger fused tiles",
    "memory_s": "fuse attention/SSD chains (Pallas kernels), bf16 "
                "intermediates",
    "collective_s": "reduce-scatter forms, overlap FSDP gathers, trim "
                    "replicated KV",
}


def _load(mesh):
    out = {}
    for p in sorted(glob.glob(os.path.join(ART, mesh, "*.json"))):
        r = json.load(open(p))
        out[(r["arch"], r["shape"])] = r
    return out


def dryrun_table() -> str:
    single, multi = _load("single"), _load("multi")
    lines = [
        "| arch | shape | kind | peak GiB/chip (256c) | peak GiB/chip "
        "(512c) | compile s | HLO flops/chip | collective B/chip |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for key in sorted(single):
        s, m = single[key], multi.get(key)
        h = s["hlo_analysis"]
        lines.append(
            f"| {key[0]} | {key[1]} | "
            f"{'train' if key[1].startswith('train') else 'serve'} | "
            f"{s['memory']['peak_bytes'] / 2**30:.2f} | "
            f"{(m['memory']['peak_bytes'] / 2**30):.2f} | "
            f"{s['compile_s']:.0f} | {h['flops']:.3g} | "
            f"{h['collective_total']:.3g} |")
    import importlib

    from repro import configs as cfgs
    skips = []
    for arch in cfgs.all_arch_ids():
        mod = cfgs.get(arch)
        for shape, why in mod.SKIPS.items():
            skips.append(f"| {arch} | {shape} | skipped | {why} |")
    lines.append("")
    lines.append(f"{len(single)} cells x 2 meshes compiled. Skipped cells "
                 "(with reasons):")
    lines.append("")
    lines.append("| arch | shape | status | reason |")
    lines.append("|---|---|---|---|")
    lines.extend(skips)
    return "\n".join(lines)


def roofline_table() -> str:
    single = _load("single")
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant | "
        "MODEL/HLO flops | roofline frac | lever on dominant |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    worst = []
    doms = {}
    for key in sorted(single):
        r = single[key]
        t = r["roofline"]
        mf_s = r["model_flops_per_chip"] / _V5E.bf16_flops
        frac = mf_s / max(t["bound_s"], 1e-30)
        doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
        worst.append((frac, key))
        lines.append(
            f"| {key[0]} | {key[1]} | {t['compute_s']:.3g} | "
            f"{t['memory_s']:.3g} | {t['collective_s']:.3g} | "
            f"{t['dominant'].replace('_s', '')} | "
            f"{r['useful_flops_ratio']:.2f} | {frac:.4f} | "
            f"{LEVERS[t['dominant']]} |")
    worst.sort()
    lines.append("")
    lines.append(f"Dominant-term histogram: "
                 + ", ".join(f"{k.replace('_s','')}: {v}"
                             for k, v in sorted(doms.items())))
    lines.append("")
    lines.append("Worst roofline fractions (hillclimb candidates): "
                 + "; ".join(f"{a}×{s} ({f:.4f})"
                             for f, (a, s) in worst[:4]))
    return "\n".join(lines)


def _splice(text: str, tag: str, body: str) -> str:
    """Replace <!-- TAG --> or an existing BEGIN/END TAG region."""
    import re as _re

    begin, end = f"<!-- BEGIN {tag} -->", f"<!-- END {tag} -->"
    wrapped = f"{begin}\n{body}\n{end}"
    if begin in text:
        return _re.sub(_re.escape(begin) + r".*?" + _re.escape(end),
                       wrapped, text, flags=_re.S)
    return text.replace(f"<!-- {tag} -->", wrapped)


def main() -> None:
    path = os.path.join(ROOT, "EXPERIMENTS.md")
    text = open(path).read()
    text = _splice(text, "DRYRUN_TABLE", dryrun_table())
    text = _splice(text, "ROOFLINE_TABLE", roofline_table())
    open(path, "w").write(text)
    print("EXPERIMENTS.md tables rendered "
          f"({len(_load('single'))} single-pod cells).")


if __name__ == "__main__":
    main()
