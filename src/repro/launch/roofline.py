"""Roofline report: aggregate the dry-run artifacts into the §Roofline table.

Reads ``artifacts/dryrun/single/*.json`` (the roofline table is single-pod
per the brief; multi-pod artifacts prove the pod axis shards) and emits a
markdown table with, per (arch x shape):

  compute_s    = HLO_FLOPs / (chips x 197 TFLOP/s)      [per-chip form]
  memory_s     = HLO_bytes / (chips x 819 GB/s)
  collective_s = collective_bytes / (chips x 50 GB/s)
  dominant term, MODEL_FLOPS/HLO_FLOPs ratio, and a one-line lever.

All three terms are computed from per-chip quantities (the SPMD module is
the per-device program), which is numerically identical to the brief's
global-quantity / (chips x peak) form.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.launch.peaks import PEAKS

# the dry-run's production meshes are v5e pods
_V5E = PEAKS["TPU v5 lite"]

LEVERS = {
    "compute_s": "raise MXU occupancy: fewer rematerialised dots, "
                 "larger fused matmul tiles",
    "memory_s": "cut HBM traffic: fuse attention softmax chain (Pallas "
                "flash kernel), bf16 intermediates, wider fusion",
    "collective_s": "cut collective bytes: reduce-scatter instead of "
                    "all-reduce+slice, overlap FSDP gathers, shrink "
                    "replicated KV/router traffic",
}


def load_records(art_dir: str, mesh: str = "single") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(art_dir, mesh, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def fmt_row(rec: dict) -> str:
    r = rec["roofline"]
    h = rec["hlo_analysis"]
    ratio = rec["useful_flops_ratio"]
    frac = {
        k: r[k] / max(r["bound_s"], 1e-30)
        for k in ("compute_s", "memory_s", "collective_s")
    }
    # roofline fraction: useful model compute time / bound time
    mf_s = rec["model_flops_per_chip"] / _V5E.bf16_flops
    roofline_frac = mf_s / max(r["bound_s"], 1e-30)
    return (f"| {rec['arch']} | {rec['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"{r['dominant'].replace('_s', '')} | {ratio:.2f} | "
            f"{roofline_frac:.3f} |")


def main() -> None:
    ap = argparse.ArgumentParser()
    default_art = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "..",
                     "artifacts", "dryrun"))
    ap.add_argument("--artifacts", default=default_art)
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()

    recs = [r for r in load_records(args.artifacts, args.mesh)
            if r.get("status") == "ok"]
    print("| arch | shape | compute_s | memory_s | collective_s | dominant "
          "| MODEL/HLO flops | roofline frac |")
    print("|---|---|---|---|---|---|---|---|")
    for rec in sorted(recs, key=lambda r: (r["arch"], r["shape"])):
        print(fmt_row(rec))
    print()
    doms: dict[str, int] = {}
    worst = sorted(
        recs, key=lambda r: (r["model_flops_per_chip"] / _V5E.bf16_flops)
        / max(r["roofline"]["bound_s"], 1e-30))
    for rec in recs:
        doms[rec["roofline"]["dominant"]] = doms.get(
            rec["roofline"]["dominant"], 0) + 1
    print(f"dominant-term histogram: {doms}")
    if worst:
        print("worst roofline fractions:")
        for rec in worst[:5]:
            r = rec["roofline"]
            mf_s = rec["model_flops_per_chip"] / _V5E.bf16_flops
            print(f"  {rec['arch']:24s} {rec['shape']:12s} "
                  f"frac={mf_s / max(r['bound_s'], 1e-30):.4f} "
                  f"dom={r['dominant']} lever: {LEVERS[r['dominant']]}")


if __name__ == "__main__":
    main()
