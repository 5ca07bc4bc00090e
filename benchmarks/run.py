"""Run every benchmark (one per paper table/figure) and print CSV blocks.

  python -m benchmarks.run                  # all
  python -m benchmarks.run fig10            # substring filter
  python -m benchmarks.run --backend gpu    # keep only this backend's
                                            # tile contenders; rows whose
                                            # path can't resolve on this
                                            # host are skipped, not fatal
  python -m benchmarks.run --policy reduce=tile,scan=baseline
                                            # pin per-op choices for the
                                            # sweep's "auto" rows (JSON
                                            # policy objects work too);
                                            # --kernel-path <label> is the
                                            # deprecated spelling of
                                            # --policy <label>
  python -m benchmarks.run --tune ssd.q=64  # override kernel geometry for
                                            # the tile contender rows (the
                                            # tuning= column shows what ran)
"""
from __future__ import annotations

import argparse
import importlib
import time

from benchmarks import common

BENCHES = [
    ("fig2_3_gemm_gemv", "benchmarks.gemm_bench"),
    ("fig10_segmented_reduce", "benchmarks.segmented_reduce_bench"),
    ("fig11_small_segments", "benchmarks.small_segment_bench"),
    ("fig12_segmented_scan", "benchmarks.segmented_scan_bench"),
    ("fig13_14_full_reduce_scan", "benchmarks.full_collectives_bench"),
    ("sec6_3_alu_mix_power_proxy", "benchmarks.alu_mix_bench"),
    ("ssd_weighted_scan", "benchmarks.ssd_bench"),
    ("serving_open_loop", "benchmarks.serving_bench"),
]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("filter", nargs="?", default="",
                    help="substring filter on benchmark names")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "cpu", "gpu", "tpu"),
                    help="which backend's kernel contenders to include; "
                         "paths unresolvable on the current host are "
                         "skipped with a note instead of crashing")
    ap.add_argument("--policy", default=None,
                    help="KernelPolicy the sweep runs under: a path "
                         "label, an op=path,op=path override list (pins "
                         "per-op choices for the auto rows), or a JSON "
                         "object of policy fields")
    ap.add_argument("--tune", default=None,
                    help="per-op kernel tuning overrides layered on the "
                         "policy: op.knob=value pairs, e.g. "
                         "'ssd.q=64,reduce.block_n=256' (shown in each "
                         "benchmark's tuning= column)")
    ap.add_argument("--kernel-path", default=None,
                    help="deprecated alias for --policy <path-label>")
    ap.add_argument("--json-dir", default=".",
                    help="directory the BENCH_<name>.json row files land "
                         "in (created if missing)")
    from repro.obs import cli as obs_cli

    obs_cli.add_obs_args(ap)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    compile_cache.enable()
    common.set_bench_backend(args.backend)
    common.set_bench_json_dir(args.json_dir)

    from repro.core import policy as kpolicy

    pol = kpolicy.policy_from_cli(args.policy, args.kernel_path,
                                  "deprecated:benchmarks.run.kernel_path",
                                  tune_arg=args.tune)
    if pol is not None:
        kpolicy.set_policy(pol)

    with obs_cli.obs_scope(args):
        t0 = time.time()
        ran = 0
        for name, module in BENCHES:
            if args.filter and args.filter not in name:
                continue
            m = importlib.import_module(module)
            t = time.time()
            m.main()
            print(f"# [{name}] {time.time() - t:.1f}s")
            ran += 1
        print(f"\n# {ran} benchmarks in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
