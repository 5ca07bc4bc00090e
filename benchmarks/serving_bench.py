"""Beyond-paper table: open-loop serving — continuous batching vs the wave
baseline on one synthetic Poisson workload.

The paper's small-segment reduce/scan primitives do the per-token math of
a decode step (softmax, RMSNorm, SSD); whether they stay busy is a
scheduling question. This benchmark drives both schedulers with the same
open-loop arrival trace — mixed prompt/output lengths with one
deliberately long sequence near the front — and reports throughput and
per-token completion latency (emission minus request arrival). The wave
scheduler strands short requests behind the long sequence's wave barrier;
the continuous scheduler refills each slot as it frees, so the p99 gap is
the checked-in number the refactor is judged by.

A second contender drives a *shared-prefix* workload (every request
extends one long system prompt) through the continuous scheduler under
both KV-cache layouts: the per-slot ring baseline and the paged
block-table pool (``cache_kind="paged"``, serving/kvpool.py) whose prefix
trie maps the common pages once and copy-on-writes on divergence. Every
row records ``cache_kind`` and ``peak_pages_in_use`` (from the
``repro_kvpool_peak_pages_in_use`` obs gauge when a session is active),
so the memory win — peak pages strictly below N x full-context — is a
checked-in number.

Writes ``BENCH_serving.json`` (one row per scheduler x offered load,
plus the shared-prefix cache rows) and prints the usual CSV block.
``--budget tiny`` is the CI smoke shape.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

try:
    from benchmarks.common import bandwidth_model, print_csv
except ModuleNotFoundError:     # run as a script: sys.path[0] is
    import os                   # benchmarks/, not the repo root
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import bandwidth_model, print_csv

BUDGETS = {
    # n_req, slots, short max_new range, long max_new, prefill_chunk, loads
    "tiny": dict(n_req=8, slots=2, short=(3, 7), long_new=24,
                 prefill_chunk=8, loads=(8.0,),
                 prefix_len=40, tail=4, prefix_new=6, prefix_ctx=64),
    "full": dict(n_req=24, slots=4, short=(4, 12), long_new=48,
                 prefill_chunk=16, loads=(4.0, 16.0),
                 prefix_len=96, tail=8, prefix_new=12, prefix_ctx=128),
}


def make_workload(n_req, rate, vocab, *, short, long_new, seed=0):
    """Poisson arrivals at ``rate`` req/s; prompts 4-24 tokens; short
    decode budgets except request 1, which is deliberately long (the wave
    barrier the continuous scheduler must not inherit)."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    for i in range(n_req):
        t += float(rng.exponential(1.0 / rate))
        max_new = long_new if i == 1 else int(rng.integers(*short))
        prompt = rng.integers(3, vocab, size=int(rng.integers(4, 24)),
                              dtype=np.int32)
        reqs.append(Request(uid=i, prompt=prompt, max_new=max_new,
                            arrival_s=t))
    return reqs


def make_shared_prefix_workload(n_req, rate, vocab, *, prefix_len, tail,
                                max_new, seed=0):
    """Poisson arrivals where every prompt extends ONE ``prefix_len``-token
    system prompt with a short random tail — the paged cache's prefix trie
    maps the common pages once; the ring baseline re-prefills them per
    slot."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, vocab, size=prefix_len, dtype=np.int32)
    reqs, t = [], 0.0
    for i in range(n_req):
        t += float(rng.exponential(1.0 / rate))
        prompt = np.concatenate(
            [prefix, rng.integers(3, vocab, size=tail, dtype=np.int32)]
        ).astype(np.int32)
        reqs.append(Request(uid=i, prompt=prompt, max_new=max_new,
                            arrival_s=t))
    return reqs


def _metrics(results):
    lats = [1e3 * (ts - r.arrival_s) for r in results for ts in r.token_s]
    total = sum(len(r.tokens) for r in results)
    makespan = (max(r.finish_s for r in results)
                - min(r.arrival_s for r in results))
    p25, p50, p75 = (float(x) for x in np.percentile(lats, (25, 50, 75)))
    return {
        "throughput_tok_s": round(total / max(makespan, 1e-9), 2),
        "p50_ms": round(p50, 2),
        "p99_ms": round(float(np.percentile(lats, 99)), 2),
        "p25_ms": round(p25, 2),
        "p75_ms": round(p75, 2),
        "iqr_ms": round(p75 - p25, 2),
        "total_tokens": total,
        "makespan_s": round(makespan, 4),
    }


def run(budget: str = "tiny", arch: str = "llama3.2-1b",
        policy=None, mesh_ctx=None) -> list[dict]:
    import jax

    from repro import configs
    from repro.models import build
    from repro.models.common import init_params
    from repro.serving import ServeConfig, ServingEngine

    shape = BUDGETS[budget]
    mod = configs.get(arch)
    cfg = mod.SMOKE
    bundle = build(cfg)
    params = init_params(jax.random.PRNGKey(0), bundle.params_pspec,
                         cfg.dtype)
    # roofline proxy: a decode step streams the whole parameter set once
    # per generated token, which dominates traffic at batch sizes this
    # small — so bytes ~= param_bytes * total_tokens over the makespan
    param_bytes = sum(p.size * p.dtype.itemsize
                      for p in jax.tree.leaves(params))

    # sharded rows stay comparable to single-host history: every row
    # records the process count and the mesh shape it ran under
    mesh_label = "none" if mesh_ctx is None else mesh_ctx.label()

    def peak_pages(eng):
        """Peak pages-in-use: prefer the obs gauge (the number dashboards
        see), fall back to the engine's pool stats; None for ring."""
        from repro.obs import runtime as _obs

        kv = eng.kv_stats()
        if kv is None:
            return None
        if _obs.ACTIVE is not None:
            v = _obs.ACTIVE.gauge("repro_kvpool_peak_pages_in_use").value()
            if v is not None:
                return int(v)
        return kv.get("peak_pages_in_use", 0)

    def measure(eng, wl, base_row):
        eng.run(wl())                   # warmup: compiles out of the
        results = eng.run(wl())         # measured pass
        pol = eng.bundle.cfg.policy
        row = {"policy": "default" if pol is None else pol.label(),
               "n_req": shape["n_req"], "slots": shape["slots"],
               "arch": arch, "process_count": jax.process_count(),
               "mesh": mesh_label, "warmup_runs": 1, "measured_runs": 1,
               **base_row}
        row.update(_metrics(results))
        row.update(bandwidth_model(
            param_bytes * row["total_tokens"], row["makespan_s"]))
        row["peak_pages_in_use"] = peak_pages(eng)
        return row

    rows = []
    for rate in shape["loads"]:
        for sched in ("wave", "continuous"):
            if sched == "wave" and mesh_ctx is not None \
                    and jax.process_count() > 1:
                continue        # wave admission is per-host wall clock
            eng = ServingEngine(bundle, params, ServeConfig(
                slots=shape["slots"], max_new=16, eos_token=-1,
                scheduler=sched, prefill_chunk=shape["prefill_chunk"],
                policy=policy), mesh_ctx=mesh_ctx)
            wl = lambda: make_workload(
                shape["n_req"], rate, cfg.vocab,
                short=shape["short"], long_new=shape["long_new"])
            row = measure(eng, wl, {"scheduler": sched,
                                    "offered_load": rate,
                                    "workload": "mixed",
                                    "cache_kind": "ring"})
            if sched == "continuous":
                row["compiled_block_shapes"] = \
                    eng.compile_stats()["block"]
            rows.append(row)

        # shared-prefix contender: ring vs paged under the continuous
        # scheduler — the paged pool maps the common prompt pages once
        for kind in ("ring", "paged"):
            eng = ServingEngine(bundle, params, ServeConfig(
                slots=shape["slots"], max_new=shape["prefix_new"],
                eos_token=-1, scheduler="continuous",
                prefill_chunk=shape["prefill_chunk"],
                max_context=shape["prefix_ctx"], cache_kind=kind,
                policy=policy), mesh_ctx=mesh_ctx)
            wl = lambda: make_shared_prefix_workload(
                shape["n_req"], rate, cfg.vocab,
                prefix_len=shape["prefix_len"], tail=shape["tail"],
                max_new=shape["prefix_new"])
            row = measure(eng, wl, {"scheduler": "continuous",
                                    "offered_load": rate,
                                    "workload": "shared_prefix",
                                    "cache_kind": kind})
            if kind == "paged":
                kv = eng.kv_stats()
                row["pool_pages"] = kv["pages_total"]
                row["shared_prompt_tokens"] = kv["shared_tokens"]
                row["cow_copies"] = kv["cow_copies"]
            rows.append(row)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", choices=tuple(BUDGETS), default="tiny")
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--mesh", default=None,
                    help="serve sharded: mesh axes as 'data=1,model=2' "
                         "(must multiply to the device count)")
    from repro.obs import cli as obs_cli

    obs_cli.add_obs_args(ap)
    args = ap.parse_args(argv if argv is not None else [])
    from repro.launch import compile_cache

    compile_cache.enable()

    mesh_ctx = None
    if args.mesh:
        from repro.parallel.mesh_context import make_context

        mesh_ctx = make_context(args.mesh)
    # the obs scope opens before run(): the warmup pass is where the
    # engine compiles, so trace-time resolution events need it active
    with obs_cli.obs_scope(args):
        rows = run(args.budget, args.arch, mesh_ctx=mesh_ctx)
    cols = ["scheduler", "workload", "cache_kind", "offered_load",
            "throughput_tok_s", "p50_ms", "p99_ms", "iqr_ms",
            "achieved_gbps", "pct_peak", "total_tokens",
            "peak_pages_in_use"]
    print_csv("serving_open_loop",
              cols, [[r[c] for c in cols] for r in rows])
    with open(args.out, "w") as f:
        json.dump({"bench": "serving_open_loop", "budget": args.budget,
                   "arch": args.arch, "rows": rows}, f, indent=2)
    print(f"# wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
