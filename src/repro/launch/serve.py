"""Serving driver: batched generation with the continuous-batching engine
(or the wave baseline via --scheduler wave).

CPU demo: reduced configs, randomly initialised weights (or a checkpoint
produced by launch/train.py via --ckpt-dir) — the point is the serving
path: chunked prefill interleaved with decode over a ring KV cache, with
the model's softmax/RMSNorm/SSD all routing through the matmul-form
primitives. --arrival-rate spreads the synthetic requests as open-loop
Poisson arrivals instead of presenting them all at once.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import ckpt
from repro.launch import compile_cache
from repro.models import build
from repro.models.common import init_params
from repro.obs import cli as obs_cli
from repro.serving import Request, ServeConfig, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--config", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--scheduler", choices=("continuous", "wave"),
                    default="continuous",
                    help="continuous batching (per-slot admission, ring "
                         "KV cache, chunked prefill) or the wave baseline")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens a prefilling slot consumes per "
                         "tick (continuous scheduler)")
    ap.add_argument("--cache", choices=("ring", "paged"), default="ring",
                    help="KV-cache layout (continuous scheduler): per-slot "
                         "ring buffers, or the paged block-table pool with "
                         "prompt-prefix sharing and copy-on-write")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged cache: total KV pool pages (default "
                         "(slots+1) x pages-per-slot)")
    ap.add_argument("--page-rows", type=int, default=None,
                    help="paged cache: rows per page — a power-of-two "
                         "multiple of the sublane tile (default "
                         "kernels/layout.KV_PAGE_ROWS)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in requests/s "
                         "(0: all requests available immediately)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling RNG seed (and synthetic request seed)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default=None,
                    help="mesh axes as 'data=1,model=2' (multiplies to the "
                         "global device count): serve sharded — the ring "
                         "KV cache splits over the model axis and the "
                         "kernel policy resolves per-shard TuneSpecs")
    from repro.core import dispatch
    from repro.core import policy as kpolicy

    ap.add_argument("--policy", default=None,
                    help="KernelPolicy for every core op in the served "
                         "model: a path label, an op=path,op=path override "
                         "list, or a JSON object of policy fields "
                         "(default: the active policy)")
    ap.add_argument("--tune", default=None,
                    help="per-op kernel tuning overrides layered on the "
                         "policy: op.knob=value pairs, e.g. "
                         "'ssd.q=64,attention.block_q=256'")
    ap.add_argument("--kernel-path", default=None, choices=dispatch.PATHS,
                    help="deprecated alias for --policy <path-label>")
    obs_cli.add_obs_args(ap)
    args = ap.parse_args()
    compile_cache.enable()

    pol = kpolicy.policy_from_cli(args.policy, args.kernel_path,
                                  "deprecated:launch.serve.kernel_path",
                                  tune_arg=args.tune)

    with obs_cli.obs_scope(args):
        run(args, pol)


def run(args, pol) -> None:
    mod = configs.get(args.arch)
    cfg = mod.SMOKE if args.config == "smoke" else mod.FULL
    bundle = build(cfg)
    mesh_ctx = None
    if args.mesh:
        from repro.parallel.mesh_context import make_context

        mesh_ctx = make_context(args.mesh)
        print(f"serving sharded over mesh {mesh_ctx.label()}")
    params = init_params(jax.random.PRNGKey(0), bundle.params_pspec,
                         cfg.dtype)
    if mesh_ctx is not None:
        from repro.models.common import partition_specs

        specs = partition_specs(bundle.params_pspec, rules=mesh_ctx.rules,
                                fsdp_ok=False)
        shardings = jax.tree.map(mesh_ctx.named_sharding, specs)
        params = jax.tree.map(jax.device_put, params, shardings)
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state = ckpt.restore(
                args.ckpt_dir, latest, {"params": params},
                shardings=None if mesh_ctx is None
                else {"params": shardings})
            params = state["params"]
            print(f"loaded checkpoint step {latest}")

    engine = ServingEngine(bundle, params, ServeConfig(
        slots=args.slots, max_new=args.max_new, policy=pol,
        scheduler=args.scheduler, prefill_chunk=args.prefill_chunk,
        cache_kind=args.cache, pool_pages=args.pool_pages,
        page_rows=args.page_rows,
        seed=args.seed), mesh_ctx=mesh_ctx)
    rng = np.random.default_rng(args.seed)
    arrival = 0.0
    reqs = []
    for i in range(args.requests):
        if args.arrival_rate > 0:
            arrival += float(rng.exponential(1.0 / args.arrival_rate))
        reqs.append(Request(uid=i, prompt=rng.integers(
            3, cfg.vocab, size=rng.integers(4, args.prompt_len + 1),
            dtype=np.int32), arrival_s=arrival))

    t0 = time.time()
    results = engine.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in results)
    for r in results[:4]:
        print(f"req {r.uid}: prompt_len={r.prompt_len} -> "
              f"{len(r.tokens)} tokens: {r.tokens[:12]}")
    print(f"{len(results)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s, "
          f"scheduler={engine.scheduler})")
    kv = engine.kv_stats()
    if kv is not None:
        print(f"paged KV pool: peak {kv.get('peak_pages_in_use', 0)}/"
              f"{kv.get('pages_total', 0)} pages, "
              f"{kv['shared_tokens']} prompt tokens prefix-shared, "
              f"{kv['cow_copies']} CoW copies, {kv['defers']} admissions "
              "deferred")
    if args.arrival_rate > 0:
        lats = [1e3 * (ts - r.arrival_s)
                for r in results for ts in r.token_s]
        if lats:
            print(f"open loop @ {args.arrival_rate:.1f} req/s: token "
                  f"latency p50={np.percentile(lats, 50):.1f}ms "
                  f"p99={np.percentile(lats, 99):.1f}ms")


if __name__ == "__main__":
    main()
