"""Training driver: data-parallel + TP training with checkpoint/restart,
straggler reporting, and deterministic resume.

This is the end-to-end path the fault-tolerance story hangs off:

  * periodic checkpointing (step- and wall-clock-triggered) with atomic
    commit (checkpoint/ckpt.py);
  * ``--resume auto`` restores the latest valid manifest and re-places it
    under the *current* mesh's shardings — elastic restarts across
    different chip counts;
  * per-step wall time is logged; steps slower than ``straggler_factor x``
    the running median are flagged (on a multi-host cluster this feeds the
    host-replacement loop);
  * data order is a pure function of (seed, step), so replacing a host
    never drifts the global batch (data/pipeline.py).

On this CPU container it runs the reduced smoke configs; on a real cluster
the same file runs the FULL configs (the mesh/rules scale with
``jax.device_count()``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import ckpt
from repro.configs.common import SMOKE_BATCH, SMOKE_SEQ
from repro.data.pipeline import DataConfig, SyntheticLMPipeline
from repro.launch import compile_cache
from repro.models import build
from repro.obs import cli as obs_cli
from repro.obs import profiling as _prof
from repro.optim import OptConfig
from repro.parallel.mesh_context import MeshContext, make_context
from repro.training import TrainConfig, init_train_state, make_train_step


def build_mesh_context(tp: int, mesh_arg: str | None = None) -> MeshContext:
    """The training MeshContext: ``--mesh data=2,model=2`` wins; otherwise
    the legacy ``--tp`` split of whatever devices exist."""
    if mesh_arg:
        return make_context(mesh_arg)
    dp = jax.device_count() // tp
    return make_context((("data", dp), ("model", tp)))


def build_mesh_and_rules(tp: int):
    """Deprecated spelling of :func:`build_mesh_context` (kept for older
    scripts); returns the context's (mesh, rules) pair."""
    ctx = build_mesh_context(tp)
    return ctx.mesh, ctx.rules


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--config", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=SMOKE_BATCH * 2)
    ap.add_argument("--seq", type=int, default=SMOKE_SEQ)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--mesh", default=None,
                    help="mesh axes as 'data=2,model=2' (multiplies to the "
                         "global device count); overrides --tp")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-every-s", type=float, default=600.0)
    ap.add_argument("--keep-last", type=int, default=3,
                    help="committed checkpoints to keep (0 keeps all)")
    ap.add_argument("--resume", choices=("auto", "none"), default="auto")
    ap.add_argument("--straggler-factor", type=float, default=1.5)
    ap.add_argument("--straggler-report", default=None,
                    help="jsonl path for per-step timing records")
    ap.add_argument("--log-every", type=int, default=10)
    from repro.core import dispatch
    from repro.core import policy as kpolicy

    ap.add_argument("--policy", default=None,
                    help="KernelPolicy for the model's core ops and the "
                         "optimizer's global-norm reduce: a path label, "
                         "an op=path,op=path override list, or a JSON "
                         "object of policy fields")
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
                                  "deprecated:launch.train.kernel_path",
                                  tune_arg=args.tune)

    with obs_cli.obs_scope(args) as obs_sess:
        run(args, pol, obs_sess)


def run(args, pol, obs_sess=None) -> None:
    mod = configs.get(args.arch)
    cfg = mod.SMOKE if args.config == "smoke" else mod.FULL
    if pol is not None:
        cfg = dataclasses.replace(cfg, policy=pol)
    bundle = build(cfg)
    mesh_ctx = build_mesh_context(args.tp, args.mesh)
    opt_cfg = OptConfig(peak_lr=args.lr, warmup_steps=min(20, args.steps),
                        decay_steps=args.steps, policy=pol)
    train_cfg = TrainConfig(microbatches=args.microbatches)
    ckpt_writer = ckpt.AsyncCheckpointer(
        args.ckpt_dir, keep_last=args.keep_last or None)

    with mesh_ctx:
        state = init_train_state(jax.random.PRNGKey(0), bundle, opt_cfg,
                                 train_cfg)
        step_fn = jax.jit(
            make_train_step(bundle, opt_cfg, train_cfg, mesh_ctx=mesh_ctx),
            donate_argnums=(0,))

        start = 0
        if args.resume == "auto":
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                from repro.training import train_state_pspecs

                specs = train_state_pspecs(bundle, mesh_ctx.rules,
                                           train_cfg)
                shardings = jax.tree.map(mesh_ctx.named_sharding, specs)
                state = ckpt.restore(args.ckpt_dir, latest, state,
                                     shardings=shardings)
                start = latest
                print(f"resumed from step {latest}")

        data = SyntheticLMPipeline(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
        data.skip_to(start)

        times: list[float] = []
        last_ckpt_t = time.time()
        for step in range(start, args.steps):
            batch = data.device_batch(step)
            if cfg.stub_tokens:
                batch["stub"] = jnp.zeros(
                    (args.batch, cfg.stub_tokens, cfg.stub_dim), cfg.dtype)
            if cfg.family == "encdec":
                batch = {"frames": jnp.zeros(
                    (args.batch, args.seq, cfg.d_model), cfg.dtype),
                    "tokens": batch["tokens"], "labels": batch["labels"]}
            t0 = time.time()
            with _prof.span("train/step"):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            times.append(dt)
            if obs_sess is not None:
                obs_sess.histogram(
                    "repro_train_step_seconds",
                    "optimizer step wall time").observe(dt)
                obs_sess.gauge(
                    "repro_train_tokens_per_s",
                    "training throughput at the last step").set(
                    args.batch * args.seq / max(dt, 1e-9))
                obs_sess.emit("train_step", step=step, seconds=dt,
                              loss=float(metrics["loss"]))

            med = float(np.median(times[-50:]))
            straggle = len(times) > 5 and dt > args.straggler_factor * med
            if args.straggler_report:
                with open(args.straggler_report, "a") as f:
                    f.write(json.dumps({"step": step, "dt": dt,
                                        "median": med,
                                        "straggler": straggle}) + "\n")
            if straggle:
                print(f"[straggler] step {step}: {dt:.3f}s vs median "
                      f"{med:.3f}s")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt:.3f}s")

            due_steps = (step + 1) % args.ckpt_every == 0
            due_time = time.time() - last_ckpt_t > args.ckpt_every_s
            if due_steps or due_time or step == args.steps - 1:
                # async: snapshots now, writes in the background; the next
                # save (or the final wait below) is the commit barrier
                ckpt_writer.save(step + 1, state)
                last_ckpt_t = time.time()
                print(f"checkpoint scheduled @ step {step + 1}")

        path = ckpt_writer.wait()
        print(f"checkpointed -> {path}")

    print(f"done: {args.steps - start} steps, "
          f"median step {np.median(times):.3f}s")


if __name__ == "__main__":
    main()
