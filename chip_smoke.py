#!/usr/bin/env python3
"""Chip smoke test: the paper's reduce/scan kernels and a full-width
Mamba-2 server, on one TPU, through the public entry points.

    python chip_smoke.py                  # one chip: kernels, then serve
    python chip_smoke.py --four-chips     # four chips: sharded dispatch only
    python chip_smoke.py --cpu-rehearsal  # tiny sizes, interpreted kernels

Phases (one process; nothing here starts a child that touches JAX):

* **kernels** — ``repro.ops`` reduce, scan, weighted_scan, ssd, rmsnorm and
  attention under ``KernelPolicy(path="tile", interpret_fallback="error")``,
  the scan family again under ``tile_logdepth``. Each call is jitted, its
  compiled text must hold a ``tpu_custom_call`` (an oracle or XLA fallback
  fails the case), and its result must match ``repro.kernels.ref`` within
  the tolerance of its dtype.
* **serve** — ``mamba2-1.3b`` at its published widths, random weights from
  ``--seed``, served by ``ServingEngine`` (continuous scheduler, ring cache,
  the default policy with ``interpret_fallback="error"``): 4 requests with
  64–512-token prompts, 16 new tokens each, run twice (cold, then warm for
  tok/s; the warm run must repeat the cold run's tokens). Then one
  2048-token ``Bundle.prefill`` with ``ssd=tile`` against ``ssd=fused``,
  both measured against an f32 forward of the same weights.
* **four-chips** (``--four-chips`` only) — ``repro.ops`` reduce, scan,
  weighted_scan and ssd on arrays sharded over a ``data=4`` MeshContext, so
  they route through ``parallel/shard_ops.py``; ``dist_weighted_scan``
  under ``shard_map``. Each is compared with the same op on one device, its
  output must span all four devices and its compiled program must hold the
  cross-device collective it needs.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only when
every phase passed on a TPU. Without a TPU the script exits non-zero before
any phase. ``--cpu-rehearsal`` runs the same phases at toy sizes with the
kernels interpreted and never reports ``"ok": true``. Only committed files
are read (the checked-in autotune table; ``REPRO_*`` variables are
ignored) and all data and weights are made from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# relative error (max |out - ref| / max |ref|) allowed per output dtype
TOL = {"float32": 1e-4, "bfloat16": 2e-2}
# prefill logits through 48 bf16 layers: the bf16 noise of XLA's chunked
# SSD form (ssd=fused) against an f32 forward sets the scale. The kernel
# path may differ from it by twice that noise, and sit no further from f32
# than 1.25 times it.
PREFILL_VS_FUSED, PREFILL_VS_F32 = 2.0, 1.25


class Smoke:
    """Per-run state: sizes, policies, failures and compile accounting."""

    def __init__(self, args):
        import jax

        self.args = args
        self.rehearsal = args.cpu_rehearsal
        self.failures: list[str] = []
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def policy(self, **kw):
        from repro.core.policy import KernelPolicy

        # the rehearsal asks for the interpreter explicitly: "tile" then
        # runs the Pallas kernel bodies on the CPU and nothing downgrades
        backend = "cpu" if self.rehearsal else None
        return KernelPolicy(backend=backend, interpret_fallback="error", **kw)

    def check(self, name: str, ok: bool, detail: str) -> None:
        print(f"{name}: {'ok' if ok else 'FAIL'} {detail}", flush=True)
        if not ok:
            self.failures.append(name)

    def compiled(self, name: str, fn, *args):
        """AOT-compile ``fn``; the text must hold a Pallas kernel on TPU."""
        import jax

        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        if not self.rehearsal and "tpu_custom_call" not in exe.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 "compiled program (kernel did not run)")
        return exe, dt


def rel_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.all(np.isfinite(out)):
        return float("inf")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def reference(fn, *args):
    """A ``kernels/ref.py`` oracle at full f32 matmul precision."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fn)(*args))


# ---------------------------------------------------------------------------
# kernels


def kernel_cases(s: Smoke):
    """(name, op, repro.ops call, kernels/ref.py oracle, args) per case."""
    import jax
    import jax.numpy as jnp

    import repro.ops as rops
    from repro.kernels import ref

    key = jax.random.PRNGKey(s.args.seed)
    keys = iter(jax.random.split(key, 64))
    total = 1 << (14 if s.rehearsal else 26)     # reduce/scan elements
    cases = []
    for seg in (16, 256, 4096):
        x = jax.random.normal(next(keys), (total // seg, seg), jnp.float32)
        cases.append((f"reduce seg={seg}", "reduce", rops.reduce,
                      ref.segmented_reduce_ref, (x,)))
        cases.append((f"scan seg={seg}", "scan", rops.scan,
                      ref.segmented_scan_ref, (x,)))
    rows, n = (8, 256) if s.rehearsal else (1024, 2048)
    x = jax.random.normal(next(keys), (rows, n), jnp.float32)
    la = -jax.random.uniform(next(keys), (rows, n), jnp.float32, 0.0, 0.1)
    cases.append((f"weighted_scan {rows}x{n}", "weighted_scan",
                  rops.weighted_scan, ref.weighted_scan_ref, (x, la)))
    # Mamba-2-1.3B head geometry: H=64 heads of P=64, state N=128, G=1
    bsz, seqlen, h, p, nst = (1, 256, 2, 16, 16) if s.rehearsal \
        else (4, 4096, 64, 64, 128)
    ssd_args = (
        jax.random.normal(next(keys), (bsz, seqlen, h, p), jnp.bfloat16),
        jax.random.uniform(next(keys), (bsz, seqlen, h), jnp.float32,
                           1e-3, 1e-1),
        -jax.random.uniform(next(keys), (h,), jnp.float32, 1.0, 16.0),
        jax.random.normal(next(keys), (bsz, seqlen, 1, nst), jnp.bfloat16),
        jax.random.normal(next(keys), (bsz, seqlen, 1, nst), jnp.bfloat16))
    cases.append((f"ssd B={bsz} L={seqlen} H={h} P={p} N={nst}", "ssd",
                  rops.ssd, ref.ssd_scan_ref, ssd_args))
    for d in ((256,) if s.rehearsal else (2048, 4096)):
        rows = 16 if s.rehearsal else 8192
        x = jax.random.normal(next(keys), (rows, d), jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(next(keys), (d,), jnp.bfloat16)
        cases.append((f"rmsnorm {rows}x{d}", "rmsnorm", rops.rmsnorm,
                      ref.rmsnorm_ref, (x, w)))
        seq = 256 if s.rehearsal else 2048
        heads = d // 128
        qkv = tuple(jax.random.normal(next(keys), (1, seq, heads, 128),
                                      jnp.bfloat16) for _ in range(3))
        t = lambda a: jnp.swapaxes(a, 1, 2)   # model <-> kernel layout
        cases.append((f"attention d={d} L={seq}", "attention",
                      rops.attention,
                      lambda q, k, v: t(ref.flash_attention_ref(
                          t(q), t(k), t(v), causal=True)), qkv))
    return cases


def phase_kernels(s: Smoke) -> None:
    import jax

    linear = s.policy(path="tile")
    logdepth = s.policy(path="tile", op_paths={
        "scan": "tile_logdepth", "weighted_scan": "tile_logdepth",
        "ssd": "tile_logdepth"})
    c0 = s.compile_s
    for name, op, call, oracle, args in kernel_cases(s):
        want = reference(oracle, *args)
        families = [("tile", linear)]
        if op in ("scan", "weighted_scan", "ssd"):
            families.append(("tile_logdepth", logdepth))
        for fam, pol in families:
            case = f"kernels {name} [{fam}]"
            try:
                exe, ct = s.compiled(
                    case, lambda *a, c=call, p=pol: c(*a, policy=p), *args)
                t0 = time.perf_counter()
                got = jax.block_until_ready(exe(*args))
                rt = time.perf_counter() - t0
                dtype = str(got.dtype)
                err = rel_err(got, want)
                s.check(case, err <= TOL[dtype],
                        f"rel_err={err:.3e} tol={TOL[dtype]:.0e} "
                        f"dtype={dtype} compile_s={ct:.2f} run_s={rt:.4f}")
            except Exception as e:  # report every case, then fail
                traceback.print_exc()
                s.check(case, False, f"{type(e).__name__}: {e}"[:400])
    print(f"kernels: backend compile seconds {s.compile_s - c0:.2f}",
          flush=True)


# ---------------------------------------------------------------------------
# serve


def mamba_config(s: Smoke):
    from repro.configs import mamba2_1_3b

    cfg = mamba2_1_3b.FULL
    if s.rehearsal:   # same structure, toy widths and depth
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=256, vocab=1024)
    return cfg


def phase_serve(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import using_policy
    from repro.models import build
    from repro.models.common import init_params
    from repro.serving import Request, ServeConfig, ServingEngine

    c0 = s.compile_s
    cfg = mamba_config(s)
    pol = s.policy()
    bundle = build(dataclasses.replace(cfg, policy=pol))
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(
        jax.random.PRNGKey(s.args.seed), bundle.params_pspec, jnp.bfloat16))
    print(f"serve: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"state={cfg.ssm_state} params={bundle.n_params} "
          f"init_s={time.perf_counter() - t0:.2f}", flush=True)

    rng = np.random.default_rng(s.args.seed)
    lens = (8, 16, 24, 32) if s.rehearsal else (64, 192, 384, 512)
    prompts = [rng.integers(3, cfg.vocab, size=n, dtype=np.int32)
               for n in lens]
    max_new = 16
    # eos disabled: random weights give EOS no meaning, every request
    # must produce its full budget
    engine = ServingEngine(bundle, params, ServeConfig(
        slots=4, max_new=max_new, eos_token=-1, policy=pol,
        scheduler="continuous", cache_kind="ring", prefill_chunk=64,
        seed=s.args.seed))
    runs = []
    with using_policy(pol):
        for label in ("cold", "warm"):
            reqs = [Request(uid=i, prompt=p) for i, p in enumerate(prompts)]
            t0 = time.perf_counter()
            res = sorted(engine.run(reqs), key=lambda r: r.uid)
            dt = time.perf_counter() - t0
            n_tok = sum(len(r.tokens) for r in res)
            runs.append([list(map(int, r.tokens)) for r in res])
            full = len(res) == len(prompts) and all(
                len(r.tokens) == max_new
                and all(0 <= t < cfg.vocab for t in r.tokens) for r in res)
            s.check(f"serve {label}", full,
                    f"requests={len(res)} prompt_lens={list(lens)} "
                    f"tokens={n_tok} seconds={dt:.3f} "
                    f"tok_s={n_tok / dt:.1f}")
    s.check("serve warm repeats cold", runs[0] == runs[1],
            f"first tokens={[r[:4] for r in runs[0]]}")
    print(f"serve: backend compile seconds {s.compile_s - c0:.2f}",
          flush=True)

    # the SSD kernel at model width: one 2048-token prefill per SSD path,
    # and an f32 forward (same weights, XLA's SSD, f32 matmuls) to measure
    # both against
    c0 = s.compile_s
    seq = 128 if s.rehearsal else 2048
    batch = {"tokens": jnp.asarray(
        rng.integers(3, cfg.vocab, size=(1, seq), dtype=np.int32))}
    logits = {}
    for path in ("tile", "fused", "f32"):
        name = f"prefill ssd={path} L={seq}"
        try:
            run_cfg = dataclasses.replace(cfg, policy=s.policy(
                op_paths={"ssd": "fused" if path == "f32" else path}))
            run_params = params
            if path == "f32":
                run_cfg = dataclasses.replace(run_cfg, dtype=jnp.float32)
                run_params = jax.tree.map(
                    lambda a: a.astype(jnp.float32)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
            b = build(run_cfg)
            fn = lambda p, x, b=b: b.prefill(p, x)[0]
            with jax.default_matmul_precision(
                    "highest" if path == "f32" else None):
                if path == "tile":
                    exe, ct = s.compiled(name, fn, run_params, batch)
                else:
                    t0 = time.perf_counter()
                    exe = jax.jit(fn).lower(run_params, batch).compile()
                    ct = time.perf_counter() - t0
            t0 = time.perf_counter()
            logits[path] = np.asarray(jax.block_until_ready(
                exe(run_params, batch)), np.float32)
            del run_params
            finite = bool(np.all(np.isfinite(logits[path])))
            s.check(name, finite,
                    f"shape={logits[path].shape} compile_s={ct:.2f} "
                    f"run_s={time.perf_counter() - t0:.3f}")
        except Exception as e:
            traceback.print_exc()
            s.check(name, False, f"{type(e).__name__}: {e}"[:400])
    if len(logits) == 3:
        def rel_rms(a, b):
            return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b * b)))

        noise = rel_rms(logits["fused"], logits["f32"])
        vs_fused = rel_rms(logits["tile"], logits["fused"])
        vs_f32 = rel_rms(logits["tile"], logits["f32"])
        top = float(np.mean(np.argmax(logits["tile"], -1)
                            == np.argmax(logits["fused"], -1)))
        s.check("prefill ssd=tile vs ssd=fused",
                vs_fused <= PREFILL_VS_FUSED * noise
                and vs_f32 <= PREFILL_VS_F32 * noise,
                f"rel_rms={vs_fused:.3e} (tol {PREFILL_VS_FUSED} x "
                f"fused-vs-f32 {noise:.3e}); tile-vs-f32={vs_f32:.3e} "
                f"(tol {PREFILL_VS_F32} x); argmax_agree={top:.4f}")
    print(f"prefill: backend compile seconds {s.compile_s - c0:.2f}",
          flush=True)


# ---------------------------------------------------------------------------
# four chips


def phase_four_chips(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro.ops as rops
    from repro import obs
    from repro.kernels import ref
    from repro.parallel import shard_ops
    from repro.parallel.compat import shard_map
    from repro.parallel.mesh_context import make_context

    ndev = len(jax.devices())
    if ndev != 4:
        s.check("four-chips devices", False, f"found {ndev}, need 4")
        return
    ctx = make_context("data=4")
    pol = s.policy(path="tile")
    keys = iter(jax.random.split(jax.random.PRNGKey(s.args.seed), 16))
    rows, n = (8, 1024) if s.rehearsal else (1024, 65536)
    x = jax.random.normal(next(keys), (rows, n), jnp.float32)
    # the weighted-scan tile path pads every element to a 128-lane row of
    # the SSD kernel, so it gets a quarter of the rows and of the length
    wrows, wn = (8, 1024) if s.rehearsal else (256, 16384)
    wx = jax.random.normal(next(keys), (wrows, wn), jnp.float32)
    la = -jax.random.uniform(next(keys), (wrows, wn), jnp.float32, 0.0, 0.1)
    bsz, seqlen, h, p, nst = (1, 512, 2, 16, 16) if s.rehearsal \
        else (1, 16384, 64, 64, 128)
    ssd_args = (
        jax.random.normal(next(keys), (bsz, seqlen, h, p), jnp.bfloat16),
        jax.random.uniform(next(keys), (bsz, seqlen, h), jnp.float32,
                           1e-3, 1e-1),
        -jax.random.uniform(next(keys), (h,), jnp.float32, 1.0, 16.0),
        jax.random.normal(next(keys), (bsz, seqlen, 1, nst), jnp.bfloat16),
        jax.random.normal(next(keys), (bsz, seqlen, 1, nst), jnp.bfloat16))
    last = P(None, "data")
    seq = P(None, "data")          # (B, L, ...) sharded on L
    cases = [  # op, args, shardings (None: replicated), collective
        ("reduce", (x,), (last,), "all-reduce"),
        ("scan", (x,), (last,), "all-gather"),
        ("weighted_scan", (wx, la), (last, last), "all-gather"),
        ("ssd", ssd_args, (seq, seq, P(), seq, seq), "all-gather"),
    ]
    one = jax.devices()[0]
    c0 = s.compile_s
    for op, args, specs, collective in cases:
        name = f"four-chips {op} {tuple(args[0].shape)}"
        try:
            call = getattr(rops, op)
            want = jax.block_until_ready(call(
                *[jax.device_put(a, one) for a in args], policy=pol))
            sharded = [jax.device_put(a, NamedSharding(ctx.mesh, sp))
                       for a, sp in zip(args, specs)]
            with ctx, obs.using_obs() as sess:
                plan = shard_ops.plan(op, *sharded, policy=pol)
                if plan is None:
                    raise AssertionError("the call did not route through "
                                         "shard_ops")
                text = jax.jit(plan.fn).lower(*plan.args).compile().as_text()
                got = jax.block_until_ready(call(*sharded, policy=pol))
                routed = [e for e in sess.events.events("sharded_dispatch")
                          if e["op"] == op and e["nshards"] == 4]
            err = rel_err(got, want)
            tol = TOL[str(got.dtype)]
            spans = len(got.sharding.device_set)
            s.check(name, bool(routed) and spans == 4 and err <= tol
                    and collective in text
                    and (s.rehearsal or "tpu_custom_call" in text),
                    f"routed={bool(routed)} devices={spans} "
                    f"{collective}={collective in text} "
                    f"rel_err_vs_one_device={err:.3e} tol={tol:.0e}")
        except Exception as e:
            traceback.print_exc()
            s.check(name, False, f"{type(e).__name__}: {e}"[:400])

    name = "four-chips dist_weighted_scan under shard_map"
    try:
        fn = shard_map(lambda a, b: rops.dist_weighted_scan(a, b, "data"),
                       mesh=ctx.mesh, in_specs=(last, last), out_specs=last)
        xs = jax.device_put(wx, NamedSharding(ctx.mesh, last))
        las = jax.device_put(la, NamedSharding(ctx.mesh, last))
        exe = jax.jit(fn).lower(xs, las).compile()
        got = jax.block_until_ready(exe(xs, las))
        want = reference(ref.weighted_scan_ref, jax.device_put(wx, one),
                         jax.device_put(la, one))
        err = rel_err(got, want)
        spans = len(got.sharding.device_set)
        has = "all-gather" in exe.as_text()
        s.check(name, spans == 4 and has and err <= TOL["float32"],
                f"devices={spans} all-gather={has} "
                f"rel_err_vs_one_device={err:.3e} tol={TOL['float32']:.0e}")
    except Exception as e:
        traceback.print_exc()
        s.check(name, False, f"{type(e).__name__}: {e}"[:400])
    print(f"four-chips: backend compile seconds {s.compile_s - c0:.2f}",
          flush=True)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-dispatch phase on 4 chips")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the CPU with interpreted kernels; "
                         "never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke.py: no repro checkout next to this script "
              f"({HERE})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    for var in [v for v in os.environ if v.startswith("REPRO_")]:
        del os.environ[var]          # committed files only
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu_rehearsal and args.four_chips:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")

    import jax

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke.py: no TPU found (JAX sees {device}); this is a "
              "chip test and does not fall back to the CPU",
              file=sys.stderr)
        return 1
    print(f"device: {device} jax={jax.__version__} cache={cache_dir}",
          flush=True)

    s = Smoke(args)
    t0 = time.perf_counter()
    phases = [phase_four_chips] if args.four_chips \
        else [phase_kernels, phase_serve]
    for phase in phases:
        try:
            phase(s)
        except Exception as e:
            traceback.print_exc()
            s.check(phase.__name__, False, f"{type(e).__name__}: {e}"[:400])
    print(f"total: wall seconds {time.perf_counter() - t0:.1f}, backend "
          f"compile seconds {s.compile_s:.2f}, persistent-cache hits "
          f"{s.cache_hits}, failures {s.failures}", flush=True)
    ok = not s.failures and not args.cpu_rehearsal
    result = {"ok": ok, "device": device}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    if s.failures:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
