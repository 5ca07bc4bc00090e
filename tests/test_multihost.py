"""Multi-host scale-out tests.

Two layers:

* **Unit** — shard-shape kernel resolution under a topology-only
  :class:`~repro.parallel.mesh_context.MeshContext` (no devices needed)
  and entry-granularity merging of per-host autotune tables.
* **Integration** — a real 2-process ``jax.distributed`` group over the
  gloo CPU collectives backend (each process in a subprocess, same idiom
  as ``test_distributed``): train step on a ``data=2`` mesh -> async
  distributed checkpoint -> elastic restore on a ``model=2`` mesh ->
  lockstep continuous serving -> per-host autotune measure + ``--merge``.
  Skips (rather than fails) when the container's gloo transport cannot
  bind — the unit layer still covers the logic.
"""
import json
import os
import shutil
import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune
from repro.core.policy import KernelPolicy
from repro.parallel.mesh_context import MeshContext, parse_mesh_arg
from repro.parallel.sharding import Rules

ROOT = __file__.rsplit("/tests/", 1)[0]


# ---------------------------------------------------------------------------
# unit: shard-shape policy resolution


def _topology_ctx(op_shard_axes, sizes):
    """Topology-only context: policy resolution without devices."""
    return MeshContext(mesh=None,
                       rules=Rules(table={}, axis_sizes=dict(sizes)),
                       op_shard_axes=op_shard_axes)


def _two_band_table(tmp_path):
    """reduce/f32 table where the full shape's band and the shard's band
    resolve to *different* paths and TuneSpecs — so the test can tell
    which shape resolution keyed off."""
    table = {"version": 3, "backends": {"cpu": {"jax": jax.__version__,
             "entries": {
                 "reduce/f32/10": {"path": "fused", "us": {"fused": 1.0},
                                   "tuning": {"block_s": 16}},
                 "reduce/f32/8": {"path": "baseline",
                                  "us": {"baseline": 1.0},
                                  "tuning": {"block_s": 8}},
             }}}}
    path = tmp_path / "two_band.json"
    path.write_text(json.dumps(table))
    return str(path)


def test_resolve_buckets_by_shard_shape(tmp_path):
    """Under an active MeshContext, resolve(op, n) keys off the *shard*
    shape: the resolved path AND TuneSpec are the shard's (the ISSUE's
    acceptance unit test)."""
    pol = KernelPolicy(path="auto", autotune="on",
                       autotune_table=_two_band_table(tmp_path))
    autotune.invalidate_cache()
    try:
        full = pol.resolve("reduce", 1024, jnp.float32)
        shard_direct = pol.resolve("reduce", 256, jnp.float32)
        assert str(full) == "fused" and str(shard_direct) == "baseline"
        assert full.tuning != shard_direct.tuning

        ctx = _topology_ctx({"reduce": "model"}, {"model": 4})
        with ctx:
            in_ctx = pol.resolve("reduce", 1024, jnp.float32)
        assert str(in_ctx) == str(shard_direct) == "baseline"
        assert in_ctx.tuning == shard_direct.tuning

        # ops with no registered shard axis are untouched
        with ctx:
            assert str(pol.resolve("scan", 1024, jnp.float32)) == \
                str(pol.resolve("scan", 1024, jnp.float32))
            assert ctx.effective_n("scan", 1024) == 1024
    finally:
        autotune.invalidate_cache()


def test_resolve_shard_divisor_degrades_on_non_divisible(tmp_path):
    """A shard axis that does not divide n replicates (divisor 1), the
    same degradation rule as ``spec_for`` — resolution sees the full n."""
    ctx = _topology_ctx({"reduce": "model"}, {"model": 4})
    assert ctx.effective_n("reduce", 1024) == 256
    assert ctx.effective_n("reduce", 1023) == 1023


def test_shard_local_scope_suppresses_division(tmp_path):
    """Inside a shard_map body shapes are already per-shard; the divisor
    must not apply twice."""
    from repro.parallel.mesh_context import (effective_call_n,
                                             shard_local_scope)

    ctx = _topology_ctx({"reduce": "model"}, {"model": 4})
    with ctx:
        assert effective_call_n("reduce", 1024) == 256
        with shard_local_scope():
            assert effective_call_n("reduce", 1024) == 1024
        assert effective_call_n("reduce", 1024) == 256
    assert effective_call_n("reduce", 1024) == 1024


# ---------------------------------------------------------------------------
# unit: per-host autotune table merge


def _host_table(path, entries):
    table = {"version": 3,
             "backends": {"cpu": {"jax": jax.__version__,
                                  "entries": entries}}}
    path.write_text(json.dumps(table))
    return str(path)


def test_merge_host_tables_union_and_conflict(tmp_path):
    h0 = _host_table(tmp_path / "host_0.json", {
        "reduce/f32/8": {"path": "fused", "us": {"fused": 3.0}},
        "reduce/f32/9": {"path": "baseline", "us": {"baseline": 2.0}},
    })
    h1 = _host_table(tmp_path / "host_1.json", {
        "reduce/f32/9": {"path": "fused", "us": {"fused": 1.5}},
        "scan/f32/8": {"path": "fused", "us": {"fused": 4.0}},
    })
    merged = autotune.merge_host_tables([h0, h1])
    ents = merged["backends"]["cpu"]["entries"]
    # union of both hosts' buckets
    assert set(ents) == {"reduce/f32/8", "reduce/f32/9", "scan/f32/8"}
    # conflict resolved by timing: host_1's 1.5us beats host_0's 2.0us
    assert ents["reduce/f32/9"]["path"] == "fused"
    assert ents["reduce/f32/9"]["src"] == "host_1.json"
    # provenance on every merged entry
    assert ents["reduce/f32/8"]["src"] == "host_0.json"
    assert ents["scan/f32/8"]["src"] == "host_1.json"
    # round-trips through the validator (src is preserved, not rejected)
    out = tmp_path / "merged.json"
    autotune.save_table(merged, out)
    loaded = autotune.load_table(out)
    assert loaded["backends"]["cpu"]["entries"]["reduce/f32/9"]["src"] == \
        "host_1.json"


def test_merge_host_tables_timed_beats_untimed(tmp_path):
    """An entry with a measured winning time beats one without (inf)."""
    h0 = _host_table(tmp_path / "a.json",
                     {"reduce/f32/8": {"path": "baseline", "us": {}}})
    h1 = _host_table(tmp_path / "b.json",
                     {"reduce/f32/8": {"path": "fused",
                                       "us": {"fused": 9.0}}})
    merged = autotune.merge_host_tables([h0, h1])
    ent = merged["backends"]["cpu"]["entries"]["reduce/f32/8"]
    assert ent["path"] == "fused" and ent["src"] == "b.json"


def test_merge_host_tables_empty_raises():
    with pytest.raises(ValueError):
        autotune.merge_host_tables([])


def test_autotune_cli_merge(tmp_path):
    h0 = _host_table(tmp_path / "host_0.json", {
        "reduce/f32/8": {"path": "fused", "us": {"fused": 3.0}}})
    h1 = _host_table(tmp_path / "host_1.json", {
        "reduce/f32/9": {"path": "baseline", "us": {"baseline": 2.0}}})
    out = tmp_path / "merged.json"
    rc = autotune.main(["--merge", h0, h1, "--out", str(out)])
    assert rc == 0
    ents = autotune.load_table(out)["backends"]["cpu"]["entries"]
    assert set(ents) == {"reduce/f32/8", "reduce/f32/9"}


def test_parse_mesh_arg():
    assert parse_mesh_arg("data=2,model=2") == (("data", 2), ("model", 2))
    assert parse_mesh_arg(" data=4 ") == (("data", 4),)
    with pytest.raises(ValueError):
        parse_mesh_arg("data")
    with pytest.raises(ValueError):
        parse_mesh_arg("data=0")


# ---------------------------------------------------------------------------
# integration: 2-process CPU distributed group


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_PRELUDE = """
import os, sys
import jax
# jax 0.4.x: the gloo CPU collectives backend must be selected via
# jax.config BEFORE distributed.initialize (the env var is not honored)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
try:
    jax.distributed.initialize(coordinator_address="localhost:%(port)d",
                               num_processes=2, process_id=%(pid)d)
except Exception as e:  # no gloo transport in this container -> skip
    print("GLOO_INIT_FAILED", type(e).__name__, e)
    sys.exit(0)
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
"""


def _run_pair(body: str, tmp_path) -> list[str]:
    """Run ``body`` as both processes of a 2-process jax.distributed group;
    returns [stdout_proc0, stdout_proc1] or skips if gloo can't start."""
    port = _free_port()
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    procs = []
    for pid in (0, 1):
        code = _PRELUDE % {"port": port, "pid": pid} + textwrap.dedent(body)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env, cwd=ROOT))
    outs = []
    for pid, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"process {pid} timed out")
        if "GLOO_INIT_FAILED" in out:
            for q in procs:
                q.kill()
            pytest.skip("gloo CPU collectives unavailable: "
                        + out.split("GLOO_INIT_FAILED", 1)[1][:200])
        assert p.returncode == 0, \
            f"process {pid} failed:\n{err[-4000:]}\n{out[-2000:]}"
        outs.append(out)
    return outs


def test_two_process_collective_smoke(tmp_path):
    """The cheapest possible check that the 2-process group works: a psum
    across hosts."""
    outs = _run_pair("""
        assert jax.process_count() == 2 and jax.device_count() == 2
        from repro.parallel.compat import make_mesh, shard_map
        mesh = make_mesh((2,), ("data",))
        x = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")),
            np.full((1,), 1.0 + jax.process_index(), np.float32))
        total = shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P(None),
                          check_vma=False)(x)
        print("PSUM", float(total.addressable_data(0)[0]))
    """, tmp_path)
    for out in outs:
        assert "PSUM 3.0" in out


def test_two_process_train_ckpt_restore_serve_autotune(tmp_path):
    """The ISSUE's e2e: train step on data=2 -> async distributed
    checkpoint (host_<i>.npz + manifest commit) -> elastic restore on a
    model=2 mesh -> lockstep continuous serving (identical results on
    both hosts) -> per-host autotune measure + entry-granularity merge.
    A kernel policy with an interpret-path op override is active
    throughout, and shard-shape resolution is asserted in-process."""
    ckdir = tmp_path / "ckpt"
    tabdir = tmp_path / "tables"
    os.makedirs(ckdir)
    os.makedirs(tabdir)
    outs = _run_pair(f"""
        from jax.experimental import multihost_utils

        from repro import configs
        from repro.checkpoint import ckpt
        from repro.configs.common import SMOKE_SEQ
        from repro.core import autotune
        from repro.core.policy import KernelPolicy
        from repro.data.pipeline import DataConfig, SyntheticLMPipeline
        from repro.models import build
        from repro.models.common import init_params, partition_specs
        from repro.optim import OptConfig
        from repro.parallel.mesh_context import make_context
        from repro.serving import Request, ServeConfig, ServingEngine
        from repro.training import (TrainConfig, init_train_state,
                                    make_train_step, train_state_pspecs)

        pid = jax.process_index()
        assert jax.process_count() == 2 and jax.device_count() == 2

        pol = KernelPolicy(op_paths={{"reduce": "interpret"}},
                           interpret_fallback="silent")
        mod = configs.get("llama3.2-1b")
        bundle = build(mod.SMOKE)
        opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)
        train_cfg = TrainConfig()

        # --- train one step on a data=2 mesh -------------------------------
        ctx = make_context("data=2", op_shard_axes={{"reduce": "data"}})
        with ctx:
            # shard-shape resolution: the resolved path+TuneSpec under the
            # active context are the SHARD's, not the full shape's
            rp_in = pol.resolve("reduce", 512, jnp.float32)
        rp_shard = pol.resolve("reduce", 256, jnp.float32)
        assert str(rp_in) == str(rp_shard) and \
            rp_in.tuning == rp_shard.tuning, (rp_in, rp_shard)

        with ctx:
            specs = train_state_pspecs(bundle, ctx.rules, train_cfg)
            shardings = jax.tree.map(ctx.named_sharding, specs)
            state = jax.jit(
                lambda: init_train_state(jax.random.PRNGKey(0), bundle,
                                         opt_cfg, train_cfg),
                out_shardings=shardings)()
            data = SyntheticLMPipeline(
                DataConfig(vocab=mod.SMOKE.vocab, seq_len=SMOKE_SEQ,
                           global_batch=4),
                sharding=ctx.named_sharding(P("data")))
            lo, hi = data.host_range()
            assert hi - lo == 2     # even split of the global batch
            step_fn = jax.jit(make_train_step(bundle, opt_cfg, train_cfg,
                                              mesh_ctx=ctx))
            state, metrics = step_fn(state, data.device_batch(0))
            loss = float(np.asarray(
                metrics["loss"].addressable_data(0)))
            assert np.isfinite(loss)
            print("LOSS", round(loss, 6))

            # --- async distributed checkpoint ------------------------------
            writer = ckpt.AsyncCheckpointer("{ckdir}")
            writer.save(1, state)
            path = writer.wait()
            assert path.endswith("step_1")
        multihost_utils.sync_global_devices("ckpt_committed")
        import os as _os
        files = sorted(_os.listdir("{ckdir}/step_1"))
        assert files == ["host_0.npz", "host_1.npz", "manifest.json"], files
        import json as _json
        with open("{ckdir}/step_1/manifest.json") as f:
            man = _json.load(f)
        assert man["n_hosts"] == 2 and man["step"] == 1
        print("CKPT_OK")

        # --- elastic restore on a DIFFERENT mesh shape + serve -------------
        ctx2 = make_context("model=2")
        with ctx2:
            pspecs = partition_specs(bundle.params_pspec, rules=ctx2.rules,
                                     fsdp_ok=False)
            shardings2 = jax.tree.map(ctx2.named_sharding, pspecs)
            template = jax.jit(
                lambda: init_params(jax.random.PRNGKey(1),
                                    bundle.params_pspec, mod.SMOKE.dtype),
                out_shardings=shardings2)()
            restored = ckpt.restore("{ckdir}", 1, {{"params": template}},
                                    shardings={{"params": shardings2}})
        params = restored["params"]

        eng = ServingEngine(bundle, params, ServeConfig(
            slots=2, max_new=4, eos_token=-1, scheduler="continuous",
            prefill_chunk=4, policy=pol), mesh_ctx=ctx2)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, prompt=rng.integers(
                    3, mod.SMOKE.vocab, size=6, dtype=np.int32))
                for i in range(3)]
        results = eng.run(reqs)
        toks = {{r.uid: list(map(int, r.tokens)) for r in results}}
        assert all(len(t) == 4 for t in toks.values())
        print("TOKENS", _json.dumps(toks, sort_keys=True))

        # --- per-host autotune measure + merge -----------------------------
        table = autotune.measure_table(ops=("reduce",), bands=(8,),
                                       dtypes=(jnp.float32,), iters=1,
                                       sweep=False)
        autotune.save_table(table, "{tabdir}/host_%d.json" % pid)
        multihost_utils.sync_global_devices("tables_written")
        if pid == 0:
            merged = autotune.merge_host_tables(
                ["{tabdir}/host_0.json", "{tabdir}/host_1.json"])
            autotune.save_table(merged, "{tabdir}/merged.json")
            autotune.load_table("{tabdir}/merged.json")   # validates
            print("MERGED_OK")
        print("E2E_OK")
    """, tmp_path)
    for out in outs:
        assert "CKPT_OK" in out and "E2E_OK" in out, out
    assert "MERGED_OK" in outs[0]

    # lockstep invariant: both hosts computed the same loss and emitted
    # identical token streams
    def line(out, tag):
        return [ln for ln in out.splitlines() if ln.startswith(tag)][0]

    assert line(outs[0], "LOSS") == line(outs[1], "LOSS")
    assert line(outs[0], "TOKENS") == line(outs[1], "TOKENS")

    merged = autotune.load_table(tabdir / "merged.json")
    ents = merged["backends"]["cpu"]["entries"]
    assert "reduce/f32/8" in ents and "src" in ents["reduce/f32/8"]

    # CI artifact hook: export the merged per-host table when asked
    dest = os.environ.get("REPRO_MERGE_OUT")
    if dest:
        shutil.copy(tabdir / "merged.json", dest)
