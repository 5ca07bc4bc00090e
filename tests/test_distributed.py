"""Grid-level (device) reduce/scan + pipeline tests.

These need >1 device, so they run in a subprocess with
``xla_force_host_platform_device_count`` set before jax initialises —
the main pytest process keeps the brief-mandated single device.
"""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(ndev: int, body: str) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={ndev}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compat import make_mesh, shard_map
    """) + textwrap.dedent(body)
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    if "JAX_PLATFORMS" in os.environ:  # keep the parent's backend pin —
        # without it a TPU-enabled jaxlib probes for hardware and hangs
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env,
                          cwd=__file__.rsplit("/tests/", 1)[0])
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_dist_reduce_correct():
    out = _run(4, """
        from repro.core import dist_reduce
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 512))

        def f(xl):
            return dist_reduce(xl, "data")

        r = shard_map(f, mesh=mesh, in_specs=P("data"),
                      out_specs=P())(x)
        np.testing.assert_allclose(float(r), float(jnp.sum(x)), rtol=1e-4)
        print("REDUCE_OK")
    """)
    assert "REDUCE_OK" in out


def test_dist_scan_correct():
    out = _run(4, """
        from repro.core import dist_scan
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 2048))

        def g(xl):
            return dist_scan(xl, "data")

        s = shard_map(g, mesh=mesh, in_specs=P(None, "data"),
                      out_specs=P(None, "data"))(x)
        np.testing.assert_allclose(
            np.asarray(s), np.cumsum(np.asarray(x), -1),
            rtol=1e-3, atol=1e-2)
        print("SCAN_OK")
    """)
    assert "SCAN_OK" in out


def test_dist_weighted_scan_correct():
    out = _run(4, """
        from repro.core import dist_weighted_scan
        mesh = make_mesh((4,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 1024))
        la = -jax.random.uniform(jax.random.PRNGKey(3), (2, 1024))

        def g(xl, ll):
            return dist_weighted_scan(xl, ll, "data")

        s = shard_map(g, mesh=mesh,
                      in_specs=(P(None, "data"), P(None, "data")),
                      out_specs=P(None, "data"))(x, la)
        xa, laa = np.asarray(x), np.asarray(la)
        ref = np.zeros_like(xa)
        for r in range(2):
            y = 0.0
            for i in range(1024):
                y = np.exp(laa[r, i]) * y + xa[r, i]
                ref[r, i] = y
        np.testing.assert_allclose(np.asarray(s), ref, rtol=1e-3, atol=1e-3)
        print("WSCAN_OK")
    """)
    assert "WSCAN_OK" in out


def test_pipeline_parallel_matches_sequential():
    out = _run(4, """
        from repro.parallel.pipeline import (PipelineConfig, pipeline_apply,
                                             pipeline_stats)
        mesh = make_mesh((4,), ("stage",))
        S, M, mb, d = 4, 8, 2, 16
        w = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.1

        def block(wl, x):
            return x + jnp.tanh(x @ wl)

        x = jax.random.normal(jax.random.PRNGKey(1), (M * mb, d))
        cfg = PipelineConfig(n_stages=S, n_microbatches=M)
        y = pipeline_apply(block, w, x, cfg, mesh)
        ref = x
        for si in range(S):
            ref = block(w[si], ref)
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)
        st = pipeline_stats(cfg)
        assert st["ticks"] == 11 and abs(st["bubble_fraction"] - 3/11) < 1e-9
        print("PIPE_OK")
    """)
    assert "PIPE_OK" in out


def test_training_shards_run_on_mesh():
    """End-to-end: 2x2 mesh, TP+DP smoke training step with sharded state."""
    out = _run(4, """
        from repro import configs
        from repro.configs.common import smoke_batch
        from repro.models import build
        from repro.optim import OptConfig
        from repro.parallel.sharding import Rules, use_rules
        from repro.training import (TrainConfig, init_train_state,
                                    make_train_step)
        from repro.training.train_lib import train_state_pspecs

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = Rules(table={"batch": ("data",), "heads": "model",
                             "kv_heads": "model", "ff": "model",
                             "vocab": "model", "embed": None,
                             "layers": None},
                      fsdp="data", axis_sizes={"data": 2, "model": 2})
        mod = configs.get("llama3.2-1b")
        bundle = build(mod.SMOKE)
        opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)
        with use_rules(rules), mesh:
            state = init_train_state(jax.random.PRNGKey(0), bundle, opt_cfg)
            step = jax.jit(make_train_step(bundle, opt_cfg))
            batch = smoke_batch(mod.SMOKE)
            l0 = None
            for _ in range(3):
                state, m = step(state, batch)
                l0 = l0 or float(m["loss"])
            assert float(m["loss"]) < l0
        print("MESH_TRAIN_OK", l0, float(m["loss"]))
    """)
    assert "MESH_TRAIN_OK" in out


def test_shard_ops_route_and_match_unsharded():
    """ops.reduce/scan/weighted_scan on committed sharded arrays under an
    active MeshContext run the shard_map path and match the unsharded
    references (the tentpole's numerics contract)."""
    out = _run(4, """
        from jax.sharding import NamedSharding
        from repro import ops
        from repro.parallel import shard_ops
        from repro.parallel.mesh_context import make_context

        ctx = make_context("data=4")
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 4096))
        la = -jax.random.uniform(jax.random.PRNGKey(1), (3, 4096))
        want_r = np.asarray(ops.reduce(x))
        want_s = np.asarray(ops.scan(x))
        want_w = np.asarray(ops.weighted_scan(x, la))

        shd = NamedSharding(ctx.mesh, P(None, "data"))
        xs, las = jax.device_put(x, shd), jax.device_put(la, shd)
        with ctx:
            assert shard_ops._routing_ctx(xs, 1) is not None
            got_r = np.asarray(ops.reduce(xs))
            got_s = np.asarray(ops.scan(xs))
            got_w = np.asarray(ops.weighted_scan(xs, las))
        np.testing.assert_allclose(got_r, want_r, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got_s, want_s, rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(got_w, want_w, rtol=1e-3, atol=1e-3)

        # non-divisible bucket axis: conservative fallback, still correct
        x_odd = jax.random.normal(jax.random.PRNGKey(2), (2, 1023))
        with ctx:
            assert shard_ops._routing_ctx(x_odd, 1) is None
            np.testing.assert_allclose(np.asarray(ops.reduce(x_odd)),
                                       np.asarray(jnp.sum(
                                           x_odd.astype(jnp.float32), -1)),
                                       rtol=1e-4, atol=1e-4)
        print("SHARD_OPS_OK")
    """)
    assert "SHARD_OPS_OK" in out


def test_shard_ops_spans_and_compile_counter(tmp_path):
    """A routed eager scan: its ``ops/scan`` span holds a
    ``shard_ops/scan`` span with the same ``call``, the
    ``sharded_dispatch`` event carries that call, and the compile counter
    puts the lowerings of the per-call shard_map program under
    ``op="scan"``."""
    out = _run(4, f"""
        import glob
        from jax.profiler import ProfileData
        from jax.sharding import NamedSharding
        from repro import obs, ops
        from repro.obs import profiling
        from repro.parallel.mesh_context import make_context

        ctx = make_context("data=4")
        xs = jax.device_put(jax.random.normal(jax.random.PRNGKey(0),
                                              (8, 1024)),
                            NamedSharding(ctx.mesh, P(None, "data")))
        with ctx, obs.using_obs() as sess, \\
                profiling.tracing({str(tmp_path)!r}):
            jax.block_until_ready(ops.scan(xs, policy="fused"))
        (pb,) = glob.glob({str(tmp_path)!r} + "/**/*.xplane.pb",
                          recursive=True)
        spans = {{}}
        for plane in ProfileData.from_file(pb).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("ops/scan", "shard_ops/scan"):
                        spans[e.name] = (e.start_ns, e.end_ns,
                                         dict(e.stats))
        outer, inner = spans["ops/scan"], spans["shard_ops/scan"]
        assert outer[2]["routed"] == 1 and outer[2]["n"] == 1024
        assert inner[2]["call"] == outer[2]["call"]
        assert outer[0] <= inner[0] <= inner[1] <= outer[1]
        (route,) = sess.events.events("sharded_dispatch")
        assert route["call"] == outer[2]["call"]
        m = sess.metrics
        assert m.get("repro_compile_events_total").value(
            op="scan", stage="lower") > 0
        assert m.get("repro_compile_seconds_total").value(
            op="scan", stage="compile") > 0
        assert m.get("repro_ops_calls_total").value(
            op="scan", routed="true") == 1
        print("SHARD_SPANS_OK")
    """)
    assert "SHARD_SPANS_OK" in out


def test_shard_ops_ssd_matches_unsharded():
    """Sequence-sharded SSD (shard finals carried by the 1-semiseparable
    combine) against the unsharded op, y and final state both."""
    out = _run(4, """
        from jax.sharding import NamedSharding
        from repro import ops
        from repro.parallel.mesh_context import make_context

        ctx = make_context("data=4")
        bsz, L, h, p, g, n = 1, 128, 2, 8, 1, 4
        ks = jax.random.split(jax.random.PRNGKey(8), 5)
        x = 0.2 * jax.random.normal(ks[0], (bsz, L, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, L, h)))
        a = -jnp.exp(0.2 * jax.random.normal(ks[2], (h,)))
        bb = jax.random.normal(ks[3], (bsz, L, g, n)) / np.sqrt(n)
        cc = jax.random.normal(ks[4], (bsz, L, g, n)) / np.sqrt(n)
        want_y, want_h = ops.ssd(x, dt, a, bb, cc, return_state=True)

        seq = lambda nd: NamedSharding(
            ctx.mesh, P(*((None, "data") + (None,) * (nd - 2))))
        xs = jax.device_put(x, seq(4))
        dts = jax.device_put(dt, seq(3))
        bbs = jax.device_put(bb, seq(4))
        ccs = jax.device_put(cc, seq(4))
        with ctx:
            got_y, got_h = ops.ssd(xs, dts, a, bbs, ccs, return_state=True)
            got_y2 = ops.ssd(xs, dts, a, bbs, ccs)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(got_y2), np.asarray(got_y),
                                   rtol=1e-5, atol=1e-5)
        print("SHARD_SSD_OK")
    """)
    assert "SHARD_SSD_OK" in out


@pytest.mark.parametrize("op", ["reduce", "scan", "weighted_scan", "ssd"])
def test_shard_ops_builds_each_routed_program_once(op):
    """Two routed calls of one op on two inputs of the same shape: both
    match the unsharded op, the second lowers nothing, and the program
    cache reads one miss and one hit."""
    out = _run(4, f"""
        from jax.sharding import NamedSharding
        from repro import obs, ops
        from repro.parallel.mesh_context import make_context

        ctx = make_context("data=4")
        seq = lambda nd: NamedSharding(
            ctx.mesh, P(*((None, "data") + (None,) * (nd - 2))))

        def inputs(seed):
            ks = jax.random.split(jax.random.PRNGKey(seed), 5)
            if {op!r} == "ssd":
                bsz, L, h, p, g, n = 1, 128, 2, 8, 1, 4
                return (0.2 * jax.random.normal(ks[0], (bsz, L, h, p)),
                        jax.nn.softplus(jax.random.normal(ks[1],
                                                          (bsz, L, h))),
                        -jnp.exp(0.2 * jax.random.normal(ks[2], (h,))),
                        jax.random.normal(ks[3], (bsz, L, g, n)) / 2,
                        jax.random.normal(ks[4], (bsz, L, g, n)) / 2)
            x = jax.random.normal(ks[0], (3, 1024))
            if {op!r} == "weighted_scan":
                return x, -jax.random.uniform(ks[1], (3, 1024))
            return (x,)

        def shard(args):
            return [a if a.ndim == 1 else jax.device_put(a, seq(a.ndim))
                    for a in args]

        call = getattr(ops, {op!r})
        tol = dict(reduce=1e-4, scan=1e-2, weighted_scan=1e-3, ssd=2e-3)
        lowered = []
        with obs.using_obs() as sess:
            for seed in (1, 2):
                args = inputs(seed)
                want = np.asarray(call(*args))
                with ctx:
                    got = np.asarray(call(*shard(args)))
                np.testing.assert_allclose(got, want, rtol=1e-3,
                                           atol=tol[{op!r}])
                lowered.append(sess.metrics.get(
                    "repro_compile_events_total").value(
                        op={op!r}, stage="lower"))
        assert lowered[0] > 0 and lowered[1] == lowered[0], lowered
        cache = sess.metrics.get("repro_sharded_program_cache_total")
        assert cache.value(op={op!r}, outcome="miss") == 1
        assert cache.value(op={op!r}, outcome="hit") == 1
        print("BUILT_ONCE_OK")
    """)
    assert "BUILT_ONCE_OK" in out


def test_shard_ops_program_cache_is_never_stale():
    """The cache keys on the resolved policy and the shape: a routed scan
    under ``using_policy("fused")``, then under the default policy, then
    at another shape, misses each time, traces under its own policy and
    is correct."""
    out = _run(4, """
        from jax.sharding import NamedSharding
        from repro import obs, ops
        from repro.parallel.mesh_context import make_context

        ctx = make_context("data=4")
        shd = NamedSharding(ctx.mesh, P(None, "data"))
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 1024))
        x2 = jax.random.normal(jax.random.PRNGKey(1), (5, 2048))
        with obs.using_obs() as sess, ctx:
            with ops.using_policy("fused"):
                got = [ops.scan(jax.device_put(x, shd))]
            got.append(ops.scan(jax.device_put(x, shd)))
            got.append(ops.scan(jax.device_put(x2, shd)))
        for g, v in zip(got, (x, x, x2)):
            np.testing.assert_allclose(np.asarray(g),
                                       np.cumsum(np.asarray(v), -1),
                                       rtol=1e-3, atol=1e-2)
        cache = sess.metrics.get("repro_sharded_program_cache_total")
        assert cache.value(op="scan", outcome="miss") == 3
        assert cache.value(op="scan", outcome="hit") == 0
        routes = sess.events.events("sharded_dispatch")
        resolved = {e["call"]: e["chosen_path"]
                    for e in sess.events.events("resolution")
                    if e["op"] == "scan"}
        assert [r["call"] in resolved for r in routes] == [True] * 3
        assert resolved[routes[0]["call"]] == "fused"
        print("NEVER_STALE_OK")
    """)
    assert "NEVER_STALE_OK" in out


def test_elastic_restart_across_mesh_sizes(tmp_path):
    """Fault-tolerance contract: checkpoint under a 4-device mesh, restore
    and continue under a 2-device mesh — values identical (elastic)."""
    out = _run(4, f"""
        from repro import configs
        from repro.checkpoint import ckpt
        from repro.configs.common import smoke_batch
        from repro.models import build
        from repro.optim import OptConfig
        from repro.parallel.sharding import Rules, use_rules
        from repro.training import init_train_state, make_train_step

        mod = configs.get("llama3.2-1b")
        bundle = build(mod.SMOKE)
        opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)
        mesh = make_mesh((4,), ("data",))
        rules = Rules(table={{"batch": ("data",)}}, fsdp="data",
                      axis_sizes={{"data": 4}})
        with use_rules(rules), mesh:
            state = init_train_state(jax.random.PRNGKey(0), bundle, opt_cfg)
            step = jax.jit(make_train_step(bundle, opt_cfg))
            state, m = step(state, smoke_batch(mod.SMOKE))
            ckpt.save("{tmp_path}", 1, state)
        print("SAVED", float(m["loss"]))
    """)
    assert "SAVED" in out
    out2 = _run(2, f"""
        from repro import configs
        from repro.checkpoint import ckpt
        from repro.configs.common import smoke_batch
        from repro.models import build
        from repro.optim import OptConfig
        from repro.parallel.sharding import Rules, use_rules
        from repro.training import init_train_state, make_train_step

        mod = configs.get("llama3.2-1b")
        bundle = build(mod.SMOKE)
        opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)
        mesh = make_mesh((2,), ("data",))
        rules = Rules(table={{"batch": ("data",)}}, fsdp="data",
                      axis_sizes={{"data": 2}})
        with use_rules(rules), mesh:
            template = init_train_state(jax.random.PRNGKey(0), bundle,
                                        opt_cfg)
            state = ckpt.restore("{tmp_path}", 1, template)
            step = jax.jit(make_train_step(bundle, opt_cfg))
            state, m = step(state, smoke_batch(mod.SMOKE))
            assert int(state["opt"]["step"]) == 2     # resumed, not reset
        print("RESTORED_OK", float(m["loss"]))
    """)
    assert "RESTORED_OK" in out2
