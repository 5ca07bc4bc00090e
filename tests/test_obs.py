"""Observability subsystem tests: off-by-default guarantees, metrics
math, the resolution-event audit trail (including shard contexts),
serving tick-phase timings, checkpoint barrier durations, and the
autotune --check diff rendering."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import autotune, dispatch
from repro.core.policy import KernelPolicy
from repro.obs import runtime as obs_runtime
from repro.obs.events import RESOLUTION_FIELDS, EventSink
from repro.obs.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# off by default


def _compile_listener_registered() -> bool:
    from jax._src import monitoring   # the public module cannot list them

    return obs_runtime._on_compile_event in \
        monitoring.get_event_duration_listeners()


def test_disabled_by_default(tmp_path):
    import repro.ops as rops

    assert obs.active() is None
    assert not _compile_listener_registered()
    pol = KernelPolicy(interpret_fallback="silent")
    pol.resolve(op="reduce", n=1024, dtype=jnp.float32)  # must not record
    with obs.using_obs() as sess:
        assert sess.events.emitted == 0          # nothing retroactive
        assert _compile_listener_registered()    # one, while active
    assert obs.active() is None                  # scope restored
    assert not _compile_listener_registered()
    obs.enable()
    assert _compile_listener_registered()
    obs.disable()
    assert not _compile_listener_registered()
    # a profiler trace alone, with the repo's spans off: repro.ops records
    # no span and numbers no call
    ids = repr(obs_runtime._CALL_IDS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(rops.scan(jnp.ones((2, 64)), policy="fused"))
    finally:
        jax.profiler.stop_trace()
    assert repr(obs_runtime._CALL_IDS) == ids
    assert obs_runtime.current_call() is None
    assert not [name for name, *_ in _host_events(tmp_path)
                if name.startswith(("ops/", "shard_ops/"))]


def test_resolve_emits_only_inside_scope():
    pol = KernelPolicy(interpret_fallback="silent")
    with obs.using_obs() as sess:
        pol.resolve(op="reduce", n=1024, dtype=jnp.float32)
        n_inside = sess.events.emitted
    pol.resolve(op="reduce", n=1024, dtype=jnp.float32)  # after exit
    assert n_inside == 1
    assert sess.events.emitted == n_inside


def test_using_obs_restores_previous_session():
    with obs.using_obs() as outer:
        with obs.using_obs() as inner:
            assert obs.active() is inner
        assert obs.active() is outer
    assert obs.active() is None


# ---------------------------------------------------------------------------
# metrics math


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c", "help")
    c.inc()
    c.inc(2, op="reduce")
    assert c.value() == 1
    assert c.value(op="reduce") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(3.5)
    g.set(7, slot="1")
    assert g.value() == 3.5
    assert g.value(slot="1") == 7.0


def test_metric_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("m")


def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("h", "lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 1.0, 2.0):      # 1.0 lands in le=1.0 (<= edge)
        h.observe(v)
    st = h.stats()
    assert st["count"] == 4
    assert st["sum"] == pytest.approx(3.55)
    assert st["counts"] == [1, 2, 1]     # per-bucket + the +Inf bucket
    txt = reg.prometheus_text()
    assert 'h_bucket{le="0.1"} 1' in txt
    assert 'h_bucket{le="1"} 3' in txt            # cumulative
    assert 'h_bucket{le="+Inf"} 4' in txt
    assert "h_sum 3.55" in txt
    assert "h_count 4" in txt


def test_histogram_rejects_bad_edges():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("empty", buckets=())
    with pytest.raises(ValueError):
        reg.histogram("dupe", buckets=(1.0, 1.0))


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c").inc(op="x")
    snap = reg.snapshot()
    assert snap["c"]["kind"] == "counter"
    assert snap["c"]["series"] == [{"labels": {"op": "x"}, "value": 1}]
    json.dumps(snap)                      # JSON-lines exporter contract


# ---------------------------------------------------------------------------
# event sink


def test_event_ring_bounded():
    sink = EventSink(ring=3)
    for i in range(10):
        sink.emit("k", i=i)
    assert sink.emitted == 10
    assert [e["i"] for e in sink.events()] == [7, 8, 9]
    with pytest.raises(ValueError):
        EventSink(ring=0)


def test_jsonl_tee_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with obs.using_obs(events_path=path) as sess:
        sess.emit("custom", value=1, arr=np.int32(7))  # stringified, not lost
    evs = obs.load_jsonl(path)
    assert len(evs) == 1
    assert evs[0]["kind"] == "custom" and evs[0]["value"] == 1
    assert "ts" in evs[0]


def test_format_resolution_tolerates_partial():
    line = obs.format_resolution({"op": "reduce", "chosen_path": "fused"})
    assert "op=reduce" in line and "path=fused" in line
    assert "n=-" in line and "src=-" in line
    full = obs.format_resolution({
        "op": "scan", "n": 2048, "shard_n": 512, "shard_divisor": 4,
        "dtype": "f32", "band": 11, "backend": "cpu", "level": "dispatch",
        "chosen_path": "baseline", "tuning": {"block_s": 64},
        "table_src": "heuristic"})
    assert "shard_divisor=4(shard_n=512)" in full
    assert "tuning=block_s=64" in full


# ---------------------------------------------------------------------------
# resolution audit trail


def _res_events(sess):
    return sess.events.events("resolution")


def test_resolution_event_schema_and_reresolve():
    pols = [KernelPolicy(interpret_fallback="silent"),
            KernelPolicy(path="baseline"),
            KernelPolicy(op_paths={"reduce": "fused"})]
    cases = [("reduce", 1 << 10, jnp.float32, None),
             ("scan", 1 << 8, jnp.bfloat16, None),
             ("reduce", 1 << 6, jnp.float32, "baseline")]
    for pol in pols:
        with obs.using_obs() as sess:
            for op, n, dtype, explicit in cases:
                got = pol.resolve(op=op, n=n, dtype=dtype,
                                  explicit=explicit)
                ev = _res_events(sess)[-1]
                assert all(f in ev for f in RESOLUTION_FIELDS)
                assert ev["op"] == op and ev["n"] == n
                assert ev["dtype"] == autotune.dtype_tag(dtype)
                assert ev["band"] == autotune.band(n)
                assert ev["chosen_path"] == str(got)
                # the event alone must re-resolve to the same choice
                again = pol.resolve(
                    op=ev["op"], n=ev["n"],
                    dtype=autotune.dtype_from_tag(ev["dtype"]),
                    level=ev["level"], explicit=ev["explicit"])
                assert str(again) == ev["chosen_path"]


def test_resolution_table_src_classification():
    pol = KernelPolicy(interpret_fallback="silent")
    with obs.using_obs() as sess:
        pol.resolve(op="reduce", n=512, dtype=jnp.float32,
                    explicit="baseline")
        pol.resolve(op="reduce")                       # auto, shapeless
        pol.resolve(op="reduce", n=512, dtype=jnp.float32)   # bucket hit
        KernelPolicy(autotune="off", interpret_fallback="silent").resolve(
            op="reduce", n=512, dtype=jnp.float32)
        srcs = [e["table_src"] for e in _res_events(sess)]
    assert srcs[0] == "none"
    assert srcs[1] == "static"
    assert srcs[2].endswith(".json")     # the consulted table file
    assert srcs[3] == "static"           # autotune off: no table consulted


def test_resolution_under_shard_context():
    from repro.parallel.mesh_context import MeshContext
    from repro.parallel.sharding import Rules

    ctx = MeshContext(mesh=None,
                      rules=Rules(table={}, axis_sizes={"model": 4}),
                      op_shard_axes={"reduce": "model"})
    pol = KernelPolicy(interpret_fallback="silent")
    with obs.using_obs() as sess:
        with ctx:
            got = pol.resolve(op="reduce", n=1024, dtype=jnp.float32)
            ev = _res_events(sess)[-1]
            assert ev["n"] == 1024                  # caller's shape...
            assert ev["shard_n"] == 256             # ...and the shard's
            assert ev["shard_divisor"] == 4
            assert ev["band"] == autotune.band(256)
            assert ev["chosen_path"] == str(
                pol.resolve(op="reduce", n=1024, dtype=jnp.float32))
        unsharded = _res_events(sess)[-1]
    # outside the context the same call is unsharded
    pol2 = KernelPolicy(interpret_fallback="silent")
    with obs.using_obs() as sess2:
        pol2.resolve(op="reduce", n=1024, dtype=jnp.float32)
        ev2 = _res_events(sess2)[-1]
    assert ev2["shard_divisor"] == 1 and ev2["shard_n"] == 1024
    assert got is not None and unsharded is not None


def test_kernel_invoke_event():
    x = jnp.arange(64, dtype=jnp.float32).reshape(4, 16)
    with obs.using_obs() as sess:
        dispatch.reduce(x, policy="interpret")
        invokes = sess.events.events("kernel_invoke")
    assert invokes, "pallas_op ran but emitted no kernel_invoke event"
    ev = invokes[-1]
    assert ev["n"] == 16 and ev["dtype"] == "f32"
    assert "path" in ev and "tuning" in ev


def test_resolution_counter_increments():
    pol = KernelPolicy(interpret_fallback="silent")
    with obs.using_obs() as sess:
        pol.resolve(op="reduce", n=256, dtype=jnp.float32)
        c = sess.metrics.get("repro_resolutions_total")
        assert c is not None
        assert sum(c.series().values()) == 1


# ---------------------------------------------------------------------------
# repro.ops spans and the compile counter


def _host_events(trace_dir):
    """``(name, start_ns, end_ns, stats, profile_start_time)`` of every
    host event named like a span (``layer/name``) in the one trace under
    ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (pb,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(pb)
    start = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats), start)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if "/" in e.name]


def test_event_in_an_ops_span_lands_inside_it_on_the_trace_clock(tmp_path):
    """Events keep wall-clock ``ts``; minus the trace's
    ``profile_start_time`` it falls inside the ``ops/<op>`` span of the
    call the event carries."""
    import repro.ops as rops
    from repro.obs import profiling

    x = jnp.arange(256, dtype=jnp.float32).reshape(4, 64)
    with obs.using_obs() as sess, profiling.tracing(str(tmp_path)):
        jax.block_until_ready(rops.reduce(x, policy="interpret"))
    res = sess.events.events("resolution")[-1]
    inv = sess.events.events("kernel_invoke")[-1]
    assert res["call"] is not None and inv["call"] == res["call"]
    spans = [ev for ev in _host_events(tmp_path) if ev[0] == "ops/reduce"]
    assert len(spans) == 1
    _, s, e, stats, start = spans[0]
    assert stats["call"] == res["call"] and stats["n"] == 64
    assert stats["routed"] == 0
    for ev in (res, inv):
        at = ev["ts"] * 1e9 - start
        assert s <= at <= e, (s, at, e)
    assert sess.metrics.get("repro_ops_calls_total").value(
        op="reduce", routed="false") == 1


def test_spans_enabled_is_scoped_and_tracing_takes_profile_options(
        tmp_path):
    from repro.obs import profiling

    assert not profiling._TRACING
    with profiling.spans_enabled():
        assert profiling._TRACING
        with profiling.spans_enabled():
            assert profiling._TRACING
        assert profiling._TRACING
    assert not profiling._TRACING
    with profiling.tracing(str(tmp_path)):
        with profiling.span("test/section", step=3):
            jax.block_until_ready(jnp.ones(4) + 1)
    assert not profiling._TRACING
    (ev,) = [e for e in _host_events(tmp_path) if e[0] == "test/section"]
    assert ev[3] == {"step": 3}


def test_compile_counter_counts_a_jitted_call_once():
    """Compiles are counted by stage; a jitted call's second run adds
    none, and its repro.ops call is counted at trace time only."""
    import repro.ops as rops

    f = jax.jit(lambda x: rops.scan(x, policy="fused") * 2.0)
    x = jnp.ones((3, 32), jnp.float32)
    with obs.using_obs() as sess:
        jax.block_until_ready(f(x))
        first = sess.metrics.snapshot()
        jax.block_until_ready(f(x))
        again = sess.metrics.snapshot()
    events = first["repro_compile_events_total"]["series"]
    stages = {s["labels"]["stage"] for s in events}
    assert {"trace", "lower", "compile"} <= stages
    assert again == first
    calls = first["repro_ops_calls_total"]["series"]
    assert calls == [{"labels": {"op": "scan", "routed": "false"},
                      "value": 1}]


# ---------------------------------------------------------------------------
# serving engine instrumentation


@pytest.fixture(scope="module")
def serving_parts():
    from repro import configs
    from repro.models import build
    from repro.models.common import init_params

    mod = configs.get("llama3.2-1b")
    bundle = build(mod.SMOKE)
    params = init_params(jax.random.PRNGKey(0), bundle.params_pspec,
                        mod.SMOKE.dtype)
    return bundle, params


def _requests(n, vocab=256):
    from repro.serving import Request

    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(
        3, vocab, size=int(rng.integers(4, 12)), dtype=np.int32))
        for i in range(n)]


def test_serving_tick_phases_sum_to_tick(serving_parts):
    from repro.serving import ServeConfig, ServingEngine

    bundle, params = serving_parts
    with obs.using_obs() as sess:
        eng = ServingEngine(bundle, params, ServeConfig(
            slots=2, max_new=4, eos_token=-1, scheduler="continuous"))
        eng.run(_requests(3))
        ph = sess.metrics.get("repro_serving_tick_phase_seconds")
        tick = sess.metrics.get("repro_serving_tick_seconds")
    assert ph is not None and tick is not None
    phase_sum = 0.0
    phases = set()
    for key, val in ph.series().items():
        phase_sum += val["sum"]
        phases.add(dict(key)["phase"])
    tick_stats = tick.stats()
    # the four phase intervals share their endpoints, so they sum to the
    # tick wall time up to float addition error
    assert phase_sum == pytest.approx(tick_stats["sum"], rel=1e-6)
    assert {"admission", "sample", "bookkeep"} <= phases
    assert phases & {"prefill", "decode"}
    counts = {dict(k)["phase"]: v["count"] for k, v in ph.series().items()}
    assert counts["admission"] == tick_stats["count"]


def test_serving_events_and_compile_cache(serving_parts):
    from repro.serving import ServeConfig, ServingEngine

    bundle, params = serving_parts
    with obs.using_obs() as sess:
        eng = ServingEngine(bundle, params, ServeConfig(
            slots=2, max_new=3, eos_token=-1, scheduler="continuous"))
        eng.run(_requests(2))
        serving = sess.events.events("serving")
        cache = sess.metrics.get("repro_serving_compile_cache_total")
        ttft = sess.metrics.get("repro_serving_ttft_seconds")
    kinds = {e["event"] for e in serving}
    assert "admit" in kinds and "finish" in kinds
    assert cache is not None and sum(cache.series().values()) >= 1
    assert ttft is not None and ttft.stats()["count"] >= 1


def test_serving_trace_ring_bounded(serving_parts):
    from repro.serving import ServeConfig, ServingEngine

    bundle, params = serving_parts
    eng = ServingEngine(bundle, params, ServeConfig(
        slots=2, max_new=4, eos_token=-1, scheduler="continuous",
        trace_ring=4))
    eng.run(_requests(4))
    assert len(eng.trace) <= 4           # bounded, newest-wins
    with pytest.raises(ValueError):
        ServeConfig(trace_ring=0)


# ---------------------------------------------------------------------------
# checkpoint instrumentation


def test_ckpt_phases_recorded(tmp_path):
    from repro.checkpoint import ckpt

    tree = {"params": {"w": jnp.arange(8, dtype=jnp.float32)}}
    with obs.using_obs() as sess:
        writer = ckpt.AsyncCheckpointer(str(tmp_path))
        writer.save(1, tree)
        writer.wait()
        snap = sess.metrics.get("repro_ckpt_snapshot_seconds")
        barrier = sess.metrics.get("repro_ckpt_commit_barrier_seconds")
        phases = {e["phase"] for e in sess.events.events("ckpt")}
    assert snap is not None and snap.stats()["count"] == 1
    assert barrier is not None and barrier.stats()["count"] == 1
    assert {"snapshot", "write", "commit_barrier"} <= phases


def test_ckpt_write_lands_in_issuing_session(tmp_path):
    """The background write records into the session active at save()
    time, even when the scope closes before the write finishes."""
    from repro.checkpoint import ckpt

    gate = threading.Event()
    tree = {"params": {"w": jnp.arange(4, dtype=jnp.float32)}}
    writer = ckpt.AsyncCheckpointer(str(tmp_path), _pre_commit=gate.wait)
    with obs.using_obs() as sess:
        writer.save(2, tree)             # write now gated, still in flight
    gate.set()                           # scope closed; release the write
    writer.wait()
    wh = sess.metrics.get("repro_ckpt_write_seconds")
    assert wh is not None and wh.stats()["count"] == 1


# ---------------------------------------------------------------------------
# autotune --check diff


def test_describe_bucket_renders_entry_and_live():
    ent = {"path": "fused", "us": {"fused": 12.5, "baseline": 20.0},
           "tuning": {"block_n": 256}}
    line = autotune.describe_bucket("reduce/f32/9", ent)
    assert "op=reduce" in line and "n=512" in line
    assert "path=fused" in line and "us=12.50" in line
    live = autotune.describe_bucket("reduce/f32/9")
    assert "op=reduce" in line and "path=" in live


def test_check_report_names_missing_and_stale(tmp_path):
    table = {"version": autotune.TABLE_VERSION, "backends": {
        autotune.current_backend(): {"jax": jax.__version__, "entries": {
            # one bucket outside the harness grid -> stale
            "reduce/f32/20": {"path": "fused", "us": {"fused": 1.0}},
        }}}}
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    problems = autotune.check_default(path)
    assert any("missing" in p for p in problems)
    assert any("stale" in p for p in problems)
    lines = autotune.check_report(path)
    assert any(l.strip().startswith("missing reduce/f32/4") for l in lines)
    stale = [l for l in lines if "stale" in l]
    assert len(stale) == 1 and "reduce/f32/20" in stale[0]
    assert "path=fused" in stale[0]


def test_dtype_tag_roundtrip():
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        assert autotune.dtype_from_tag(autotune.dtype_tag(dt)) == \
            jnp.dtype(dt)


# ---------------------------------------------------------------------------
# CLI scope + bench harness


def test_obs_scope_noop_without_flags():
    import argparse

    from repro.obs import cli as obs_cli

    args = argparse.Namespace(obs_events=None, metrics_out=None,
                              profile_dir=None)
    with obs_cli.obs_scope(args) as sess:
        assert sess is None
        assert obs.active() is None


def test_obs_scope_writes_artifacts(tmp_path):
    import argparse

    from repro.obs import cli as obs_cli

    ev = str(tmp_path / "e.jsonl")
    prom = str(tmp_path / "m.prom")
    args = argparse.Namespace(obs_events=ev, metrics_out=prom,
                              profile_dir=None)
    with obs_cli.obs_scope(args) as sess:
        sess.counter("repro_test_total", "x").inc()
        sess.emit("custom", a=1)
    assert obs.active() is None
    assert obs.load_jsonl(ev)[0]["a"] == 1
    assert "repro_test_total 1" in open(prom).read()


def test_time_stats_and_bandwidth_model():
    from benchmarks import common

    calls = []
    st = common.time_stats(lambda: calls.append(1) or jnp.zeros(1),
                          iters=4, warmup=2)
    assert len(calls) == 6               # warmup ran but is not measured
    assert st["iters"] == 4 and st["warmup"] == 2
    assert st["p25_s"] <= st["median_s"] <= st["p75_s"]
    assert st["iqr_s"] == pytest.approx(st["p75_s"] - st["p25_s"])

    from repro.launch import peaks

    # the peak comes from the device-kind table: the CPU entry here, the
    # published v5e numbers for the chip, and an unknown kind raises
    assert jax.devices()[0].device_kind == "cpu"
    bm = common.bandwidth_model(2_000_000_000, 0.1)
    assert bm["achieved_gbps"] == pytest.approx(20.0)
    assert bm["peak_gbps"] == peaks.PEAKS["cpu"].hbm_bytes_s / 1e9 == 50.0
    assert bm["pct_peak"] == pytest.approx(40.0)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
