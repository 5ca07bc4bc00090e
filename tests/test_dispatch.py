"""Dispatch-layer tests: the version shim, path resolution/override, and
agreement of the fused / tile / interpret paths for reduce, scan, and
weighted scan (fp32 and bf16)."""
import dataclasses
import re
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core import policy as kpolicy
from repro.kernels import backend, ops, ref

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


# ---------------------------------------------------------------------------
# version shim


def test_compiler_params_resolves_on_this_jax():
    cp = backend.compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    from jax.experimental.pallas import tpu as pltpu

    assert type(cp) is pltpu.CompilerParams
    assert tuple(cp.dimension_semantics) == ("parallel", "arbitrary")


def test_compiler_params_rejects_unknown_fields():
    # a misspelled knob must fail loudly, not be dropped
    with pytest.raises(TypeError):
        backend.compiler_params(dimension_semantics=("arbitrary",),
                                dimension_semantic=("arbitrary",))
    with pytest.raises(TypeError):
        backend.compiler_params(backend="gpu", num_warp=4)


def test_no_raw_compiler_params_outside_backend():
    """Only backend.py may construct the pltpu compiler-params class."""
    pat = re.compile(r"pltpu\s*\.\s*CompilerParams")
    offenders = [
        str(p.relative_to(SRC))
        for p in sorted(SRC.rglob("*.py"))
        if p.name != "backend.py" and pat.search(p.read_text())
    ]
    assert not offenders, (
        f"raw pltpu compiler-params construction in {offenders}; "
        "use repro.kernels.backend.compiler_params instead"
    )


def test_no_pallas_triton_import_outside_triton_package():
    """Same discipline for the GPU twin subsystem: only
    ``repro.kernels.triton`` may import ``jax.experimental.pallas.triton``
    (and within the package, only its ``compat`` shim does)."""
    pat = re.compile(
        r"^\s*(?:import\s+jax\.experimental\.pallas\.triton"
        r"|from\s+jax\.experimental\.pallas\.triton\s+import"
        r"|from\s+jax\.experimental\.pallas\s+import\s+[^\n]*\btriton\b)",
        re.MULTILINE)
    offenders = []
    for p in sorted(SRC.rglob("*.py")):
        rel = p.relative_to(SRC)
        if rel.parts[:2] == ("kernels", "triton"):
            if rel.name != "compat.py" and pat.search(p.read_text()):
                offenders.append(f"{rel} (only compat.py may)")
            continue
        if pat.search(p.read_text()):
            offenders.append(str(rel))
    assert not offenders, (
        f"raw jax.experimental.pallas.triton import in {offenders}; "
        "route through repro.kernels.triton.compat / "
        "backend.compiler_params(backend='gpu')"
    )


def test_no_shard_map_import_outside_parallel_compat():
    """Only ``parallel/compat.py`` may import ``shard_map``: one import
    site for a jax symbol that has been renamed before."""
    pat = re.compile(
        r"^\s*(?:from\s+jax\.experimental\.shard_map\s+import"
        r"|import\s+jax\.experimental\.shard_map"
        r"|from\s+jax\s+import\s+[^\n]*\bshard_map\b)",
        re.MULTILINE)
    offenders = [
        str(p.relative_to(SRC))
        for p in sorted(SRC.rglob("*.py"))
        if p.relative_to(SRC).parts != ("parallel", "compat.py")
        and pat.search(p.read_text())
    ]
    assert not offenders, (
        f"raw shard_map import in {offenders}; "
        "import it from repro.parallel.compat instead"
    )


def test_no_make_mesh_outside_parallel():
    """Only the ``parallel`` package may call ``jax.make_mesh`` — every
    other layer consumes a MeshContext (or ``parallel.compat.make_mesh``),
    so mesh construction policy (axis types, version shims) has one home."""
    pat = re.compile(r"\bjax\s*\.\s*make_mesh\s*\(")
    offenders = [
        str(p.relative_to(SRC))
        for p in sorted(SRC.rglob("*.py"))
        if p.relative_to(SRC).parts[0] != "parallel"
        and pat.search(p.read_text())
    ]
    assert not offenders, (
        f"raw jax.make_mesh call in {offenders}; build meshes via "
        "repro.parallel.mesh_context.make_context or parallel.compat"
    )


# ---------------------------------------------------------------------------
# path resolution


def test_kernel_resolution_defaults_off_tpu(monkeypatch):
    monkeypatch.delenv(backend.ENV_PATH, raising=False)
    if backend.native_tile_backend() is not None:
        pytest.skip("CPU-only expectations")
    silent = dataclasses.replace(kpolicy.get_policy(),
                                 interpret_fallback="silent")
    resolve = lambda p=None: silent.resolve(level="kernel", explicit=p)
    assert resolve() == "fused"
    assert resolve("tile") == "interpret"   # nothing to compile
    assert resolve("interpret") == "interpret"
    # the legacy use_pallas bool folds into a label before resolution
    assert resolve(backend._merge_use_pallas(None, True)) == "interpret"
    assert resolve(backend._merge_use_pallas(None, False)) == "fused"


def test_tile_downgrade_warns_once_then_stays_silent(monkeypatch):
    """The off-accelerator tile→interpret downgrade must say so ONCE —
    naming the resolved backend and the way to silence it — and never
    again in the same process."""
    if backend.native_tile_backend() is not None:
        pytest.skip("downgrade only happens off-accelerator")
    monkeypatch.delenv(backend.ENV_PATH, raising=False)
    monkeypatch.setattr(kpolicy, "_TILE_DOWNGRADE_WARNED", False)
    resolve = kpolicy.get_policy().resolve
    with pytest.warns(UserWarning, match="interpret") as rec:
        assert resolve(level="kernel", explicit="tile") == "interpret"
    msg = str(rec[0].message)
    assert jax.default_backend() in msg          # names the backend
    assert "path='interpret'" in msg             # names the silencer
    # second resolution: no warning at all
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve(level="kernel", explicit="tile") == "interpret"
    # an explicit interpret request never warns
    monkeypatch.setattr(kpolicy, "_TILE_DOWNGRADE_WARNED", False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve(level="kernel", explicit="interpret") == "interpret"
    # interpret_fallback="silent" suppresses it entirely; "error" raises
    monkeypatch.setattr(kpolicy, "_TILE_DOWNGRADE_WARNED", False)
    silent = dataclasses.replace(kpolicy.get_policy(),
                                 interpret_fallback="silent")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert silent.resolve(level="kernel", explicit="tile") == "interpret"
    strict = dataclasses.replace(kpolicy.get_policy(),
                                 interpret_fallback="error")
    with pytest.raises(RuntimeError, match="interpret_fallback"):
        strict.resolve(level="kernel", explicit="tile")


def test_unfit_shape_runs_oracle_warns_once_and_records(monkeypatch):
    """A kernel-path call whose shape the kernel's blocks do not fit runs
    the fused oracle: it warns once per op, records a kernel_invoke event
    whose path says oracle, and raises under interpret_fallback='error'."""
    from repro import obs

    monkeypatch.setattr(kpolicy, "_WARNED", set())
    x = jnp.ones((4, 100), jnp.float32)         # d=100: not lane-aligned
    w = jnp.ones((100,), jnp.float32)
    want = ref.rmsnorm_ref(x, w)
    with obs.using_obs() as sess:
        with pytest.warns(UserWarning, match="fused oracle"):
            got = ops.rmsnorm(x, w, path="interpret")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ops.rmsnorm(x, w, path="interpret")  # once per op, not per call
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    paths = [e["path"] for e in sess.events.events("kernel_invoke")]
    assert paths == ["oracle", "oracle"]
    strict = dataclasses.replace(kpolicy.get_policy(),
                                 interpret_fallback="error")
    with pytest.raises(RuntimeError, match="interpret_fallback='error'"):
        ops.rmsnorm(x, w, policy=strict, path="interpret")
    # a shape the kernel fits runs it, and says so
    q = jnp.ones((1, 1, 128, 128), jnp.float32)
    with obs.using_obs() as sess:
        ops.attention(q, q, q, policy=strict, path="interpret")
    assert [e["path"] for e in sess.events.events("kernel_invoke")] == \
        ["interpret"]


def test_explicit_tile_backend_labels_are_strict():
    """tile_tpu / tile_gpu force a backend and must raise clearly on the
    wrong host (the generic 'tile' is the lenient spelling)."""
    native = backend.native_tile_backend()
    resolve = lambda p: kpolicy.get_policy().resolve(level="kernel",
                                                     explicit=p)
    if native != "tile_tpu":
        with pytest.raises(RuntimeError, match="tile_tpu"):
            resolve("tile_tpu")
        with pytest.raises(RuntimeError, match="requires a TPU"):
            dispatch.reduce(jnp.ones((2, 64)), path="tile_tpu")
    if native != "tile_gpu":
        with pytest.raises(RuntimeError, match="tile_gpu"):
            resolve("tile_gpu")
    if native is not None:
        assert resolve("tile") == native


def test_resolution_env_override(monkeypatch):
    monkeypatch.setenv(backend.ENV_PATH, "interpret")
    assert kpolicy.get_policy().resolve(level="kernel") == "interpret"
    assert kpolicy.get_policy().resolve() == "interpret"
    # explicit per-call choice beats the env var
    assert kpolicy.get_policy().resolve(level="kernel",
                                        explicit="fused") == "fused"
    monkeypatch.setenv(backend.ENV_PATH, "baseline")
    assert kpolicy.get_policy().resolve() == "baseline"


def test_resolution_rejects_unknown():
    with pytest.raises(ValueError):
        kpolicy.get_policy().resolve(level="kernel", explicit="cuda")
    with pytest.raises(ValueError):
        kpolicy.get_policy().resolve(explicit="warp")


def test_pallas_op_unknown_name():
    with pytest.raises(KeyError):
        backend.pallas_op("nonexistent_op", jnp.zeros((4,)))


def test_registry_has_all_ops():
    assert set(backend.available_ops()) >= {
        "segmented_reduce", "segmented_scan", "weighted_scan",
        "rmsnorm", "ssd_scan", "attention",
    }


# ---------------------------------------------------------------------------
# path agreement (the acceptance contract: one switch, same numbers)

KERNEL_PATHS = ["fused", "tile", "interpret"]


def _tol(dtype):
    return dict(rtol=1e-4, atol=1e-3) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", KERNEL_PATHS)
def test_reduce_paths_agree(path, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 300)).astype(dtype)
    got = np.asarray(ops.segmented_reduce(x, path=path))
    want = np.asarray(x, np.float32).sum(-1)
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", KERNEL_PATHS)
def test_scan_paths_agree(path, dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 200)).astype(dtype)
    got = np.asarray(ops.segmented_scan(x, path=path))
    want = np.cumsum(np.asarray(x, np.float32), axis=-1)
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", KERNEL_PATHS)
def test_weighted_scan_paths_agree(path, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 160)).astype(dtype)
    la = (-jax.random.uniform(jax.random.PRNGKey(3), (2, 160))).astype(dtype)
    got = np.asarray(ops.weighted_scan(x, la, path=path))
    want = np.asarray(
        ref.weighted_scan_ref(x.astype(jnp.float32), la.astype(jnp.float32)))
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("path", ["fused", "xla_tile", "interpret",
                                  "baseline"])
def test_core_dispatch_reduce_scan_one_switch(path):
    """The benchmark entry contract: every contender from one argument."""
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 257))
    np.testing.assert_allclose(
        np.asarray(dispatch.reduce(x, path=path)),
        np.asarray(x).sum(-1), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(dispatch.scan(x, path=path)),
        np.cumsum(np.asarray(x), -1), rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("exclusive", [False, True])
def test_core_dispatch_scan_exclusive_paths(exclusive):
    x = jax.random.normal(jax.random.PRNGKey(5), (300,))
    want = np.asarray(dispatch.scan(x, path="baseline", exclusive=exclusive))
    for path in ("fused", "interpret"):
        got = np.asarray(dispatch.scan(x, path=path, exclusive=exclusive))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)


def test_core_dispatch_weighted_scan_paths():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 300))
    la = -jax.random.uniform(jax.random.PRNGKey(7), (2, 300))
    want = np.asarray(dispatch.weighted_scan(x, la, path="baseline"))
    for path in ("fused", "interpret"):
        got = np.asarray(dispatch.weighted_scan(x, la, path=path))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_core_dispatch_ssd_paths():
    b, L, h, p, g, n = 1, 100, 2, 8, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = 0.2 * jax.random.normal(ks[0], (b, L, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, h)))
    a = -jnp.exp(0.2 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, L, g, n)) / np.sqrt(n)
    cc = jax.random.normal(ks[4], (b, L, g, n)) / np.sqrt(n)
    want = np.asarray(dispatch.ssd(x, dt, a, bb, cc, path="baseline"))
    for path in ("fused", "interpret"):
        got = np.asarray(dispatch.ssd(x, dt, a, bb, cc, path=path))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_core_dispatch_ssd_return_state_paths_agree():
    """The prefill->decode handoff state must agree on every path — the
    kernel path's padded-state slice (lam zero-pad => decay 1) is the
    subtle part, exercised here with L not a multiple of the chunk."""
    b, L, h, p, g, n = 1, 100, 2, 8, 1, 4   # L=100: forces padding
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = 0.2 * jax.random.normal(ks[0], (b, L, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, h)))
    a = -jnp.exp(0.2 * jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, L, g, n)) / np.sqrt(n)
    cc = jax.random.normal(ks[4], (b, L, g, n)) / np.sqrt(n)
    y_want, h_want = dispatch.ssd(x, dt, a, bb, cc, path="baseline",
                                  return_state=True)
    assert h_want.shape == (b, h, p, n)
    for path in ("fused", "interpret"):
        y_got, h_got = dispatch.ssd(x, dt, a, bb, cc, path=path,
                                    return_state=True)
        assert h_got.shape == (b, h, p, n)
        np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want),
                                   rtol=2e-3, atol=2e-3)
        # y must be identical with and without the state request
        y_only = dispatch.ssd(x, dt, a, bb, cc, path=path)
        np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_only),
                                   rtol=0, atol=0)


def test_env_var_steers_op_execution(monkeypatch):
    """REPRO_KERNEL_PATH reroutes an unannotated call site end to end."""
    x = jnp.ones((2, 130))
    monkeypatch.setenv(backend.ENV_PATH, "interpret")
    got = np.asarray(ops.segmented_reduce(x))
    monkeypatch.setenv(backend.ENV_PATH, "fused")
    want = np.asarray(ops.segmented_reduce(x))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(want, 130.0)


@pytest.mark.parametrize("envval", ["fused", "tile", "interpret",
                                    "baseline", "xla_tile"])
def test_env_values_never_crash_kernel_ops(monkeypatch, envval):
    """The env var is process-wide and shared with repro.core.dispatch, so
    its algorithm-level values (baseline/xla_tile) must not blow up
    kernel-level call sites (e.g. every model's rmsnorm)."""
    monkeypatch.setenv(backend.ENV_PATH, envval)
    x = jnp.ones((2, 130))
    np.testing.assert_allclose(
        np.asarray(ops.segmented_reduce(x)), 130.0, rtol=1e-6)


def test_legacy_use_pallas_kwarg_still_works():
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 100))
    np.testing.assert_allclose(
        np.asarray(ops.segmented_reduce(x, use_pallas=True)),
        np.asarray(ops.segmented_reduce(x, use_pallas=False)),
        rtol=1e-4, atol=1e-3)


def test_conflicting_path_and_use_pallas_warns_path_wins():
    x = jnp.ones((2, 100))
    with pytest.warns(UserWarning, match="path= takes precedence"):
        assert backend._merge_use_pallas("fused", True) == "fused"
    with pytest.warns(UserWarning, match="path= takes precedence"):
        got = ops.segmented_reduce(x, path="fused", use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), 100.0)
    with pytest.warns(UserWarning):
        assert backend._merge_use_pallas("tile", False) == "tile"


def test_agreeing_path_and_use_pallas_no_warning(recwarn):
    # interpret runs the same kernel body -> not a conflict with
    # use_pallas=True; matching values never warn
    assert backend._merge_use_pallas("interpret", True) == "interpret"
    assert backend._merge_use_pallas("fused", False) == "fused"
    silent = dataclasses.replace(kpolicy.get_policy(),
                                 interpret_fallback="silent")
    assert silent.resolve(
        level="kernel",
        explicit=backend._merge_use_pallas(None, False)) == "fused"
    assert not [w for w in recwarn.list
                if issubclass(w.category, UserWarning)]


# ---------------------------------------------------------------------------
# autodiff: kernel paths differentiate (backward rides the ref twin)


def test_kernel_paths_differentiate_like_fused():
    """pallas_call has no JVP rule in interpret mode, so the kernel
    registry wraps every tile entry in a custom VJP whose backward runs
    the reference formulation — a train step under policy='interpret'
    (or 'tile' on an accelerator) must produce the same gradients as
    'fused'."""
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 130))

    def loss(path):
        return lambda a: jnp.sum(ops.segmented_scan(a, path=path) ** 2)

    g_fused = np.asarray(jax.grad(loss("fused"))(x))
    g_int = np.asarray(jax.grad(loss("interpret"))(x))
    np.testing.assert_allclose(g_int, g_fused, rtol=1e-4, atol=1e-4)

    def red_loss(path):
        return lambda a: jnp.sum(ops.segmented_reduce(a, path=path) ** 2)

    np.testing.assert_allclose(
        np.asarray(jax.grad(red_loss("interpret"))(x)),
        np.asarray(jax.grad(red_loss("fused"))(x)), rtol=1e-4, atol=1e-4)


def test_attention_and_ssd_interpret_paths_differentiate():
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = jax.random.normal(ks[0], (1, 2, 128, 16))
    k = jax.random.normal(ks[1], (1, 2, 128, 16))
    v = jax.random.normal(ks[2], (1, 2, 128, 16))

    def att_loss(path):
        return lambda qq: jnp.sum(ops.attention(qq, k, v, path=path) ** 2)

    g_f = np.asarray(jax.grad(att_loss("fused"))(q))
    g_i = np.asarray(jax.grad(att_loss("interpret"))(q))
    np.testing.assert_allclose(g_i, g_f, rtol=2e-3, atol=2e-3)

    x = 0.2 * jax.random.normal(ks[3], (1, 64, 2, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 64, 2)))
    a = -jnp.exp(jnp.zeros((2,)))
    bb = jax.random.normal(ks[0], (1, 64, 1, 4)) / 2.0
    cc = jax.random.normal(ks[1], (1, 64, 1, 4)) / 2.0

    def ssd_loss(path):
        return lambda xx: jnp.sum(
            dispatch.ssd(xx, dt, a, bb, cc, path=path) ** 2)

    g_f = np.asarray(jax.grad(ssd_loss("fused"))(x))
    g_i = np.asarray(jax.grad(ssd_loss("interpret"))(x))
    np.testing.assert_allclose(g_i, g_f, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# exclusive scan: shift, never inclusive-minus-x (catastrophic cancellation)


@pytest.mark.parametrize("path", ["fused", "interpret", "baseline"])
def test_exclusive_scan_adversarial_magnitudes(path):
    """exclusive[i] must be exact when the preceding prefix is tiny and
    x[i] is huge — reconstructing it as ``inclusive - x`` absorbs the
    prefix into x[i]'s rounding and returns garbage."""
    x = jnp.asarray([0.1, 0.2, 0.3, 1e8, -1e8, 0.4], jnp.float32)
    got = np.asarray(dispatch.scan(x, path=path, exclusive=True))
    want = np.concatenate(
        [[0.0], np.cumsum(np.asarray(x, np.float64))[:-1]])
    # positions 0..3 have small true prefixes; the shift keeps them exact
    np.testing.assert_allclose(got[:4], want[:4], rtol=1e-6, atol=1e-6)
    assert got.shape == x.shape


def test_exclusive_scan_paths_agree_random():
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 300))
    want = np.asarray(dispatch.scan(x, path="baseline", exclusive=True))
    for path in ("fused", "interpret"):
        got = np.asarray(dispatch.scan(x, path=path, exclusive=True))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)


# ---------------------------------------------------------------------------
# ragged entries (the paper's footnote-4 case through the one switch)

RAGGED_PATHS = ["fused", "xla_tile", "interpret", "baseline"]


def _ragged_case(n, s, seed, dtype):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, s, n)).astype(np.int32)
    x = rng.normal(size=n).astype(np.float32)
    return jnp.asarray(x).astype(dtype), jnp.asarray(seg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", RAGGED_PATHS)
def test_ragged_reduce_paths_agree(path, dtype):
    n, s = 300, 7
    x, seg = _ragged_case(n, s, 0, dtype)
    got = np.asarray(dispatch.ragged_reduce(x, seg, s, path=path))
    xs = np.asarray(x, np.float32)
    segn = np.asarray(seg)
    want = np.array([xs[segn == i].sum() for i in range(s)])
    tol = dict(rtol=1e-4, atol=1e-3) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(got, want, **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", RAGGED_PATHS)
def test_ragged_scan_paths_agree(path, dtype):
    n, s = 300, 7
    x, seg = _ragged_case(n, s, 1, dtype)
    got = np.asarray(dispatch.ragged_scan(x, seg, s, path=path))
    xs = np.asarray(x, np.float32)
    segn = np.asarray(seg)
    want = np.empty(n, np.float32)
    for i in range(s):
        m = segn == i
        want[m] = np.cumsum(xs[m])
    tol = dict(rtol=1e-3, atol=1e-2) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(got, want, **tol)


@pytest.mark.parametrize("path", ["fused", "baseline"])
def test_ragged_batched_seg_ids(path):
    """Per-batch segment assignments (the MoE per-group layout)."""
    g, n, s = 3, 64, 5
    rng = np.random.default_rng(2)
    seg = np.sort(rng.integers(0, s, (g, n)), axis=-1).astype(np.int32)
    x = rng.normal(size=(g, n)).astype(np.float32)
    got = np.asarray(dispatch.ragged_reduce(jnp.asarray(x),
                                            jnp.asarray(seg), s, path=path))
    want = np.stack([[x[b][seg[b] == i].sum() for i in range(s)]
                     for b in range(g)])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ragged_env_var_steers(monkeypatch):
    x, seg = _ragged_case(200, 5, 3, jnp.float32)
    monkeypatch.setenv(backend.ENV_PATH, "baseline")
    got_b = np.asarray(dispatch.ragged_scan(x, seg, 5))
    monkeypatch.setenv(backend.ENV_PATH, "fused")
    got_f = np.asarray(dispatch.ragged_scan(x, seg, 5))
    np.testing.assert_allclose(got_b, got_f, rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# consumer discipline: every model/optim/serving op goes through the switch


def test_no_direct_core_primitive_imports_outside_core_kernels():
    """Same discipline as the compiler-params guard: the dispatch layer is
    the single source of truth for which formulation runs where. Modules
    outside repro.core/repro.kernels must not touch the primitives
    directly — that is exactly the bypass that made REPRO_KERNEL_PATH
    silently no-op for models, optim, and the ragged ops."""
    pat = re.compile(
        r"\b(tcu_segmented_reduce|tcu_scan|tcu_reduce|tcu_weighted_scan"
        r"|tcu_ragged_segment_reduce|tcu_ragged_segment_scan"
        r"|ssd_chunked)\b")
    offenders = []
    for p in sorted(SRC.rglob("*.py")):
        rel = p.relative_to(SRC)
        if rel.parts[0] in ("core", "kernels"):
            continue
        if pat.search(p.read_text()):
            offenders.append(str(rel))
    assert not offenders, (
        f"direct repro.core primitive use in {offenders}; route through "
        "repro.core.dispatch (path= / REPRO_KERNEL_PATH / autotuned auto)"
    )
