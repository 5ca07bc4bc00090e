"""HLO static-analyser tests: parsing, loop multipliers, collective and
memory-traffic conventions — on handcrafted modules and a real lowering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import (
    analyse,
    parse_computations,
    roofline_terms,
)

MINI = """
HloModule mini

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128] get-tuple-element(%p), index=1
  %w = f32[128,128] constant({...})
  %y = f32[8,128] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128] all-reduce(%y), replica_groups={}, to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,128]) tuple(%ni, %ar)
}

%cond (p2: (s32[], f32[8,128])) -> pred[] {
  %p2 = (s32[], f32[8,128]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i2, %n), direction=LT
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x0: f32[8,128]) -> (s32[], f32[8,128]) {
  %x0 = f32[8,128] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,128]) tuple(%z, %x0)
  ROOT %w0 = (s32[], f32[8,128]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
}
"""


def test_mini_module_loop_flops():
    h = analyse(MINI)
    # dot: 2*8*128*128 flops, body runs 10x
    assert h["flops"] >= 10 * 2 * 8 * 128 * 128
    assert h["flops"] < 11 * 2 * 8 * 128 * 128


def test_mini_module_collectives():
    h = analyse(MINI)
    # all-reduce convention: 2x result bytes, 10 iterations
    want = 10 * 2 * 8 * 128 * 4
    assert h["collective_bytes"]["all-reduce"] == want
    assert h["collective_total"] == want
    assert h["unknown_trip_whiles"] == 0


def test_tuple_with_index_comments_parsed():
    txt = MINI.replace(
        "(s32[], f32[8,128]) while",
        "(s32[], f32[8,128], s32[], s32[], s32[], /*index=5*/f32[8,128]) "
        "while")
    comps = parse_computations(txt)
    assert any(i.opcode == "while" for c in comps.values()
               for i in c.instrs)


def test_roofline_terms_dominant():
    terms = roofline_terms(
        {"flops": 197e12, "memory_bytes": 819e9 * 2,
         "collective_total": 50e9 * 0.5},
        peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(2.0)
    assert terms["collective_s"] == pytest.approx(0.5)
    assert terms["dominant"] == "memory_s"


def test_real_lowering_matmul_flops():
    """Lower C = A@B on this process's devices; analyser flops ~= 2MNK."""
    m, k, n = 256, 512, 128

    def f(a, b):
        return a @ b

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32))
    h = analyse(lowered.compile().as_text())
    assert h["flops"] == pytest.approx(2 * m * k * n, rel=0.05)


def test_real_lowering_scan_multiplier():
    """A lax.scan of T matmuls must count T x the per-iteration flops."""
    t, d = 8, 64

    def f(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((t, d, d), jnp.float32),
        jax.ShapeDtypeStruct((4, d), jnp.float32))
    h = analyse(lowered.compile().as_text())
    want = t * 2 * 4 * d * d
    assert h["flops"] >= want
    assert h["flops"] < 2.0 * want


def test_memory_model_slices_not_full_buffers():
    """A scan that slices one row per step must charge per-slice traffic,
    not the whole stacked buffer per iteration."""
    t, d = 64, 256

    def f(w, x):
        def body(h, wl):
            return h + wl, None
        h, _ = jax.lax.scan(body, x, w)
        return h

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((t, d), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32))
    h = analyse(lowered.compile().as_text())
    full_buffer_per_iter = t * (t * d * 4)       # the wrong accounting
    assert h["memory_bytes"] < full_buffer_per_iter / 4


# ---------------------------------------------------------------------------
# carry-depth structure of the scan kernel paths (jaxpr-level, no timing):
# the linear tile path serialises its inter-block carry as an 'arbitrary'
# grid dimension whose extent grows with n, while tile_logdepth keeps every
# Pallas grid fully parallel and pays only O(log_radix n) tree-combine
# matmuls at the XLA level.


def _subjaxprs(v):
    if isinstance(v, jax.extend.core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax.extend.core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def _walk_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _scan_structure(path, n):
    """(serialised pallas grid steps, dot_general count) of a lowering."""
    import dataclasses

    from repro.core import policy as kpolicy
    from repro.kernels import ops

    pol = dataclasses.replace(kpolicy.get_policy(),
                              interpret_fallback="silent")
    x = jnp.ones((8, n), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a: ops.segmented_scan(a, policy=pol, path=path))(x).jaxpr
    serial, dots, semantics = 1, 0, []
    for e in _walk_eqns(jaxpr):
        if e.primitive.name == "dot_general":
            dots += 1
        if e.primitive.name != "pallas_call":
            continue
        grid = e.params["grid_mapping"].grid
        # one pltpu.CompilerParams object, keyed by its backend name
        (cp,) = e.params["compiler_params"].values()
        sem = tuple(cp.dimension_semantics)
        semantics.extend(sem)
        for g, s in zip(grid, sem):
            if s == "arbitrary":
                serial *= g
    return serial, dots, semantics


def test_linear_tile_path_serialises_carry_with_n():
    base, _, sem = _scan_structure("interpret", 1024)
    quad, _, _ = _scan_structure("interpret", 4096)
    big, _, _ = _scan_structure("interpret", 16384)
    assert "arbitrary" in sem          # the carry dimension is sequential
    assert base >= 2
    assert quad == 4 * base            # serial steps scale linearly in n
    assert big == 16 * base


def test_logdepth_path_has_parallel_grids_and_log_combines():
    s1, d1, sem1 = _scan_structure("tile_logdepth", 1024)
    s2, d2, sem2 = _scan_structure("tile_logdepth", 16384)
    # local block kernels carry nothing between grid steps
    assert sem1 and set(sem1) == {"parallel"}
    assert sem2 and set(sem2) == {"parallel"}
    assert s1 == 1 and s2 == 1
    # a 16x larger input costs at most a couple more tree rounds, nothing
    # like the 16x serial-step growth of the linear path
    assert d1 >= 1
    assert d2 <= d1 + 4
