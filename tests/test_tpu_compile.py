"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, at the widths ``chip_smoke.py`` runs them on the chip.

Interpret mode on the CPU cannot see what Mosaic refuses (block shapes that
break the (8, 128) tiling rule, layouts that do not match XLA's). These
tests hand the kernel entries shapes only, compile them for one chip of a
``v5e:2x2`` topology, and assert that the program holds the kernel
(``tpu_custom_call``) — so a shape that falls back to XLA fails too. The
routed ``shard_ops`` programs are compiled for all four chips of it, at the
four-chip cell's size.

The topology is described only inside the module fixture below: only one
process at a time may load the TPU library, and a worker that is never
given this file must not try.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.policy import KernelPolicy
from repro.kernels import backend
from repro.kernels import ops  # noqa: F401  (registers the kernel entries)
from repro.parallel import shard_ops
from repro.parallel.mesh_context import MeshContext


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache without the chip; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def four_chips(topology):
    return Mesh(np.array(topology.devices).reshape(4), ("data",))


def _compile_for_chip(one_chip, name, family, shapes):
    entry = getattr(backend.get_op(name), family)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(lambda *a: entry(*a, interpret=False)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text, (name, family, shapes)


F32, BF16 = jnp.float32, jnp.bfloat16
# 2^26 f32 elements at the paper's segment sizes; >= 2 blocks of segments
SEGMENTED = [(1 << 22, 16), (1 << 18, 256), (1 << 14, 4096)]
# Mamba-2-1.3B head geometry: B=4, L=4096, H=64, P=64, N=128, G=1
SSD = [((4, 4096, 64, 64), BF16), ((4, 4096, 64), F32), ((64,), F32),
       ((4, 4096, 1, 128), BF16), ((4, 4096, 1, 128), BF16)]


@pytest.mark.parametrize("shape", SEGMENTED)
def test_segmented_reduce_compiles_for_v5e(one_chip, shape):
    _compile_for_chip(one_chip, "segmented_reduce", "tile", [(shape, F32)])


@pytest.mark.parametrize("family", ["tile", "tile_logdepth"])
@pytest.mark.parametrize("shape", SEGMENTED)
def test_segmented_scan_compiles_for_v5e(one_chip, shape, family):
    _compile_for_chip(one_chip, "segmented_scan", family, [(shape, F32)])


@pytest.mark.parametrize("family", ["tile", "tile_logdepth"])
def test_weighted_scan_compiles_for_v5e(one_chip, family):
    _compile_for_chip(one_chip, "weighted_scan", family,
                      [((1024, 2048), F32), ((1024, 2048), F32)])


@pytest.mark.parametrize("family", ["tile", "tile_logdepth"])
def test_ssd_scan_compiles_for_v5e(one_chip, family):
    _compile_for_chip(one_chip, "ssd_scan", family, SSD)


@pytest.mark.parametrize("d", [2048, 4096])
def test_rmsnorm_compiles_for_v5e(one_chip, d):
    _compile_for_chip(one_chip, "rmsnorm", "tile",
                      [((8192, d), BF16), ((d,), BF16)])


@pytest.mark.parametrize("d", [2048, 4096])
def test_attention_compiles_for_v5e(one_chip, d):
    # kernel layout (B, H, L, D), 128-wide heads
    qkv = ((1, d // 128, 2048, 128), BF16)
    _compile_for_chip(one_chip, "attention", "tile", [qkv, qkv, qkv])


@pytest.mark.parametrize("op, collective",
                         [("reduce", "all-reduce"), ("scan", "all-gather")])
def test_routed_program_compiles_for_v5e_2x2(four_chips, monkeypatch, op,
                                             collective):
    """The routed program of the four-chip cell: per-shard tile kernels
    and the collective that combines them, within one chip's 16 GiB. The
    host's probe for a native tile backend is pointed at the described
    chip, as policy resolution would find it there."""
    monkeypatch.setattr(backend, "native_tile_backend", lambda: "tile_tpu")
    x = jax.ShapeDtypeStruct((1024, 65536), jnp.float32,
                             sharding=NamedSharding(four_chips,
                                                    P(None, "data")))
    pol = KernelPolicy(path="tile", backend="tpu", interpret_fallback="error")
    with MeshContext(mesh=four_chips):
        plan = shard_ops.plan(op, x, policy=pol)
        exe = plan.fn.lower(*plan.args).compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text and collective in text
    mem = exe.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16 * 2 ** 30, used
