"""Production meshes and the logical-axis rule tables for each.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` *before* any jax init and only then
calls in here.

Mesh axes:
  single-pod : (data=16, model=16)            = 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips; ``pod`` is an
               outer data-parallel axis whose gradient all-reduce crosses
               the DCN (slow link — see optim/compress.py).

The rule tables map the model code's logical dim names onto mesh axes.
Divisibility degradation (kv_heads=4 on a 16-way axis -> replicate) is
handled inside ``spec_for``; the table just states intent.
"""
from __future__ import annotations

import jax

from repro.parallel.compat import make_mesh
from repro.parallel.mesh_context import MeshContext
from repro.parallel.sharding import Rules

def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_production_context(*, multi_pod: bool = False, fsdp: bool = True,
                            seq_shard: bool = False,
                            op_shard_axes=()) -> MeshContext:
    """The production mesh + rules as one activatable MeshContext."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    return MeshContext(mesh=mesh,
                       rules=make_rules(mesh, fsdp=fsdp,
                                        seq_shard=seq_shard),
                       op_shard_axes=op_shard_axes)


def make_rules(mesh: jax.sharding.Mesh, *, fsdp: bool = True,
               seq_shard: bool = False) -> Rules:
    """Logical-name -> mesh-axis table for a production mesh.

    ``seq_shard`` additionally shards long sequence/cache dims over ``data``
    (sequence parallelism — the long_500k decode cells, where batch=1 leaves
    the data axis otherwise idle).
    """
    multi_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    table = {
        # activations
        "batch": batch_axes,
        "vocab": "model",
        # attention params
        "heads": "model",
        "kv_heads": "model",
        # mlp / moe params
        "ff": "model",
        "e_ff": "model",
        "experts": "model",
        # MoE dispatch groups ride the batch axes (grouped dispatch keeps
        # all routing scatter/gather shard-local; see layers.py). The flat
        # (expert x capacity) slot dim rides the model axis. exp_cap
        # catches the residual data-axis sharding for the global impl.
        "moe_groups": batch_axes,
        "exp_slots": "model",
        "exp_cap": "data",
        # mamba params
        "inner": "model",
        "inner_all": "model",
        "ssm_heads": "model",
        # never TP-shard the residual width or the layer stack
        "embed": None,
        "layers": None,
        # decode cells shard the KV-cache sequence dim; spec_for drops any
        # axis already consumed by the tensor's batch dim, so this resolves
        # to "model" when batch occupies "data" (decode_32k) and to both
        # axes when batch=1 replicates (long_500k).
        "kv_seq": ("data", "model") if seq_shard else None,
    }
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return Rules(table=table, fsdp="data" if fsdp else None,
                 axis_sizes=sizes)


def make_smoke_mesh(n: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    devs = jax.devices()[:n]
    import numpy as np

    return jax.sharding.Mesh(np.array(devs).reshape(-1), ("data",))
