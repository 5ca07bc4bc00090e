"""The mesh/sharding entry points every caller in this repo goes through,
sibling of ``repro.kernels.backend`` (which wraps the Pallas surface).

* ``shard_map`` — ``jax.shard_map``. Callers whose bodies mix per-shard
  and psum'd values built from matmul-form carries (``parallel.shard_ops``)
  pass ``check_vma=False``: the varying-manual-axes checker cannot type
  them.
* :func:`make_mesh` — ``jax.make_mesh`` pinned to ``AxisType.Auto``
  (JAX defaults new meshes to explicit sharding; every caller in this repo
  assumes GSPMD-propagated, auto axes).
"""
from __future__ import annotations

from typing import Sequence

import jax


shard_map = jax.shard_map


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kwargs) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with auto axis semantics on every axis."""
    kwargs.setdefault(
        "axis_types", (jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)
