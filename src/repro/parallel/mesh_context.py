"""MeshContext — mesh + rules + process topology as one first-class object.

PRs 1-6 built the pieces separately: ``Rules`` (logical-name sharding
table), ``compat.make_mesh`` (auto-axis mesh), and ad-hoc ``(mesh, rules)``
pairs constructed at every launch site. Multi-host execution needs them to
travel together, because three layers consult the same topology:

* **kernel resolution** — under an active MeshContext,
  :meth:`~repro.core.policy.KernelPolicy.resolve` divides the call's
  bucket axis by the context's shard divisor for that op
  (:meth:`MeshContext.effective_n`): the per-device shard is just another
  small-n shape band, which is exactly the regime where the paper's
  matmul-form reduction/scan wins. ``op_shard_axes`` declares which mesh
  axis shards each op's bucket axis.
* **shard_map dispatch** — ``repro.parallel.shard_ops`` wraps the kernel
  dispatch paths in ``shard_map`` over the context's mesh, keeping the
  tile kernels on per-shard shapes with a psum/carry combine.
* **step builders / serving** — ``make_train_step`` /
  ``make_block_serve_step`` / ``ServingEngine`` activate the context at
  trace time so logical sharding constraints and shard-shape resolution
  both see it.

Activation is scoped (``with ctx:``): it enters the jax mesh (so bare
``PartitionSpec`` constraints resolve), installs the rule table
(``sharding.use_rules``), and publishes the context through a contextvar
(:func:`current_mesh_context`). Inside a ``shard_map`` body shapes are
already per-shard; :func:`shard_local_scope` suppresses the divisor there
so shard shapes are never divided twice.

``mesh=None`` builds a *topology-only* context (axis sizes from
``rules.axis_sizes``): policy resolution and unit tests work without
devices; anything needing a real mesh (shard_ops, constraints) is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.policy import KNOWN_OPS, OP_ALIASES
from repro.parallel import compat
from repro.parallel.sharding import Rules, spec_for, use_rules

_ACTIVE: contextvars.ContextVar["MeshContext | None"] = \
    contextvars.ContextVar("repro_mesh_context", default=None)
_LOCAL: contextvars.ContextVar[bool] = \
    contextvars.ContextVar("repro_mesh_context_local", default=False)


def current_mesh_context() -> "MeshContext | None":
    """The innermost active context (None outside any ``with ctx:``)."""
    return _ACTIVE.get()


@contextlib.contextmanager
def shard_local_scope():
    """Mark the dynamic extent as *already per-shard* (a ``shard_map``
    body): :func:`effective_call_n` stops dividing so a shard's n is never
    divided twice."""
    token = _LOCAL.set(True)
    try:
        yield
    finally:
        _LOCAL.reset(token)


def effective_call_n(op: str, n: int) -> int:
    """The bucket-axis size kernel resolution should key off for one call:
    the per-shard size under an active (non-local) MeshContext, else ``n``
    unchanged. This is the hook :meth:`KernelPolicy.resolve` calls."""
    ctx = _ACTIVE.get()
    if ctx is None or _LOCAL.get():
        return n
    return ctx.effective_n(op, n)


def parse_mesh_arg(spec: str) -> tuple[tuple[str, int], ...]:
    """Parse a ``--mesh``-style string: ``"data=2,model=2"`` ->
    ``(("data", 2), ("model", 2))`` (order preserved = mesh axis order)."""
    axes = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size = part.partition("=")
        if not sep or not name.strip():
            raise ValueError(
                f"mesh spec must be 'axis=size,...', got {spec!r}")
        axes.append((name.strip(), int(size)))
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    for name, size in axes:
        if size < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {size}")
    return tuple(axes)


# The union logical-name table the smoke/launch paths share (the
# production tables in launch/mesh.py refine it per mesh shape).
DEFAULT_RULE_TABLE = {
    "batch": ("data",), "heads": "model", "kv_heads": "model",
    "ff": "model", "e_ff": "model", "experts": "model",
    "vocab": "model", "inner": "model", "inner_all": "model",
    "ssm_heads": "model", "embed": None, "layers": None,
    "moe_groups": ("data",), "exp_slots": "model",
    "exp_cap": None, "kv_seq": None,
}


@dataclasses.dataclass(frozen=True, eq=False)
class MeshContext:
    """Mesh + rules + process topology, activated with ``with ctx:``.

    ``mesh``
        The device mesh (or None for a topology-only context — policy
        resolution still works off ``rules.axis_sizes``).
    ``rules``
        The logical-name sharding table (divisibility-degrading, see
        ``parallel.sharding``).
    ``op_shard_axes``
        Which mesh axis shards each op's *bucket* axis (the last axis for
        the reduce/scan family, the sequence axis for attention/ssd) — a
        mapping or tuple of ``(op, axis)`` pairs, validated against
        ``KNOWN_OPS`` and the mesh axis names. Drives
        :meth:`effective_n`, hence shard-shape kernel resolution.

    Identity-hashed (``eq=False``) so it can key caches directly; use
    :meth:`key` for a value-based cache key.
    """

    mesh: jax.sharding.Mesh | None = None
    rules: Rules = dataclasses.field(default_factory=Rules)
    op_shard_axes: tuple = ()

    def __post_init__(self):
        pairs = self.op_shard_axes
        if isinstance(pairs, Mapping):
            pairs = pairs.items()
        norm = tuple(sorted(
            (OP_ALIASES.get(str(op), str(op)), str(ax)) for op, ax in pairs))
        for op, ax in norm:
            if op not in KNOWN_OPS:
                raise ValueError(
                    f"op_shard_axes: unknown op {op!r}; expected one of "
                    f"{KNOWN_OPS} (or a kernel-registry alias "
                    f"{tuple(OP_ALIASES)})")
            if ax not in self.axis_sizes_of(op_check=False):
                raise ValueError(
                    f"op_shard_axes[{op!r}]: unknown mesh axis {ax!r}; "
                    f"have {tuple(self.axis_sizes_of(op_check=False))}")
        object.__setattr__(self, "op_shard_axes", norm)

    # -- topology -----------------------------------------------------------

    def axis_sizes_of(self, *, op_check: bool = True) -> dict[str, int]:
        if self.mesh is not None:
            return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return dict(self.rules.axis_sizes)

    @property
    def axis_sizes(self) -> dict[str, int]:
        return self.axis_sizes_of()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    def label(self) -> str:
        """Compact mesh-shape label for benchmark rows (``"data=2,model=2"``;
        ``"none"`` for a mesh-less context)."""
        sizes = self.axis_sizes
        return ",".join(f"{a}={s}" for a, s in sizes.items()) or "none"

    def key(self) -> tuple:
        """Value-based cache key (Rules holds dicts, so the dataclass
        itself is identity-hashed)."""
        return (tuple(sorted(self.axis_sizes.items())),
                tuple(sorted((k, v if not isinstance(v, list) else tuple(v))
                             for k, v in self.rules.table.items())),
                self.rules.fsdp, self.op_shard_axes)

    # -- shard-shape resolution ---------------------------------------------

    def shard_axis(self, op: str) -> str | None:
        op = OP_ALIASES.get(op, op)
        for name, ax in self.op_shard_axes:
            if name == op:
                return ax
        return None

    def shard_divisor(self, op: str, n: int) -> int:
        """The factor the op's bucket axis is sharded by: the registered
        axis size when it divides ``n``, else 1 (the same divisibility
        degradation as ``spec_for`` — a non-dividing rule replicates)."""
        ax = self.shard_axis(op)
        if ax is None:
            return 1
        size = self.axis_sizes.get(ax, 1)
        return size if size > 1 and n % size == 0 else 1

    def effective_n(self, op: str, n: int) -> int:
        return n // self.shard_divisor(op, n)

    # -- sharding helpers ---------------------------------------------------

    def spec_for(self, shape: Sequence[int],
                 logical: Sequence[str | None], *,
                 fsdp_ok: bool = False) -> P:
        return spec_for(shape, logical, rules=self.rules, fsdp_ok=fsdp_ok)

    def named_sharding(self, spec: P) -> NamedSharding:
        if self.mesh is None:
            raise ValueError("named_sharding needs a real mesh "
                             "(this context is topology-only)")
        return NamedSharding(self.mesh, spec)

    # -- activation ---------------------------------------------------------

    def __enter__(self) -> "MeshContext":
        stack = contextlib.ExitStack()
        stack.enter_context(use_rules(self.rules))
        if self.mesh is not None:
            stack.enter_context(self.mesh)
        token = _ACTIVE.set(self)
        stack.callback(_ACTIVE.reset, token)
        object.__setattr__(self, "_stack", stack)
        return self

    def __exit__(self, *exc) -> None:
        stack = getattr(self, "_stack", None)
        object.__setattr__(self, "_stack", None)
        if stack is not None:
            stack.close()


@contextlib.contextmanager
def activate(ctx: "MeshContext | None"):
    """``with activate(ctx):`` — like ``with ctx:`` but a no-op for None
    (step builders thread an optional context through)."""
    if ctx is None:
        yield None
    else:
        with ctx:
            yield ctx


def make_context(
    mesh_spec: "str | Sequence[tuple[str, int]]",
    *,
    table: Mapping | None = None,
    fsdp: bool | None = None,
    op_shard_axes: "Mapping | tuple" = (),
) -> MeshContext:
    """Build a MeshContext from a mesh spec (``"data=2,model=2"`` or parsed
    pairs) over this process's global device set.

    The mesh is built through ``compat.make_mesh`` (the one sanctioned
    ``jax.make_mesh`` call site); axis sizes must multiply to the global
    device count. ``table`` defaults to :data:`DEFAULT_RULE_TABLE`;
    ``fsdp`` defaults to sharding over ``data`` when that axis is > 1.
    """
    axes = parse_mesh_arg(mesh_spec) if isinstance(mesh_spec, str) \
        else tuple(mesh_spec)
    names = tuple(a for a, _ in axes)
    shape = tuple(s for _, s in axes)
    total = 1
    for s in shape:
        total *= s
    ndev = jax.device_count()
    if total != ndev:
        raise ValueError(
            f"mesh {dict(axes)} needs {total} devices; this process group "
            f"has {ndev}")
    mesh = compat.make_mesh(shape, names)
    sizes = dict(axes)
    if fsdp is None:
        fsdp = sizes.get("data", 1) > 1
    rules = Rules(table=dict(table if table is not None
                             else DEFAULT_RULE_TABLE),
                  fsdp="data" if fsdp and sizes.get("data", 1) > 1 else None,
                  axis_sizes=sizes)
    return MeshContext(mesh=mesh, rules=rules, op_shard_axes=op_shard_axes)
