"""`KernelPolicy` — the single home for kernel-selection state.

The paper's contribution is one idea (express reduction/scan as TCU
matmuls and pick the matmul form where it wins), but by PR 3 the *choice*
of formulation was smeared across four mechanisms: two overlapping
``resolve_path()`` functions (``repro.core.dispatch`` and
``repro.kernels.backend``), bare ``path=`` strings on every op,
``kernel_path`` fields duplicated on ``ModelConfig``/``OptConfig``/
``ServeConfig``, and ``REPRO_KERNEL_PATH``/``REPRO_AUTOTUNE*`` env vars
re-read at call sites. This module replaces all of that with one object:

* :class:`KernelPolicy` — a frozen, hashable dataclass capturing the full
  selection state: global ``path``, per-op overrides (``op_paths``), a
  ``backend`` preference, the ``autotune`` mode and table source, per-op
  tuning-knob overrides (``op_tuning``), and the off-accelerator
  ``interpret_fallback`` behaviour. Hashable means it can ride through
  ``jit`` static args and config dataclasses unchanged.
* :class:`TuneSpec` — the per-op kernel *geometry* (block/chunk shapes,
  GPU ``num_warps``/``num_stages``) as data instead of constants, each
  knob validated against :data:`KNOB_SCHEMA` the way ``op_paths``
  validates against :data:`KNOWN_OPS`.
* :meth:`KernelPolicy.resolve` — THE resolution algorithm; nothing else
  in the repo decides which formulation runs (the pre-policy
  ``resolve_path`` delegates are gone). It returns a :class:`ResolvedPath`
  — a plain ``str`` path label that also carries the resolved
  :class:`TuneSpec` (defaults from ``repro.kernels.layout``, overlaid by
  the autotune table's swept winner, overlaid by ``op_tuning``), so every
  kernel takes its geometry from the same resolution pass that picked it.
* A process-default policy built from the env vars — **this module is the
  only place that reads** ``REPRO_KERNEL_PATH`` / ``REPRO_AUTOTUNE`` /
  ``REPRO_AUTOTUNE_TABLE`` (a grep-guard test enforces it).
* :func:`get_policy` / :func:`set_policy` / :func:`using_policy` — a
  context-var based active policy, so overrides are scoped, thread-safe,
  and safe under ``jit`` tracing (the policy is read eagerly at trace
  time, never captured as a tracer).

The stable public surface for running ops under a policy is
:mod:`repro.ops`.

String shorthands (accepted everywhere a policy is):

* ``"fused"`` (any bare path label) — run exactly this path; per-call it
  overlays the active policy with ``path=<label>`` and clears per-op
  overrides.
* ``"attention=fused,reduce=tile"`` — per-op overrides (a bare label mixed
  in sets the global path: ``"baseline,attention=fused"``).
* ``'{"path": "auto", "autotune": "off"}'`` — JSON field overrides.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import os
import warnings
from typing import Any, Iterator, Mapping

from repro.obs import runtime as _obs

# The env vars (parsed ONLY here; other modules may re-export the names):
ENV_PATH = "REPRO_KERNEL_PATH"         # default path label
ENV_AUTOTUNE = "REPRO_AUTOTUNE"        # "off"/"0"/"static"/"false" -> off
ENV_TABLE = "REPRO_AUTOTUNE_TABLE"     # explicit autotune table file

# Path labels by level. "dispatch" admits the algorithm-level contenders
# the paper compares (xla_tile, baseline); "kernel" is the
# implementation-level subset the Pallas registry understands.
# "tile_logdepth" is the log-depth MatMulScan contender (scan-family only):
# backend-agnostic like "tile" — it runs the host's native local kernels
# plus an XLA tree combine, or the interpreter off-accelerator.
DISPATCH_PATHS = ("auto", "fused", "xla_tile", "tile", "tile_tpu",
                  "tile_gpu", "tile_logdepth", "interpret", "baseline")
KERNEL_PATHS = ("auto", "fused", "tile", "tile_tpu", "tile_gpu",
                "tile_logdepth", "interpret")
_DISPATCH_ONLY = ("xla_tile", "baseline")

BACKENDS = ("cpu", "gpu", "tpu")
AUTOTUNE_MODES = ("on", "off")
INTERPRET_FALLBACKS = ("warn", "silent", "error")

# Canonical (dispatch-level) op names a policy can carry overrides for;
# the kernel-registry spellings alias onto them so one override steers
# both layers. Unknown keys are rejected at construction — a typo'd
# override that silently no-ops is exactly the failure mode this
# subsystem exists to remove.
KNOWN_OPS = ("reduce", "scan", "weighted_scan", "ragged_reduce",
             "ragged_scan", "rmsnorm", "attention", "ssd")
OP_ALIASES = {"segmented_reduce": "reduce", "segmented_scan": "scan",
              "ssd_scan": "ssd"}

# Per-op tuning-knob schema: the only knob names a TuneSpec (and the
# ``tuning`` field of an autotune-table entry) may carry for each op.
# The knob *values* — per-backend defaults and sweep candidates — live in
# ``repro.kernels.layout`` (the one module allowed to spell out geometry
# numbers); this schema is the validation contract, owned by the policy
# layer the way KNOWN_OPS is. ``num_warps``/``num_stages`` are GPU-only
# at runtime (the TPU glue ignores them) but legal in any spec so one
# override string can serve both backends.
KNOB_SCHEMA = {
    "reduce": ("block_s", "block_n", "num_warps", "num_stages"),
    "scan": ("block_s", "block_n", "radix", "fan_in",
             "num_warps", "num_stages"),
    "weighted_scan": ("q", "radix", "fan_in", "num_warps", "num_stages"),
    "ragged_reduce": (),     # no Pallas kernel yet (XLA matmul form)
    "ragged_scan": (),
    "rmsnorm": ("row_block", "block_d", "num_warps", "num_stages"),
    "attention": ("block_q", "block_k", "num_warps", "num_stages"),
    "ssd": ("q", "radix", "fan_in", "num_warps", "num_stages"),
}


# ---------------------------------------------------------------------------
# one-time warnings (deprecation shims warn once per process, not per call)


_WARNED: set[str] = set()


def warn_once(key: str, message: str, category: type = DeprecationWarning,
              stacklevel: int = 3) -> None:
    """Emit ``message`` the first time ``key`` is seen this process."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(message, category, stacklevel=stacklevel + 1)


_TILE_DOWNGRADE_WARNED = False


def _warn_tile_downgrade() -> None:
    """One-time notice that the generic ``tile`` label fell back to the
    interpreter — silent interpreter execution looks like a hang at real
    sizes, so say so once per process."""
    global _TILE_DOWNGRADE_WARNED
    if _TILE_DOWNGRADE_WARNED:
        return
    _TILE_DOWNGRADE_WARNED = True
    import jax

    warnings.warn(
        f"path='tile' has no native Pallas lowering on the "
        f"{jax.default_backend()!r} backend (tile_tpu needs a TPU, tile_gpu "
        "a GPU with Pallas-Triton); running the kernel body through the "
        "Pallas interpreter instead. Pass path='interpret' explicitly to "
        "silence this one-time warning.",
        UserWarning, stacklevel=5)


_LOGDEPTH_DOWNGRADE_WARNED = False


def _warn_logdepth_downgrade() -> None:
    """One-time notice that ``tile_logdepth``'s local kernels will run
    through the interpreter (the label is kept — the log-depth algorithm
    still runs, only its Pallas block passes are interpreted)."""
    global _LOGDEPTH_DOWNGRADE_WARNED
    if _LOGDEPTH_DOWNGRADE_WARNED:
        return
    _LOGDEPTH_DOWNGRADE_WARNED = True
    import jax

    warnings.warn(
        f"path='tile_logdepth' has no native Pallas lowering on the "
        f"{jax.default_backend()!r} backend; the log-depth tree combine "
        "still runs as XLA matmuls but the local block kernels go through "
        "the Pallas interpreter. Set interpret_fallback='silent' to "
        "silence this one-time warning.",
        UserWarning, stacklevel=5)


def _shard_effective_n(op: str, n: int) -> int:
    """Per-shard bucket size under an active ``parallel.MeshContext``.

    Sharding is what makes the per-device problem small — a model-parallel
    shard of a reduce/scan call is just another small-n band, so the
    crossover table and TuneSpec must key off the shard's shape, not the
    global one. Deferred import: ``parallel`` imports this module.
    """
    try:
        from repro.parallel import mesh_context
    except ImportError:  # parallel package stripped from a minimal install
        return n
    return mesh_context.effective_call_n(op, n)


# ---------------------------------------------------------------------------
# tuning specs


@dataclasses.dataclass(frozen=True)
class TuneSpec:
    """Per-op kernel tuning geometry, frozen and hashable.

    Mirrors :class:`KernelPolicy`'s contract one level down: where the
    policy decides *which* formulation runs, a ``TuneSpec`` decides *how*
    it runs — block/chunk shapes and (on GPU) ``num_warps``/``num_stages``
    as data instead of constants baked into the kernel files.

    ``op``
        Canonical op name (any of :data:`KNOWN_OPS`; kernel-registry
        spellings like ``segmented_reduce`` alias onto them).
    ``knobs``
        The knob values — a mapping (or tuple of ``(knob, value)`` pairs;
        normalised to a sorted tuple so the spec stays hashable and can
        ride through ``jit`` static args). Every key is validated against
        :data:`KNOB_SCHEMA` and every value must be a positive int — a
        typo'd knob that silently no-ops is exactly the failure mode this
        subsystem exists to remove.

    Construction accepts the same spellings as a policy: a ``TuneSpec``,
    a mapping, or a string shorthand (``"q=64,num_warps=8"``) via
    :meth:`from_spec`. The per-backend *default* values live in
    ``repro.kernels.layout``; :meth:`KernelPolicy.tuning_for` merges
    defaults < autotune-table winner < policy ``op_tuning`` override into
    the spec every kernel consumes.
    """

    op: str
    knobs: tuple = ()

    def __post_init__(self):
        op = OP_ALIASES.get(str(self.op), str(self.op))
        object.__setattr__(self, "op", op)
        if op not in KNOWN_OPS:
            raise ValueError(
                f"TuneSpec: unknown op {op!r}; expected one of {KNOWN_OPS} "
                f"(or a kernel-registry alias {tuple(OP_ALIASES)})")
        pairs = self.knobs
        if isinstance(pairs, Mapping):
            pairs = pairs.items()
        allowed = KNOB_SCHEMA[op]
        norm = []
        for k, v in sorted((str(k), v) for k, v in pairs):
            if k not in allowed:
                raise ValueError(
                    f"TuneSpec({op!r}): unknown knob {k!r}; expected one "
                    f"of {allowed} — a typo here would silently no-op")
            if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
                raise ValueError(
                    f"TuneSpec({op!r}): knob {k!r} must be a positive "
                    f"int, got {v!r}")
            norm.append((k, v))
        object.__setattr__(self, "knobs", tuple(norm))

    @classmethod
    def from_spec(cls, op: str, spec: "TuneSpec | Mapping | str"
                  ) -> "TuneSpec":
        """Coerce a tuning spec for ``op``: a :class:`TuneSpec`, a mapping
        of knob values, or a ``"knob=value,knob=value"`` string."""
        if isinstance(spec, TuneSpec):
            if OP_ALIASES.get(str(op), str(op)) != spec.op:
                raise ValueError(
                    f"TuneSpec for op {spec.op!r} used under op {op!r}")
            return spec
        if isinstance(spec, Mapping):
            return cls(op=op, knobs=spec)
        if not isinstance(spec, str):
            raise TypeError(
                f"cannot build a TuneSpec from {type(spec).__name__}: "
                f"{spec!r}")
        knobs = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            k, sep, v = part.partition("=")
            if not sep:
                raise ValueError(
                    f"TuneSpec string must be 'knob=value,...', got "
                    f"{spec!r}")
            knobs[k.strip()] = int(v)
        return cls(op=op, knobs=knobs)

    def get(self, key: str, default=None):
        """The value of one knob, or ``default`` when the spec doesn't
        carry it (the kernel glue then falls back to the layout default)."""
        for k, v in self.knobs:
            if k == key:
                return v
        return default

    def as_dict(self) -> dict:
        return dict(self.knobs)

    def label(self) -> str:
        """Compact human-readable form for benchmark rows / sweep keys
        (``"block_n=64;block_s=32"``; ``"-"`` for an empty spec)."""
        return ";".join(f"{k}={v}" for k, v in self.knobs) or "-"


class ResolvedPath(str):
    """What :meth:`KernelPolicy.resolve` returns: a plain ``str`` path
    label (every existing comparison and dict key keeps working) that also
    carries the resolved :class:`TuneSpec` as ``.tuning`` (None when the
    call had no op context). ``pallas_op`` hands the spec to the tile
    kernels; the fused/baseline XLA forms ignore it."""

    __slots__ = ("tuning",)

    def __new__(cls, label: str, tuning: "TuneSpec | None" = None):
        self = str.__new__(cls, label)
        self.tuning = tuning
        return self


# ---------------------------------------------------------------------------
# the policy object


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Full kernel-selection state, frozen and hashable.

    ``path``
        Global path label (any of :data:`DISPATCH_PATHS`).
    ``op_paths``
        Per-op overrides that beat ``path`` — a mapping (or tuple of
        ``(op, path)`` pairs; normalised to a sorted tuple so the policy
        stays hashable), e.g. ``{"attention": "fused"}``.
    ``backend``
        Tile-backend preference: None (host-native), ``"tpu"``/``"gpu"``
        (the generic ``tile`` label forces that backend's kernel, raising
        off-host like the explicit ``tile_tpu``/``tile_gpu`` labels), or
        ``"cpu"`` (``tile`` runs the interpreter, silently — an explicit
        CPU choice is not a downgrade).
    ``autotune``
        ``"on"`` (shape-aware ``auto`` via the measured table / heuristic)
        or ``"off"`` (static ``auto``: tile on TPU/GPU, fused elsewhere).
    ``autotune_table``
        Explicit table file. None falls back to the checked-in default;
        a set-but-unusable table fails loudly (see ``repro.core.autotune``).
    ``op_tuning``
        Per-op :class:`TuneSpec` overrides — a mapping (or tuple of
        ``(op, spec)`` pairs; normalised to a sorted tuple of
        ``(op, TuneSpec)``) from op name to a spec, mapping, or
        ``"knob=value,..."`` string, e.g. ``{"ssd": {"q": 64}}``. These
        beat both the layout defaults and the autotune table's swept
        winner in :meth:`tuning_for`.
    ``interpret_fallback``
        What the generic ``tile`` does off-accelerator: ``"warn"`` (run the
        interpreter, warn once), ``"silent"``, or ``"error"``.
    """

    path: str = "auto"
    op_paths: tuple = ()
    backend: str | None = None
    autotune: str = "on"
    autotune_table: str | None = None
    op_tuning: tuple = ()
    interpret_fallback: str = "warn"

    def __post_init__(self):
        pairs = self.op_paths
        if isinstance(pairs, Mapping):
            pairs = pairs.items()
        pairs = tuple(sorted(
            (OP_ALIASES.get(str(op), str(op)), str(p)) for op, p in pairs))
        object.__setattr__(self, "op_paths", pairs)
        tune = self.op_tuning
        if isinstance(tune, Mapping):
            tune = tune.items()
        # merge entries that alias onto the same canonical op ("ssd" and
        # "ssd_scan" are one op): knobs combine, but a conflicting value
        # for the same knob is ambiguous and must raise — first-match
        # resolution would silently depend on insertion order
        merged: dict[str, dict] = {}
        for op_name, spec in tune:
            canon = OP_ALIASES.get(str(op_name), str(op_name))
            ts = TuneSpec.from_spec(str(op_name), spec)
            cur = merged.setdefault(canon, {})
            for k, v in ts.knobs:
                if k in cur and cur[k] != v:
                    raise ValueError(
                        f"op_tuning: conflicting values for "
                        f"{canon}.{k} ({cur[k]} vs {v}) — the op was "
                        "specified twice under aliased names")
                cur[k] = v
        tune = tuple(sorted(
            ((op, TuneSpec(op, kn)) for op, kn in merged.items()),
            key=lambda kv: kv[0]))
        object.__setattr__(self, "op_tuning", tune)
        if self.path not in DISPATCH_PATHS:
            raise ValueError(
                f"unknown path {self.path!r}; expected one of "
                f"{DISPATCH_PATHS}")
        for op, p in pairs:
            if op not in KNOWN_OPS:
                raise ValueError(
                    f"op_paths: unknown op {op!r}; expected one of "
                    f"{KNOWN_OPS} (or a kernel-registry alias "
                    f"{tuple(OP_ALIASES)}) — a typo here would silently "
                    "no-op")
            if p not in DISPATCH_PATHS:
                raise ValueError(
                    f"op_paths[{op!r}]: unknown path {p!r}; expected one "
                    f"of {DISPATCH_PATHS}")
        if self.backend is not None and self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of "
                f"{BACKENDS} or None")
        if self.autotune not in AUTOTUNE_MODES:
            raise ValueError(
                f"unknown autotune mode {self.autotune!r}; expected one of "
                f"{AUTOTUNE_MODES}")
        if self.interpret_fallback not in INTERPRET_FALLBACKS:
            raise ValueError(
                f"unknown interpret_fallback {self.interpret_fallback!r}; "
                f"expected one of {INTERPRET_FALLBACKS}")

    # -- construction -------------------------------------------------------

    @classmethod
    def from_spec(cls, spec: "KernelPolicy | Mapping | str",
                  base: "KernelPolicy | None" = None) -> "KernelPolicy":
        """Coerce a policy spec onto ``base`` (default: a fresh policy).

        Accepts a :class:`KernelPolicy` (returned as-is), a mapping of
        field overrides, or a string: a bare path label, an
        ``op=path,op=path`` shorthand (a bare label mixed in sets the
        global path; dotted keys are tuning-knob overrides —
        ``"tile,ssd.q=64"`` pins the global path AND the SSD chunk), or a
        JSON object of field overrides (which may include ``op_tuning``).
        """
        if isinstance(spec, KernelPolicy):
            return spec
        if base is None:
            base = cls()
        if isinstance(spec, Mapping):
            return dataclasses.replace(base, **dict(spec))
        if not isinstance(spec, str):
            raise TypeError(
                f"cannot build a KernelPolicy from {type(spec).__name__}: "
                f"{spec!r}")
        s = spec.strip()
        if s.startswith("{"):
            fields = json.loads(s)
            if not isinstance(fields, dict):
                raise ValueError(
                    f"policy JSON must be an object, got: {s!r}")
            return dataclasses.replace(base, **fields)
        if "=" in s:
            overrides = dict(base.op_paths)
            tuning = {op: spec.as_dict() for op, spec in base.op_tuning}
            path = base.path
            for part in s.split(","):
                part = part.strip()
                if not part:
                    continue
                if "=" in part:
                    key, _, val = part.partition("=")
                    key = key.strip()
                    if "." in key:      # op.knob=value tuning override
                        op, _, kn = key.partition(".")
                        op = OP_ALIASES.get(op.strip(), op.strip())
                        tuning.setdefault(op, {})[kn.strip()] = int(val)
                    else:
                        overrides[key] = val.strip()
                else:
                    path = part
            return dataclasses.replace(
                base, path=path, op_paths=tuple(overrides.items()),
                op_tuning=tuning)
        return dataclasses.replace(base, path=s, op_paths=())

    # -- resolution ---------------------------------------------------------

    def for_op(self, op: str | None) -> str:
        """The label this policy requests for ``op`` (override > global).

        Kernel-registry spellings alias onto the canonical op names, so
        an ``op_paths={"reduce": ...}`` override also steers a direct
        ``kernels.ops.segmented_reduce`` call.
        """
        if op is not None:
            op = OP_ALIASES.get(op, op)
            for name, p in self.op_paths:
                if name == op:
                    return p
        return self.path

    def tuning_for(self, op: str | None, n: int | None = None,
                   dtype: Any = None, *,
                   label: str | None = None) -> "TuneSpec | None":
        """The :class:`TuneSpec` this policy resolves for one call.

        Three layers, later wins: the per-backend defaults in
        ``repro.kernels.layout`` (keyed by the *kernel* backend the
        resolved ``label`` implies — ``tile_gpu`` reads the GPU defaults,
        everything else the TPU/interpret ones), the autotune table's
        swept winner for this call's shape bucket (v3 tables; gated by
        this policy's ``autotune``/``autotune_table`` like path
        resolution), and this policy's own ``op_tuning`` override. Knobs
        that tile the bucket axis itself are then clamped against ``n``
        (``layout.clamp_spec``), so the returned spec reports the
        geometry that actually runs — a ``q=64`` override on a TPU host
        comes back as the 128 the MXU-edge clamp will execute, never a
        phantom value (row-axis knobs clamp at the call site instead).
        Returns None for calls with no op context.
        """
        if op is None:
            return None
        op = OP_ALIASES.get(op, op)
        if op not in KNOWN_OPS:
            return None
        if not KNOB_SCHEMA[op]:
            return TuneSpec(op)
        from repro.kernels import layout  # deferred: avoids a cycle

        if label == "tile_gpu":
            bk = "gpu"
        elif label == "tile_logdepth":
            # backend-agnostic label: read the defaults of whichever
            # backend's local kernels will actually run
            from repro.kernels import backend as kb

            bk = "gpu" if kb.native_tile_backend() == "tile_gpu" else "tpu"
        else:
            bk = "tpu"
        knobs = layout.default_tuning(bk, op)
        if n is not None and self.autotune != "off":
            from repro.core import autotune  # deferred: imports us

            swept = autotune.tuning_entry(op, n, dtype, policy=self)
            if swept:
                knobs.update(swept)
        for name, spec in self.op_tuning:
            if name == op:
                knobs.update(spec.as_dict())
        # clamp the knobs that tile the bucket axis itself, so the spec
        # this method reports IS the geometry the glue will run (row-axis
        # knobs depend on batch shape and clamp at the call site)
        return TuneSpec(op, layout.clamp_spec(bk, op, knobs, n=n))

    def resolve(self, op: str | None = None, n: int | None = None,
                dtype: Any = None, *, level: str = "dispatch",
                explicit: str | None = None) -> "ResolvedPath":
        """Resolve one call to a concrete execution path.

        This is the repo's ONLY resolution algorithm (grep-guarded; the
        pre-policy ``resolve_path`` delegates were removed once every
        caller migrated).

        ``op``/``n``/``dtype`` describe the call shape: with them,
        ``auto`` consults the measured per-shape crossover table
        (``repro.core.autotune``, gated by this policy's ``autotune`` /
        ``autotune_table``) instead of the static backend check.

        ``level`` is ``"dispatch"`` (admits the algorithm-level
        ``xla_tile``/``baseline`` contenders) or ``"kernel"`` (the Pallas
        registry's subset; policy-sourced dispatch-only labels downgrade
        to their nearest kernel equivalent, ``fused``).

        ``explicit`` is a per-call label that beats everything in the
        policy (the ``path=`` kwarg); it is validated against ``level``'s
        label set.

        Returns a :class:`ResolvedPath`: a plain ``str`` label whose
        ``.tuning`` attribute carries the :class:`TuneSpec` resolved via
        :meth:`tuning_for` (None when ``op`` is unknown) — the tile
        kernels take their geometry from it.
        """
        n_raw = n
        if op is not None and n is not None:
            n = _shard_effective_n(op, n)
        label = self._resolve_label(op=op, n=n, dtype=dtype, level=level,
                                    explicit=explicit)
        resolved = ResolvedPath(
            label, self.tuning_for(op, n, dtype, label=label))
        if _obs.ACTIVE is not None:   # observability off by default: the
            # disabled path costs one module-global load and this branch
            self._emit_resolution(op=op, n_raw=n_raw, n=n, dtype=dtype,
                                  level=level, explicit=explicit,
                                  resolved=resolved)
        return resolved

    def _emit_resolution(self, *, op, n_raw, n, dtype, level, explicit,
                         resolved: "ResolvedPath") -> None:
        """Record one resolution into the active obs session (only called
        when a session is active): a ``resolution`` event carrying the
        dispatch-audit schema (``repro.obs.events.RESOLUTION_FIELDS``) and
        a ``repro_resolutions_total`` counter labelled by op/path/level."""
        sess = _obs.ACTIVE
        if sess is None:   # raced a disable(); nothing to record into
            return
        from repro.core import autotune  # deferred: imports us

        shaped = op is not None and n is not None
        requested = explicit if explicit is not None else self.for_op(op)
        if requested != "auto":
            table_src = "none"        # no table consultation happened
        elif not shaped or self.autotune == "off":
            table_src = "static"      # static backend check resolved auto
        else:
            entries = autotune.current_entries(self)
            if entries is not None and \
                    autotune.bucket_key(op, n, dtype) in entries:
                table_src = str(autotune.table_path(self))
            else:
                table_src = "heuristic"
        tuning = resolved.tuning.as_dict() \
            if resolved.tuning is not None else None
        sess.emit(
            "resolution",
            op=op, n=n_raw, shard_n=n,
            shard_divisor=(max(1, n_raw // n) if shaped and n else 1),
            dtype=autotune.dtype_tag(dtype) if shaped else None,
            backend=autotune.current_backend(),
            band=autotune.band(n) if shaped else None,
            level=level, explicit=explicit, chosen_path=str(resolved),
            tuning=tuning, table_src=table_src,
            call=_obs.current_call_id())
        sess.counter(
            "repro_resolutions_total",
            "KernelPolicy.resolve() calls by op/path/level").inc(
            op=str(op), path=str(resolved), level=str(level))

    def _resolve_label(self, op: str | None, n: int | None, dtype: Any,
                       level: str, explicit: str | None) -> str:
        from repro.kernels import backend as kb  # deferred: avoids a cycle

        valid = DISPATCH_PATHS if level == "dispatch" else KERNEL_PATHS
        if explicit is not None:
            if explicit not in valid:
                noun = "path" if level == "dispatch" else "kernel path"
                raise ValueError(
                    f"unknown {noun} {explicit!r}; expected one of {valid}")
            label = explicit
        else:
            label = self.for_op(op)
            if level == "kernel" and label in _DISPATCH_ONLY:
                # the env var / policy is process-wide, so kernel-level
                # call sites run the nearest kernel-level equivalent
                label = "fused"
        native = kb.native_tile_backend()
        if label == "auto":
            choice = None
            if op is not None and n is not None:
                from repro.core import autotune  # deferred: imports us

                if level == "kernel":
                    canon = OP_ALIASES.get(op, op)
                    choice = autotune.choose(
                        op, n, dtype,
                        candidates=("fused", "tile", "tile_tpu", "tile_gpu",
                                    "tile_logdepth", "interpret"),
                        level="kernel", policy=self,
                        use_heuristic=(canon
                                       not in autotune.FUSED_DEFAULT_OPS))
                else:
                    choice = autotune.choose(op, n, dtype, policy=self)
                # auto must never force a tile backend the host can't lower
                if choice in ("tile_tpu", "tile_gpu") and choice != native:
                    choice = None
            label = choice or ("tile" if native else "fused")
            if level == "kernel" and label in _DISPATCH_ONLY:
                label = "fused"
        if label in _DISPATCH_ONLY:
            return label
        if label == "tile":
            if self.backend == "cpu":
                return "interpret"   # explicit CPU preference, no downgrade
            if self.backend in ("gpu", "tpu"):
                label = f"tile_{self.backend}"   # strict checks below
            elif native is None:
                if self.interpret_fallback == "error":
                    import jax

                    raise RuntimeError(
                        "path='tile' has no native Pallas lowering on the "
                        f"{jax.default_backend()!r} backend and this "
                        "policy's interpret_fallback='error' forbids the "
                        "interpreter downgrade")
                if self.interpret_fallback == "warn":
                    _warn_tile_downgrade()
                return "interpret"   # nothing to compile the tile kernel for
            else:
                return native
        if label == "tile_logdepth":
            # backend-agnostic like "tile", but the label survives: the
            # log-depth algorithm still runs off-accelerator — only its
            # local Pallas block passes drop to the interpreter (decided
            # by the registry via native_tile_backend()).
            if native is None and self.backend != "cpu":
                if self.interpret_fallback == "error":
                    import jax

                    raise RuntimeError(
                        "path='tile_logdepth' has no native Pallas lowering "
                        f"on the {jax.default_backend()!r} backend and this "
                        "policy's interpret_fallback='error' forbids the "
                        "interpreter downgrade of its local block kernels")
                if self.interpret_fallback == "warn":
                    _warn_logdepth_downgrade()
            return label
        if label == "tile_tpu" and native != "tile_tpu":
            import jax

            raise RuntimeError(
                "path='tile_tpu' requires a TPU host with the Pallas-TPU "
                f"lowering (active backend: {jax.default_backend()!r}); use "
                "path='interpret' for CPU validation or path='tile' for "
                "backend-appropriate selection")
        if label == "tile_gpu" and native != "tile_gpu":
            import jax

            raise RuntimeError(
                "path='tile_gpu' requires a GPU host with the Pallas-Triton "
                f"lowering (active backend: {jax.default_backend()!r}); use "
                "path='interpret' for CPU validation or path='tile' for "
                "backend-appropriate selection")
        return label


# ---------------------------------------------------------------------------
# the process default (built from the env vars — the ONLY place they are
# read) and the context-var active policy


_DEFAULT_CACHE: dict[tuple, KernelPolicy] = {}


def default_policy() -> KernelPolicy:
    """The process-default policy, built from the env vars.

    Parsed once per distinct env-var state (memoised on the raw values, so
    tests that monkeypatch the environment see the change without a
    process restart — the *parsing* still has exactly one home).
    """
    raw = (os.environ.get(ENV_PATH, ""), os.environ.get(ENV_AUTOTUNE, ""),
           os.environ.get(ENV_TABLE, ""))
    if raw not in _DEFAULT_CACHE:
        mode = "off" if raw[1].strip().lower() in (
            "off", "0", "static", "false") else "on"
        table = raw[2].strip() or None
        pol = KernelPolicy(autotune=mode, autotune_table=table)
        spec = raw[0].strip()
        if spec:
            # full from_spec grammar: a bare path label, an
            # "op=path,op.knob=value" shorthand, or JSON field overrides
            # (JSON is case-sensitive; the simple forms stay lowercased)
            if not spec.startswith("{"):
                spec = spec.lower()
            pol = KernelPolicy.from_spec(spec, base=pol)
        _DEFAULT_CACHE[raw] = pol
    return _DEFAULT_CACHE[raw]


_ACTIVE: contextvars.ContextVar[KernelPolicy | None] = \
    contextvars.ContextVar("repro_kernel_policy", default=None)


def get_policy() -> KernelPolicy:
    """The active policy: the innermost override, else the env default."""
    pol = _ACTIVE.get()
    return pol if pol is not None else default_policy()


def set_policy(policy: "KernelPolicy | Mapping | str | None"
               ) -> contextvars.Token:
    """Install ``policy`` as the active policy (None restores the env
    default). Returns a token for :func:`reset_policy`; prefer the scoped
    :func:`using_policy` unless the override should outlive the frame."""
    pol = None if policy is None else \
        KernelPolicy.from_spec(policy, base=get_policy())
    return _ACTIVE.set(pol)


def reset_policy(token: contextvars.Token) -> None:
    """Undo a :func:`set_policy` (restores the previous active policy)."""
    _ACTIVE.reset(token)


@contextlib.contextmanager
def using_policy(policy: "KernelPolicy | Mapping | str | None"
                 ) -> Iterator[KernelPolicy]:
    """Scoped policy override; nests and restores on exit.

    Context-var based, so it is thread-safe and ``jit``-trace-safe (the
    policy is read eagerly at trace time).
    """
    token = set_policy(policy)
    try:
        yield get_policy()
    finally:
        reset_policy(token)


def coerce_config_policy(policy, kernel_path: str | None,
                         owner: str) -> KernelPolicy | None:
    """Shared ``__post_init__`` shim for configs that hold a policy.

    Folds the deprecated ``kernel_path=`` string (warns once, keyed by
    ``owner``) into ``policy`` and coerces strings/mappings absolutely
    via :meth:`KernelPolicy.from_spec` (a config is a durable artifact —
    it must not capture whatever policy happens to be active at
    construction time). Returns the coerced policy, or None (= defer to
    the active policy at call time).
    """
    if kernel_path is not None:
        warn_once(
            f"deprecated:{owner}.kernel_path",
            f"{owner}(kernel_path=...) is deprecated; pass policy= "
            "(a KernelPolicy or a path-label string)", stacklevel=5)
        if policy is None:
            policy = kernel_path
    if policy is not None and not isinstance(policy, KernelPolicy):
        policy = KernelPolicy.from_spec(policy)
    return policy


def policy_from_cli(policy_arg: str | None, kernel_path_arg: str | None,
                    warn_key: str,
                    tune_arg: str | None = None) -> KernelPolicy | None:
    """Shared ``--policy`` / ``--tune`` / deprecated ``--kernel-path``
    merge for CLIs.

    ``--kernel-path <label>`` warns once and acts as ``--policy <label>``
    unless ``--policy`` was also given. ``--tune "op.knob=value,..."``
    (e.g. ``--tune "ssd.q=64,attention.block_q=256"``) layers per-op
    tuning overrides on top of whatever policy the other flags produced.
    The spec is applied on top of the env-derived default policy (CLIs are
    process entry points — the env vars must keep steering whatever the
    flags don't override). Returns None when no flag was passed.
    """
    spec = policy_arg
    if kernel_path_arg is not None:
        warn_once(warn_key, "--kernel-path is deprecated; use --policy")
        spec = spec if spec is not None else kernel_path_arg
    if spec is None and tune_arg is None:
        return None
    pol = default_policy()
    if spec is not None:
        pol = KernelPolicy.from_spec(spec, base=pol)
    if tune_arg is not None:
        for part in tune_arg.split(","):
            part = part.strip()
            if part and "." not in part.split("=", 1)[0]:
                raise ValueError(
                    f"--tune expects op.knob=value pairs (e.g. "
                    f"'ssd.q=64'), got {part!r} — path overrides belong "
                    "in --policy")
        pol = KernelPolicy.from_spec(tune_arg, base=pol)
    return pol


def as_policy(policy: "KernelPolicy | Mapping | str | None" = None
              ) -> KernelPolicy:
    """Coerce a per-call ``policy=`` argument.

    None means the active policy; strings/mappings overlay it (a bare
    path label additionally clears per-op overrides — "run exactly this
    path"). Configs that persist a policy coerce absolutely via
    :meth:`KernelPolicy.from_spec` instead.
    """
    if policy is None:
        return get_policy()
    if isinstance(policy, KernelPolicy):
        return policy
    return KernelPolicy.from_spec(policy, base=get_policy())
