"""Multi-backend dispatch layer for every Pallas kernel in the repo.

This module is the single place that knows which accelerator is active
and how Pallas compiler params are spelled. Kernels call
:func:`compiler_params` instead of touching ``pltpu``/``plgpu`` classes,
and the public wrappers register with :func:`register_op` so every call
site picks its execution path through one switch:

  ``fused``      the XLA reference path (``repro.kernels.ref`` /
                 ``repro.core``) — default off-accelerator
  ``tile``       the explicit Pallas tile kernel for *this host's* backend:
                 resolves to ``tile_tpu`` on TPU, ``tile_gpu`` on GPU
                 (Pallas-Triton), and downgrades to ``interpret`` elsewhere
                 with a one-time warning (there is nothing to compile for)
  ``tile_tpu``   force the Pallas-TPU kernel — raises off-TPU
  ``tile_gpu``   force the Pallas-Triton kernel — raises off-GPU
  ``tile_logdepth``  the log-depth MatMulScan contender (scan family):
                 the host backend's carry-free local block kernels + an
                 O(log) XLA tree combine; off-accelerator the local
                 kernels run through the interpreter (the label survives)
  ``interpret``  the Pallas kernel body through the interpreter — how the
                 kernels are validated on CPU
  ``auto``       ``tile`` on TPU/GPU, ``fused`` otherwise

Which path runs is decided by the active :class:`repro.core.policy.
KernelPolicy` — the single resolution algorithm for the whole repo. This
module keeps the registry, the capability probes, and the compiler-params
builder; selection state (path, per-op overrides, backend preference,
autotune mode, env-var parsing) lives entirely in ``repro.core.policy``.
Precedence: per-call ``path=`` kwarg > per-call legacy ``use_pallas=``
bool > per-call / active ``policy`` (whose process default is built from
``REPRO_KERNEL_PATH`` and friends) > ``auto``. Passing both ``path=`` and
``use_pallas=`` with conflicting values warns and honours ``path=``.
``auto`` consults the measured per-shape crossover table in
``repro.core.autotune`` (keyed by backend — a GPU-measured table never
steers a CPU/TPU host) when the call shape is known, falling back to the
static choice (tile on TPU/GPU, fused elsewhere) otherwise or when the
policy disables autotuning. ``auto`` never selects a ``tile_*`` label the
host cannot lower natively.

Selection also carries *tuning*: the resolution result is a
``ResolvedPath`` whose ``.tuning`` is the per-op
:class:`~repro.core.policy.TuneSpec` (layout defaults < autotune table's
swept winner < policy ``op_tuning``); :func:`pallas_op` hands it to the
tile entries as ``tuning=`` so every kernel's block/chunk/warp geometry
is data, not constants.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax

from repro.obs import runtime as _obs

# the env var's *name*; it is parsed only by repro.core.policy
ENV_PATH = "REPRO_KERNEL_PATH"
PATHS = ("auto", "fused", "tile", "tile_tpu", "tile_gpu", "tile_logdepth",
         "interpret")


# ---------------------------------------------------------------------------
# capability probes


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def on_gpu() -> bool:
    """True when the default JAX backend is a GPU (CUDA or ROCm)."""
    return jax.default_backend() in ("gpu", "cuda", "rocm")


def has_pallas_tpu() -> bool:
    """True when this JAX ships the Pallas-TPU lowering at all."""
    try:
        from jax.experimental.pallas import tpu as _  # noqa: F401
        return True
    except ImportError:
        return False


def has_pallas_triton() -> bool:
    """True when this JAX ships the Pallas-Triton (GPU) lowering at all."""
    try:
        from repro.kernels.triton import compat
    except ImportError:  # pragma: no cover — broken install
        return False
    return compat.available()


def native_tile_backend() -> str | None:
    """The concrete tile path this host lowers natively, or None."""
    if on_tpu() and has_pallas_tpu():
        return "tile_tpu"
    if on_gpu() and has_pallas_triton():
        return "tile_gpu"
    return None


# ---------------------------------------------------------------------------
# compiler params


def compiler_params(backend: str = "tpu", **kwargs: Any):
    """Pallas compiler params for either backend.

    ``backend="tpu"`` (default) builds ``pltpu.CompilerParams``;
    ``backend="gpu"`` defers to the Triton module
    ``repro.kernels.triton.compat`` (the only module allowed to import
    ``jax.experimental.pallas.triton``). An unknown field raises.
    """
    if backend in ("gpu", "triton"):
        from repro.kernels.triton import compat

        return compat.compiler_params(**kwargs)
    if backend != "tpu":
        raise ValueError(
            f"unknown compiler-params backend {backend!r}; "
            "expected 'tpu' or 'gpu'")
    from jax.experimental.pallas import tpu as pltpu

    if kwargs.get("dimension_semantics"):
        kwargs["dimension_semantics"] = tuple(kwargs["dimension_semantics"])
    return pltpu.CompilerParams(**kwargs)


# ---------------------------------------------------------------------------
# path resolution — repro.core.policy owns the one resolve implementation
# in the repo; this module only folds the legacy use_pallas bool into a
# label before handing the call to it


def _merge_use_pallas(path: str | None,
                      use_pallas: bool | None) -> str | None:
    """Fold the legacy ``use_pallas`` bool into an explicit path label.

    True → ``tile``, False → ``fused``, None → unspecified. When both
    ``path=`` and ``use_pallas=`` are passed with conflicting values,
    ``path=`` wins and a ``UserWarning`` is emitted (``path='interpret'``
    with ``use_pallas=True`` is *not* a conflict — interpret runs the same
    kernel body).
    """
    if use_pallas is None:
        return path
    implied = "tile" if use_pallas else "fused"
    if path is None:
        return implied
    if (use_pallas and path == "fused") or \
            (not use_pallas and path in ("tile", "tile_tpu", "tile_gpu",
                                         "interpret")):
        warnings.warn(
            f"conflicting path={path!r} and use_pallas={use_pallas}; "
            "path= takes precedence (use_pallas= is legacy)",
            UserWarning, stacklevel=4)
    return path


# ---------------------------------------------------------------------------
# op registry — the single pallas_call front door


@dataclasses.dataclass(frozen=True)
class PallasOp:
    """One kernel family: the Pallas tile entries per backend (each must
    accept ``interpret=`` and — when the family has tuning knobs —
    ``tuning=`` kwargs) and the fused-XLA reference twin.

    ``tile`` is the Pallas-TPU entry (also the body the ``interpret`` path
    runs); ``tile_gpu`` the Pallas-Triton twin, or None while a family has
    no GPU kernel yet. ``tile_logdepth``/``tile_logdepth_gpu`` are the
    log-depth MatMulScan contenders per backend (scan family only; None
    elsewhere) — each must accept ``interpret=`` like the linear entries,
    which is how the label survives off-accelerator with interpreted
    local kernels. ``fits`` is the shape precondition of the ``tile``
    entry (None: every shape fits) — called with the entry's arguments
    and ``tuning=``; a call that does not fit runs the ``fused`` oracle
    (see :func:`pallas_op`). ``knobs`` declares the family's tuning-knob
    schema (from ``repro.core.policy.KNOB_SCHEMA``, keyed by the canonical op
    name); the default and sweep-candidate knob *values* live in
    ``repro.kernels.layout`` and are exposed here per backend so autotune
    and callers interrogate the registry, not the kernel files.
    """

    name: str
    tile: Callable[..., Any]
    fused: Callable[..., Any]
    tile_gpu: Callable[..., Any] | None = None
    tile_logdepth: Callable[..., Any] | None = None
    tile_logdepth_gpu: Callable[..., Any] | None = None
    fits: Callable[..., bool] | None = None
    knobs: tuple = ()

    def _canonical(self) -> str:
        from repro.core import policy as kpolicy

        return kpolicy.OP_ALIASES.get(self.name, self.name)

    def default_tuning(self, backend: str = "tpu") -> dict:
        """Default knob values for this family on ``backend``."""
        from repro.kernels import layout

        return layout.default_tuning(backend, self._canonical())

    def candidate_tuning(self, backend: str = "tpu") -> list[dict]:
        """The candidate specs the autotune sweep times for this family."""
        from repro.kernels import layout

        return layout.candidate_tuning(backend, self._canonical())


_REGISTRY: dict[str, PallasOp] = {}


def register_op(name: str, *, tile: Callable[..., Any],
                fused: Callable[..., Any],
                tile_gpu: Callable[..., Any] | None = None,
                tile_logdepth: Callable[..., Any] | None = None,
                tile_logdepth_gpu: Callable[..., Any] | None = None,
                fits: Callable[..., bool] | None = None
                ) -> PallasOp:
    from repro.core import policy as kpolicy  # deferred: avoids a cycle

    canon = kpolicy.OP_ALIASES.get(name, name)
    op = PallasOp(name=name, tile=tile, fused=fused, tile_gpu=tile_gpu,
                  tile_logdepth=tile_logdepth,
                  tile_logdepth_gpu=tile_logdepth_gpu, fits=fits,
                  knobs=tuple(kpolicy.KNOB_SCHEMA.get(canon, ())))
    _REGISTRY[name] = op
    return op


def get_op(name: str) -> PallasOp:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no Pallas op {name!r} registered; known: {available_ops()}"
        ) from None


def available_ops() -> list[str]:
    return sorted(_REGISTRY)


def _call_shape(name: str, args: tuple) -> tuple:
    """The (size, dtype) the autotune table buckets ``name`` by, extracted
    from the call's first array argument — the same quantity the dispatch
    layer passes for its level (reduction family: trailing segment size;
    rmsnorm: feature dim; attention: query length, kernel layout
    (B, H, L, D); ssd_scan: sequence length, (B, L, H, P)). Returns
    (None, None) when no shape context is extractable — resolution then
    stays static and table tuning keeps the layout defaults.
    """
    a = next((x for x in args
              if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1), None)
    if a is None:
        return None, None
    if name in ("segmented_reduce", "segmented_scan", "weighted_scan",
                "rmsnorm"):
        return a.shape[-1], a.dtype
    if name == "attention" and a.ndim >= 3:
        return a.shape[2], a.dtype
    if name == "ssd_scan" and a.ndim >= 2:
        return a.shape[1], a.dtype
    return None, None


def _emit_invoke(name: str, n, dt, p, path: str) -> None:
    """One ``kernel_invoke`` event + counter per registry execution (only
    called when an obs session is active). ``path`` is what ran: the
    resolved label, or ``"oracle"`` for a shape the kernel does not fit."""
    sess = _obs.ACTIVE
    if sess is None:
        return
    from repro.core import autotune  # deferred: imports us

    tuning = getattr(p, "tuning", None)
    sess.emit("kernel_invoke", op=name,
              n=(int(n) if n is not None else None),
              dtype=(autotune.dtype_tag(dt) if dt is not None else None),
              path=path,
              tuning=(tuning.as_dict() if tuning is not None else None))
    sess.counter(
        "repro_kernel_invocations_total",
        "kernel-registry executions by op/path").inc(op=name, path=path)


def _oracle_fallback(name: str, p, mode: str) -> None:
    """A ``tile``/``interpret`` call whose shape the kernel does not fit
    runs the fused oracle instead: warn once per op, or raise under
    ``interpret_fallback="error"`` (a silent oracle would pass for the
    kernel in a measurement)."""
    msg = (f"{name}: the call's shape does not fit the Pallas kernel's "
           f"blocks; path={str(p)!r} runs the fused oracle instead")
    if mode == "error":
        raise RuntimeError(
            f"{msg}, which this policy's interpret_fallback='error' forbids")
    if mode == "warn":
        from repro.core import policy as kpolicy

        kpolicy.warn_once(f"oracle:{name}", msg, UserWarning, stacklevel=4)


def pallas_op(name: str, *args: Any, policy: Any = None,
              path: str | None = None,
              use_pallas: bool | None = None, **kwargs: Any) -> Any:
    """Run a registered op through the policy switch (see module
    docstring).

    ``policy`` is a :class:`repro.core.policy.KernelPolicy` (or string
    shorthand; None = the active policy); ``path``/``use_pallas`` are the
    per-call legacy spellings and beat the policy. Every family extracts
    its bucket size from the call (see :func:`_call_shape`), enabling
    shape-aware ``auto`` AND shape-bucketed table tuning. The resolved
    :class:`~repro.core.policy.TuneSpec` rides the resolution result and
    is handed to the tile entries as ``tuning=`` (families that declare
    knobs); the fused XLA twin has no geometry and never sees it.
    """
    from repro.core import policy as kpolicy

    op = get_op(name)
    n, dt = _call_shape(name, args)
    path = _merge_use_pallas(path, use_pallas)
    pol = kpolicy.as_policy(policy)
    p = pol.resolve(op=name, n=n, dtype=dt, level="kernel", explicit=path)
    ran = str(p)
    if p in ("tile_tpu", "interpret") and op.fits is not None \
            and not op.fits(*args, tuning=getattr(p, "tuning", None),
                            **kwargs):
        _oracle_fallback(name, p, pol.interpret_fallback)
        ran = "oracle"
    if _obs.ACTIVE is not None:   # off by default; one global load
        _emit_invoke(name, n, dt, p, ran)
    if p == "fused" or ran == "oracle":
        return op.fused(*args, **kwargs)
    if op.knobs:
        kwargs["tuning"] = getattr(p, "tuning", None)
    if p == "tile_gpu":
        if op.tile_gpu is None:
            raise RuntimeError(
                f"{name}: no Pallas-Triton (GPU) kernel registered for this "
                "op; use path='tile_tpu', 'interpret', or 'fused'")
        return op.tile_gpu(*args, interpret=False, **kwargs)
    if p == "tile_logdepth":
        native = native_tile_backend()
        fn = op.tile_logdepth_gpu if native == "tile_gpu" \
            else op.tile_logdepth
        if fn is None:
            raise RuntimeError(
                f"{name}: no log-depth MatMulScan kernel registered for "
                "this op (tile_logdepth covers the scan family: scan, "
                "weighted_scan, ssd); use path='tile' or 'fused'")
        # off-accelerator the local block kernels run interpreted; the
        # tree combine is plain XLA either way
        return fn(*args, interpret=(native is None), **kwargs)
    return op.tile(*args, interpret=(p == "interpret"), **kwargs)
