"""The active observability session — ``repro.obs``'s ``get_policy``.

Observability is **off by default** and scoped-enable, mirroring the
kernel-policy layer's ``using_policy``: nothing in the repo records a
metric or emits an event unless a session is active, and the hot-path
check is a single module-global load (``runtime.ACTIVE is not None``) so
the disabled path adds no measurable work to ``KernelPolicy.resolve()``
or the serving tick loop.

Unlike the policy layer, the active session is a *process* global, not a
context-var: the instrumented subsystems span threads the enabling frame
never sees (the serving engine's caller, the ``AsyncCheckpointer``'s
background writer, jit tracing), and a per-context session would silently
lose exactly those records. ``using_obs`` still nests — it saves and
restores the previous session — it just isn't thread-local.

While a session is active it also holds one ``jax.monitoring`` listener,
which counts JAX's trace, lowering, backend-compile and persistent-cache
events by the observed call that caused them (:func:`begin_call` marks
the innermost call on each thread; ``repro.ops`` marks each op call);
nothing is registered with JAX while observability is off.

Typical use::

    from repro import obs

    with obs.using_obs(events_path="events.jsonl") as sess:
        engine.run(requests)
        print(sess.metrics.prometheus_text())
        for e in sess.events.events("resolution"):
            print(obs.format_resolution(e))
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
from typing import Iterator

from repro.obs.events import DEFAULT_RING, EventSink
from repro.obs.metrics import MetricsRegistry

# THE hot-path flag: instrumented call sites guard on ``ACTIVE is not
# None`` before doing any observability work. Assigned only under _LOCK.
ACTIVE: "ObsSession | None" = None

_LOCK = threading.Lock()

# JAX monitoring events -> the ``stage`` label of the compile counters.
# JAX records backend_compile_duration around a persistent-cache fetch as
# well, so ``compile`` covers compiling or fetching and ``cache_fetch`` is
# the part of it spent reading the cache.
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}


class ObsSession:
    """One observability scope: a metrics registry + an event sink.

    ``events_path`` tees every event to a JSON-lines file; ``ring`` caps
    the in-memory event history; ``profile_dir`` is carried for the
    profiling hooks (``repro.obs.profiling``) so one flag threads through
    the CLIs.
    """

    def __init__(self, *, events_path: str | None = None,
                 ring: int = DEFAULT_RING,
                 profile_dir: str | None = None):
        self.metrics = MetricsRegistry()
        self.events = EventSink(ring=ring, jsonl_path=events_path)
        self.profile_dir = profile_dir

    # -- convenience passthroughs ------------------------------------------

    def emit(self, kind: str, **fields) -> dict:
        return self.events.emit(kind, **fields)

    def counter(self, name: str, help: str = ""):
        return self.metrics.counter(name, help)

    def gauge(self, name: str, help: str = ""):
        return self.metrics.gauge(name, help)

    def histogram(self, name: str, help: str = "", **kw):
        return self.metrics.histogram(name, help, **kw)

    def prometheus_text(self) -> str:
        return self.metrics.prometheus_text()

    def write_prometheus(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.metrics.prometheus_text())

    def close(self) -> None:
        self.events.close()


def active() -> ObsSession | None:
    """The active session, or None (the default: observability off)."""
    return ACTIVE


def _swap(sess: ObsSession | None) -> ObsSession | None:
    """Make ``sess`` the active session and return the previous one; the
    compile listener is registered exactly while some session is active,
    and the session holds its compile counters from the start, so a scope
    that compiles nothing reads 0."""
    global ACTIVE
    import jax

    if sess is not None:
        _compile_counters(sess)
    with _LOCK:
        prev, ACTIVE = ACTIVE, sess
        if prev is None and sess is not None:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event)
        elif prev is not None and sess is None:
            jax.monitoring.unregister_event_duration_listener(
                _on_compile_event)
    return prev


def enable(session: ObsSession | None = None, **kw) -> ObsSession:
    """Install ``session`` (or a fresh one built from ``kw``) as the
    active session and return it. Prefer the scoped :func:`using_obs`
    unless the session should outlive the frame."""
    sess = session if session is not None else ObsSession(**kw)
    _swap(sess)
    return sess


def disable() -> None:
    """Deactivate observability (the active session, if any, is left
    intact for post-hoc reads — only emission stops)."""
    _swap(None)


@contextlib.contextmanager
def using_obs(session: ObsSession | None = None,
              **kw) -> Iterator[ObsSession]:
    """Scoped observability: activate a session, restore the previous one
    (usually None) on exit. The session's JSON-lines file, if any, is
    closed on exit; its in-memory metrics/events stay readable."""
    sess = session if session is not None else ObsSession(**kw)
    prev = _swap(sess)
    try:
        yield sess
    finally:
        _swap(prev)
        if session is None:       # we own the sink: release the file
            sess.close()


def emit(kind: str, **fields) -> dict | None:
    """Emit one event into the active session (no-op when disabled)."""
    sess = ACTIVE
    return None if sess is None else sess.emit(kind, **fields)


# ---------------------------------------------------------------------------
# Observed calls: one identifier per call, shared by its spans and events

_CALL_IDS = itertools.count(1)
_LOCAL = threading.local()        # .call: innermost observed Call here


@dataclasses.dataclass
class Call:
    """One observed call: ``name`` labels the compile events it causes,
    ``id`` is its per-process sequence number, ``outer`` the call it runs
    inside on this thread."""
    name: str
    id: int = dataclasses.field(default_factory=lambda: next(_CALL_IDS))
    outer: "Call | None" = None


def begin_call(call: Call) -> None:
    """Make ``call`` the innermost observed call on this thread."""
    call.outer = getattr(_LOCAL, "call", None)
    _LOCAL.call = call


def end_call(call: Call) -> None:
    """Restore the call that ``call`` ran inside."""
    _LOCAL.call = call.outer


def current_call() -> Call | None:
    """The innermost observed call on this thread."""
    return getattr(_LOCAL, "call", None)


def current_call_id() -> int | None:
    c = getattr(_LOCAL, "call", None)
    return None if c is None else c.id


def _compile_counters(sess: ObsSession):
    """The session's ``repro_compile_events_total`` and
    ``repro_compile_seconds_total`` counters."""
    return (sess.counter("repro_compile_events_total",
                         "JAX traces, lowerings, backend compiles and "
                         "persistent-cache fetches by repro.ops op"),
            sess.counter("repro_compile_seconds_total",
                         "seconds of those events by repro.ops op"))


def _on_compile_event(event: str, duration: float, **_) -> None:
    """The session's ``jax.monitoring`` listener: JAX's compile events,
    by the innermost observed call on this thread (else ``other``)."""
    stage = COMPILE_STAGES.get(event)
    sess = ACTIVE
    if stage is None or sess is None:
        return
    c = getattr(_LOCAL, "call", None)
    op = "other" if c is None else c.name
    events, seconds = _compile_counters(sess)
    events.inc(op=op, stage=stage)
    seconds.inc(duration, op=op, stage=stage)
