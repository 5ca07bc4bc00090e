"""Profiling hooks: ``jax.profiler`` traces behind one CLI flag.

``--profile-dir <dir>`` on the train/serve/bench CLIs wraps the run in
:func:`tracing`, which starts a ``jax.profiler`` trace into the directory
(viewable with TensorBoard / Perfetto). Hot sections inside the run are
annotated with :func:`span`, which is a no-op unless spans are enabled —
the annotations therefore cost nothing in normal operation, same contract
as the metrics layer. A caller that starts the profiler itself turns the
spans on with :func:`spans_enabled`.

Spans are ``jax.profiler.TraceAnnotation`` host events, so they sit on the
trace's own clock: nanoseconds since the trace began. Events of
``repro.obs.events`` carry ``ts`` in wall-clock seconds; the trace's
``Task Environment`` plane holds ``profile_start_time`` (wall-clock
nanoseconds), and ``ts * 1e9 - profile_start_time`` places an event on
the trace's host clock.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

# True while spans record (a trace started by tracing(), or a caller's
# own inside spans_enabled()); span() and the repro.ops wrapper guard on
# it so annotations stay free when not profiling.
_TRACING = False


@contextlib.contextmanager
def spans_enabled() -> Iterator[None]:
    """Record :func:`span` annotations (and the ``ops/<op>`` and
    ``shard_ops/<op>`` spans) inside the block, for a caller that runs
    the ``jax.profiler`` trace itself."""
    global _TRACING
    prev, _TRACING = _TRACING, True
    try:
        yield
    finally:
        _TRACING = prev


@contextlib.contextmanager
def tracing(profile_dir: str | None) -> Iterator[None]:
    """Trace the enclosed block into ``profile_dir`` (no-op when None),
    with the repo's spans on. The profiler's Python call tracing stays
    off (``python_tracer_level=0``): every Python call as an event would
    swamp the host spans."""
    if not profile_dir:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    try:
        with spans_enabled():
            yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def span(name: str, **stats) -> Iterator[None]:
    """Named trace annotation around a hot section (serving block step,
    train step, checkpoint snapshot), with optional integer/string
    ``stats`` on the event. No-op unless spans are enabled, so call sites
    can annotate unconditionally."""
    if not _TRACING:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(str(name), **stats):
        yield
