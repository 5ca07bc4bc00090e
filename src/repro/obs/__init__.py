"""repro.obs — unified observability: metrics, tracing, profiling.

Off by default; scoped-enable mirrors ``using_policy``::

    from repro import obs

    with obs.using_obs(events_path="events.jsonl") as sess:
        ...                       # kernels/serving/training record here
        print(sess.prometheus_text())

Submodules:

* :mod:`repro.obs.metrics` — counters/gauges/histograms + exporters.
* :mod:`repro.obs.cli` — the shared ``--obs-events`` / ``--metrics-out`` /
  ``--profile-dir`` flags for the launch and bench CLIs.
* :mod:`repro.obs.events` — bounded event ring + JSON-lines tee; the
  resolution-event schema (:data:`RESOLUTION_FIELDS`).
* :mod:`repro.obs.runtime` — the active-session machinery
  (``enable``/``disable``/``using_obs``/``active``).
* :mod:`repro.obs.profiling` — ``jax.profiler`` trace hooks behind
  ``--profile-dir``, spans on the trace's clock (``ops/<op>`` and
  ``shard_ops/<op>`` around every ``repro.ops`` call).
"""
from repro.obs.events import (DEFAULT_RING, RESOLUTION_FIELDS, EventSink,
                              format_resolution, load_jsonl)
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.profiling import span, spans_enabled, tracing
from repro.obs.runtime import (ObsSession, active, disable, emit, enable,
                               using_obs)

__all__ = [
    "DEFAULT_BUCKETS", "DEFAULT_RING", "RESOLUTION_FIELDS",
    "Counter", "EventSink", "Gauge", "Histogram", "MetricsRegistry",
    "ObsSession", "active", "disable", "emit", "enable",
    "format_resolution", "load_jsonl", "span", "spans_enabled", "tracing",
    "using_obs",
]
