"""Programs built per ``repro.ops`` call in the window: the program's
``repro_compile_events_total{stage="lower"}`` of every op but ``other``
over ``repro_ops_calls_total``. A lowering precedes every backend compile
and every persistent-cache fetch, so this counts both. Silent where the
program keeps no such counters."""


def read(run):
    obs = run.record.get("obs") or {}
    events = obs.get("repro_compile_events_total")
    calls = obs.get("repro_ops_calls_total")
    if events is None or calls is None:
        return None
    n = sum(s["value"] for s in calls["series"])
    lowered = sum(s["value"] for s in events["series"]
                  if s["labels"].get("stage") == "lower"
                  and s["labels"].get("op") != "other")
    return lowered / n if n else None
