"""Share of the window JAX spent tracing, lowering and compiling (or
fetching from its persistent cache) for ``repro.ops`` calls: the program's
``repro_compile_seconds_total`` of the stages ``trace``, ``lower`` and
``compile`` (``cache_fetch`` is a part of ``compile``), every op but
``other``, over the window's seconds, in percent. Silent where the program
keeps no such counter."""

STAGES = ("trace", "lower", "compile")


def read(run):
    metric = (run.record.get("obs") or {}).get("repro_compile_seconds_total")
    if metric is None or run.window_s <= 0:
        return None
    seconds = sum(s["value"] for s in metric["series"]
                  if s["labels"].get("stage") in STAGES
                  and s["labels"].get("op") != "other")
    return 100.0 * seconds / run.window_s
