"""Share of the traced window the host spent inside ``repro.ops`` calls:
the union of the program's ``ops/<op>`` spans over the window, in percent.
Silent where the program has no such spans."""
from bench.lib import trace


def read(run):
    s = run.trace_summary
    if s is None or s.window_s <= 0:
        return None
    spans = [iv for name, ivs in s.spans.items() if name.startswith("ops/")
             for iv in ivs]
    if not spans:
        return None
    return 100.0 * trace.union_s(spans) / s.window_s
