"""Share of the roofline of the op programs (``repro.ops`` reduce/scan):
the least time of the calls in the trace (algorithmic bytes over peak HBM
bandwidth, or additions over peak FLOP/s where larger) over the device
time of those programs' executions, in percent."""
from bench.lib import yardstick


def read(run):
    s = run.trace_summary
    if s is None or run.peaks is None:
        return None
    least = device = 0.0
    for name, call in run.record["calls"].items():
        p = s.program("jit_" + name)
        least += p.count * yardstick.op_least_s(call["op"], call["rows"],
                                                call["seg"], run.peaks)
        device += p.device_s
    return 100.0 * least / device if device > 0 else None
