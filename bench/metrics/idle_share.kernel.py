"""Device idle share of the traced window: 1 - (union of device op
intervals) / traced window, in percent."""


def read(run):
    s = run.trace_summary
    if s is None or s.chips == 0 or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
