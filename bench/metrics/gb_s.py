"""Algorithmic bytes of every call completed in the window (input read
once, output written once; ``bench.lib.yardstick.op_bytes``) over the
window's seconds, 1 GB = 1e9 bytes."""


def read(run):
    return run.record["bytes"] / run.window_s / 1e9
