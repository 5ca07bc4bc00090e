"""Set-up time: from process start to the start of the window (weights or
data made on the device, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
