"""Share of the op programs' device time spent outside the Pallas kernels
(``tpu_custom_call``): the pads, transposes and slices of the kernel
registry's glue, in percent."""


def read(run):
    s = run.trace_summary
    if s is None:
        return None
    device = kernel = 0.0
    for name in run.record["calls"]:
        p = s.program("jit_" + name)
        device += p.device_s
        kernel += p.kernel_s
    return 100.0 * (device - kernel) / device if device > 0 else None
