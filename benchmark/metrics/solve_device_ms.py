"""Device program: the device time of one round's fused program
(``_resident_chain``, found by its jit name on the trace's XLA Modules
line), summed over the traced window and divided by its executions."""

import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    planes = trace_reduce.device_planes(ctx.trace)
    if not planes:
        return None
    ns, n = trace_reduce.program_ns(planes[0], "_resident_chain")
    return ns / n / 1e6 if n else None
