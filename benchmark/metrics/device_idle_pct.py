"""Device: the share of the traced window in which no operation ran on
the chip, 100 x (1 - busy / window), busy being the union of the
intervals of the trace's XLA Ops."""

import trace_reduce


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    planes = trace_reduce.device_planes(ctx.trace)
    if not planes:
        return None
    busy = trace_reduce.busy_ns(planes[0]) / 1e9
    return 100.0 * (1.0 - busy / ctx.trace_window_s)
