"""Device: the share of the device's idle time that no program span
names. Between the first and the last operation on the trace's XLA Ops
line, the gaps between operations are the idle time; the part of it
that no ``poseidon/`` event other than the root ``poseidon/tick`` covers,
on any host line, is untraced. 100 x untraced / idle. A ``#key=value#``
suffix on an event's name (the annotation's ids) is ignored. A program
without such spans reads 100."""

import trace_reduce

PREFIX = "poseidon/"
ROOT = "poseidon/tick"


def covered_ns(gaps, spans) -> float:
    """Length of the part of ``gaps`` that ``spans`` cover; both are
    sorted lists of disjoint (start, end) intervals."""
    total, j = 0.0, 0
    for s, e in gaps:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            total += min(e, spans[k][1]) - max(s, spans[k][0])
            k += 1
    return total


def read(ctx):
    if ctx.trace is None:
        return None
    planes = trace_reduce.device_planes(ctx.trace)
    if not planes:
        return None
    ops = trace_reduce.union(
        (s, s + d)
        for _n, s, d in trace_reduce.line_events(planes[0],
                                                 trace_reduce.OPS_LINE))
    gaps = [(ops[i][1], ops[i + 1][0]) for i in range(len(ops) - 1)]
    idle = sum(e - s for s, e in gaps)
    if idle <= 0:
        return None
    spans = trace_reduce.union(
        (s, s + d)
        for p in ctx.trace["planes"] if p["name"].startswith("/host:")
        for ln in p["lines"] for name, s, d in ln["events"]
        if name.startswith(PREFIX) and name.split("#", 1)[0] != ROOT)
    return 100.0 * (idle - covered_ns(gaps, spans)) / idle
