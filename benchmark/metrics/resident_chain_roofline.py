"""Device program (``_resident_chain``, the whole fused round: pricing,
redensify, the dense auction and finalize): the least time to read one
round's priced flow network once at the chip's HBM bandwidth, over the
program's device time per execution, in percent.

Per execution on both sides: an empty round dispatches no program
(``bridge.begin_round`` returns early), so the traced window's
executions are its rounds with pods. The bytes are the mean over those
rounds (``flow_bytes.network_bytes`` of their ``SchedulerStats.
pods_pending``, their preference arcs at the configuration's rate, the
configuration's node and rack counts; the stats do not say how many jobs
a round's pods belong to, so one job is counted, the lower bound); the
time is the program's device time over its executions. The peak is
``peaks.json``'s ``hbm_bytes_per_s``: reading the network is memory
work, so the memory side bounds it. The auction alone is not separable
in the trace until the program names its scopes.
"""

import flow_bytes
import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    planes = trace_reduce.device_planes(ctx.trace)
    if not planes:
        return None
    ns, n = trace_reduce.program_ns(planes[0], "_resident_chain")
    rounds = [r for r in ctx.trace_rounds if r["pods_pending"] > 0]
    if not n or not rounds:
        return None
    c, p = ctx.config["cluster"], ctx.config["pods"]
    machines = int(c["nodes"])
    per_rack = int(c.get("machines_per_rack", 0))
    racks = -(-machines // per_rack) if per_rack else 0
    prefs = (min(int(p.get("machine_prefs", 0)), per_rack or machines)
             + float(p.get("rack_pref_share", 0)))
    mean_bytes = sum(
        flow_bytes.network_bytes(r["pods_pending"],
                                 r["pods_pending"] * prefs, machines,
                                 machines if racks else 0, racks, 1)
        for r in rounds) / len(rounds)
    least_s = mean_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / n / 1e9)
