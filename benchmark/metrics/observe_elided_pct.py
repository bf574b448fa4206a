"""Apiserver I/O: the share of the nodes and pods the poll snapshots
handed to the window's rounds with pods to place whose upsert the bridge
elided as unchanged since the previous poll (the sum of
``SchedulerStats.observe_elided`` over the sum of ``observe_listed``),
in %. Nothing on a program whose stats lack the fields, or when those
rounds were handed no object."""


def read(ctx):
    rows = [r for r in ctx.rounds
            if r["pods_pending"] > 0 and "observe_listed" in r]
    listed = sum(r["observe_listed"] for r in rows)
    if not listed:
        return None
    return 100.0 * sum(r["observe_elided"] for r in rows) / listed
