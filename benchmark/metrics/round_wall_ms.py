"""Daemon loop: the mean wall span of a round (``SchedulerStats.wall_ms``,
begin to finish) over the window's rounds that had pods to place."""


def read(ctx):
    walls = [r["wall_ms"] for r in ctx.rounds if r["pods_pending"] > 0]
    return sum(walls) / len(walls) if walls else None
