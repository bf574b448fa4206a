"""Resident solver, host side: the mean host-to-device put of a round's
inputs (``SchedulerStats.upload_ms``) over the window's rounds with pods
to place."""


def read(ctx):
    xs = [r["upload_ms"] for r in ctx.rounds if r["pods_pending"] > 0]
    return sum(xs) / len(xs) if xs else None
