"""Resident solver, host side: the mean time the round blocked joining
its placement download (``SchedulerStats.fetch_wait_ms``, the
``poseidon/fetch_wait`` span) over the window's rounds with pods to
place."""


def read(ctx):
    xs = [r["fetch_wait_ms"] for r in ctx.rounds
          if r["pods_pending"] > 0 and "fetch_wait_ms" in r]
    return sum(xs) / len(xs) if xs else None
