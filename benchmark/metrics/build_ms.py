"""Bridge and graph builder: the mean host time to build a round's graph
(``SchedulerStats.build_ms``) over the window's rounds with pods to
place."""


def read(ctx):
    xs = [r["build_ms"] for r in ctx.rounds if r["pods_pending"] > 0]
    return sum(xs) / len(xs) if xs else None
