"""Apiserver I/O: the mean host time spent applying the apiserver's view
(poll LIST diff or watch events) per round (``SchedulerStats.observe_ms``,
accumulated since the previous round), over all the window's rounds."""


def read(ctx):
    obs = [r["observe_ms"] for r in ctx.rounds]
    return sum(obs) / len(obs) if obs else None
