"""Exact fallback: rounds the dense lane handed to the CPU oracle during
the window, the growth of ``SchedulerStats.degrades_total`` (a lifetime
count) from the round before the window to the window's last round."""


def read(ctx):
    if not ctx.rounds:
        return None
    start = ctx.before["degrades_total"] if ctx.before else 0
    return ctx.rounds[-1]["degrades_total"] - start
