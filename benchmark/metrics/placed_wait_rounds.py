"""Bridge finish: the rounds a placed pod had waited, on average over
the pods the window's rounds placed (the sum of
``SchedulerStats.placed_wait_rounds`` over the sum of ``pods_placed``).
Nothing on a program whose stats lack the field, or when no pod was
placed."""


def read(ctx):
    rows = [r for r in ctx.rounds if "placed_wait_rounds" in r]
    placed = sum(r["pods_placed"] for r in rows)
    if not placed:
        return None
    return sum(r["placed_wait_rounds"] for r in rows) / placed
