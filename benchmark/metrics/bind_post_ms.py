"""Actuation: the mean time of the binding POSTs charged to a round
(``SchedulerStats.post_ms``: the ``poseidon/post_bindings`` spans since
the previous round's stats, in the pipelined loop the previous round's
bindings POSTed while this round solved) over the window's rounds with
pods to place. Nothing on a program whose stats lack the field."""


def read(ctx):
    xs = [r["post_ms"] for r in ctx.rounds
          if r["pods_pending"] > 0 and "post_ms" in r]
    return sum(xs) / len(xs) if xs else None
