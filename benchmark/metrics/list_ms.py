"""Apiserver I/O: the mean time of the LISTs a round was built from
(``SchedulerStats.list_ms``: the ``poseidon/list`` span, the poll's HTTP
requests and JSON decode, summed since the previous round began) over
the window's rounds with pods to place. Nothing on a program whose
stats lack the field."""


def read(ctx):
    xs = [r["list_ms"] for r in ctx.rounds
          if r["pods_pending"] > 0 and "list_ms" in r]
    return sum(xs) / len(xs) if xs else None
