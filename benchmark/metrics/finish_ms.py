"""Bridge finish: the mean time of a round's apply phase after the join
(``SchedulerStats.finish_ms``, the ``poseidon/apply`` span: delta
extraction and each placed pod's event, log line and stamp) over the
window's rounds with pods to place. Nothing on a program whose stats
lack the field."""


def read(ctx):
    xs = [r["finish_ms"] for r in ctx.rounds
          if r["pods_pending"] > 0 and "finish_ms" in r]
    return sum(xs) / len(xs) if xs else None
