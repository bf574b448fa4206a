"""Device program (the dense auction): the mean number of bidding rounds
the auction took for a round's solve (``SchedulerStats.auction_rounds``)
over the window's rounds with pods to place that the auction solved
(``backend`` ``dense_auction``; a round the oracle solved reads 0 and is
left out). Nothing on a program whose stats lack the field, or when the
auction solved no round."""


def read(ctx):
    xs = [r["auction_rounds"] for r in ctx.rounds
          if r["pods_pending"] > 0 and r.get("backend") == "dense_auction"
          and "auction_rounds" in r]
    return sum(xs) / len(xs) if xs else None
