"""The size of one round's priced flow network, in bytes.

Counted in Firmament's graph taxonomy from the round's pending pods,
their preference arcs, and the machine, rack and job counts, never from
the padded or dense shapes a solver works on, so it is the same work
whatever implements the solve. Each arc is four 32-bit words (tail,
head, capacity, cost) and each node one (its supply):

- arcs: per pending pod one to its job's unscheduled aggregator, one to
  the cluster aggregator and one per preference; per machine one from
  the cluster aggregator, one from its rack's aggregator (if it has a
  rack) and one to the sink; per job one from its unscheduled
  aggregator to the sink;
- nodes: the pods, the cluster aggregator, the racks, the machines, the
  jobs' unscheduled aggregators and the sink.
"""

ARC_BYTES = 16
NODE_BYTES = 4


def network_bytes(pending: float, pref_arcs: float, machines: int,
                  racked_machines: int, racks: int, jobs: float) -> float:
    arcs = (2 * pending + pref_arcs + 2 * machines + racked_machines
            + jobs)
    nodes = pending + 1 + racks + machines + jobs + 1
    return arcs * ARC_BYTES + nodes * NODE_BYTES
