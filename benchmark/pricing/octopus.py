"""Plain Octopus pricing (load balancing), written from the policy's
statement, for the reference. Imports nothing of the program.

The policy, as the program states it (``models/costs.py::octopus_cost``:
"busy machines price up, so flow spreads"): a machine's price is its
load, 100 x (1 - the mean idle CPU share its node reports, allocatable
over capacity), rounded down, plus 10 for every pod already running on
it. That price is on every arc that reaches the machine (from the
cluster aggregator, from a rack aggregator, from a preference) and on
its arc to the sink. The wildcard arc costs 10 and leaving a pod
unscheduled 2,500. Costs are clipped to [0, 10,000].

Every node here reports allocatable equal to capacity, so the load term
is 0 and the running pods set the price.
"""

SCALE = 10
COST_CAP = 10_000


def _clip(c: int) -> int:
    return min(max(int(c), 0), COST_CAP)


def price(pods, machines, racks, running):
    """Arc costs of one round; ``running`` is the number of pods on each
    machine as the round begins, in ``machines``' order."""
    per_machine = [_clip(SCALE * int(r)) for r in running]
    index = {name: i for i, (name, _rack) in enumerate(machines)}
    out_pods = []
    for _wait, mprefs, rprefs in pods:
        out_pods.append((
            _clip(COST_CAP // 4),
            _clip(SCALE),
            {m: per_machine[index[m]] for m in mprefs},
            {r: 0 for r in rprefs},
        ))
    return {
        "pods": out_pods,
        "cluster_to_machine": per_machine,
        "rack_to_machine": per_machine,
        "machine_to_sink": per_machine,
    }
