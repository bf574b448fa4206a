"""Plain Quincy pricing (data locality), written from the policy's
statement, for the reference. Imports nothing of the program.

Per arc of the Firmament taxonomy, in scaled units (10 per unit):

- a preference arc (to a machine or to a rack) costs the data the pod
  would fetch remotely there: its total input (the sum of its
  preference weights, capped at 5,990) less the weight local there;
- the wildcard arc through the cluster aggregator costs the total input
  plus 10: nothing is local;
- a rack aggregator reaching its machine costs 5 (a hop);
- leaving the pod unscheduled costs 50 x (rounds waited, capped at 60,
  + 1), so waiting pods press harder each round;
- every other arc costs 0, and every cost is clipped to [0, 10,000].
"""

SCALE = 10
COST_CAP = 10_000
TOTAL_CAP = 6_000 - SCALE
WAIT_CAP = 60


def _clip(c: int) -> int:
    return min(max(int(c), 0), COST_CAP)


def price(pods, machines, racks, running):
    """Arc costs of one round. ``pods``: (wait, {machine: w}, {rack: w})
    per pending pod, preferences already limited to known names;
    ``machines``: (name, rack) per machine; ``racks``: rack names;
    ``running``: pods per machine (Quincy does not price it)."""
    out_pods = []
    for wait, mprefs, rprefs in pods:
        total = min(sum(mprefs.values()) + sum(rprefs.values()), TOTAL_CAP)
        out_pods.append((
            _clip(5 * SCALE * (min(wait, WAIT_CAP) + 1)),   # unscheduled
            _clip(total + SCALE),                           # wildcard
            {m: _clip(total - w) for m, w in mprefs.items()},
            {r: _clip(total - w) for r, w in rprefs.items()},
        ))
    n = len(machines)
    return {
        "pods": out_pods,
        "cluster_to_machine": [0] * n,
        "rack_to_machine": [_clip(SCALE // 2)] * n,
        "machine_to_sink": [0] * n,
    }
