"""The plain reference: one scheduling round, solved from its definition.

Imports nothing of the program. Given what a round saw (its pending pods,
how many pods already ran on each machine) and the pods' specifications
as the traffic generator made them, it prices the round's flow network
in Firmament's taxonomy with the configuration's policy
(``pricing/<cost_model>.py``), finds the least cost with a linear
program (scipy's HiGHS; a network matrix, so the optimum is integral),
and prices the program's decisions with the same costs.

The network, per round: each pending pod has unit supply and arcs to its
preferred machines, to its preferred racks' aggregators, to the cluster
aggregator, and to its job's unscheduled aggregator (capacity 1 each);
the cluster aggregator reaches every machine and a rack aggregator each
of its machines, with capacity the machine's free seats; each machine
reaches the sink with that same capacity; unscheduled aggregators reach
the sink without a binding limit and at no cost. Free seats are the
node's pod capacity less the pods running on it.

Placement constraints (``constraints.py``) only remove pod-machine
pairs. There is one cluster aggregator per constraint signature among
the round's pending pods, and one rack aggregator per rack and
signature; each reaches only the machines its signature admits, and a
pod's arcs go to its own signature's aggregators. A preference arc to a
machine the pod's constraint excludes is dropped (the pod is still
priced from all its preferences: its data does not move). With no
constraints there is one signature, and the network is the one above.

A pod placed on machine m costs the cheapest route to m that its arcs
allow; a pod left unscheduled costs its unscheduled arc; a pod placed on
a machine its constraint excludes is infeasible. A round is exact when
the program's decisions are feasible and cost the optimum.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import numpy as np
import scipy.optimize
import scipy.sparse


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# by path, as this module itself may be loaded (never from sys.path)
constraints = _load("bench_constraints",
                    pathlib.Path(__file__).resolve().parent / "constraints.py")


def load_pricing(pricing_dir: pathlib.Path, cost_model: str):
    """``<pricing_dir>/<cost_model>.py``, the policy's plain pricing."""
    path = pathlib.Path(pricing_dir) / f"{cost_model}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"no reference pricing for cost model {cost_model!r} "
            f"(expected {path})")
    return _load(f"bench_pricing_{cost_model}", path)


@dataclasses.dataclass
class RoundInput:
    """What one round saw, by name."""

    round_num: int
    machines: list[tuple[str, str, int]]   # (name, rack, pod capacity)
    running: dict[str, int]                # machine -> pods running there
    pending: list[str]                     # pod keys, in round order
    waits: dict[str, int]                  # pod key -> rounds waited
    prefs: dict[str, dict[str, int]]       # pod key -> {name: weight}
    # placement constraints, empty where there are none
    labels: dict[str, dict[str, str]] = dataclasses.field(
        default_factory=dict)              # machine -> its labels
    taints: dict[str, list[dict]] = dataclasses.field(
        default_factory=dict)              # machine -> its taints
    constraints: dict[str, dict] = dataclasses.field(
        default_factory=dict)              # pod key -> its constraint


@dataclasses.dataclass
class RoundVerdict:
    round_num: int
    pending: int
    placed: int
    optimum: int
    program: int | None        # None: the decisions were infeasible
    infeasible: list[str]

    @property
    def gap(self) -> int | None:
        return None if self.program is None else self.program - self.optimum


def _priced(inp: RoundInput, pricing):
    names = [m for m, _r, _c in inp.machines]
    known = set(names)
    racks = sorted({r for _m, r, _c in inp.machines if r})
    rack_set = set(racks)
    pods = []
    for key in inp.pending:
        p = inp.prefs.get(key, {})
        pods.append((
            inp.waits.get(key, 0),
            {n: w for n, w in p.items() if n in known},
            {n: w for n, w in p.items() if n in rack_set},
        ))
    prices = pricing.price(pods, [(m, r) for m, r, _c in inp.machines],
                           racks, [inp.running.get(m, 0)
                                   for m, _r, _c in inp.machines])
    free = np.array([max(c - inp.running.get(m, 0), 0)
                     for m, _r, c in inp.machines], np.int64)
    return names, racks, prices, free


def admitted(inp: RoundInput) -> tuple[list[int], list[np.ndarray]]:
    """Each pending pod's signature (an index), and per signature which
    machines admit it (in ``machines``' order)."""
    specs: dict[str, dict | None] = {}
    of = []
    for key in inp.pending:
        c = inp.constraints.get(key)
        of.append(constraints.signature(c))
        specs.setdefault(of[-1], c)
    sigs = sorted(specs) or [constraints.signature(None)]
    index = {s: i for i, s in enumerate(sigs)}
    admits = [np.array([constraints.feasible(specs.get(s),
                                             inp.labels.get(m, {}),
                                             inp.taints.get(m, []))
                        for m, _r, _c in inp.machines], bool)
              for s in sigs]
    return [index[s] for s in of], admits


def optimum(inp: RoundInput, pricing) -> int:
    """Least cost of the round's priced network."""
    return solve(inp, pricing)[0]


def solve(inp: RoundInput, pricing) -> tuple[int, dict[str, str]]:
    """Least cost of the round's priced network, and decisions that
    reach it (pod -> machine, for the pods the optimum places)."""
    names, racks, prices, free = _priced(inp, pricing)
    sig, admits = admitted(inp)
    M, T, S, R = len(names), len(inp.pending), len(admits), len(racks)
    midx = {m: i for i, m in enumerate(names)}
    ridx = {r: i for i, r in enumerate(racks)}
    m_rack = [ridx.get(r, -1) for _m, r, _c in inp.machines]
    # rows: pods [0, T), a cluster aggregator per signature, a rack
    # aggregator per (rack, signature), machines after
    r_cluster = T
    r_rack0 = T + S
    r_mach0 = r_rack0 + R * S
    rows, cols, vals, cost, ub = [], [], [], [], []
    # what a unit of flow on each arc says about a placement:
    # (pod, machine), (pod, aggregator row) or (aggregator row, machine)
    tags: dict[int, tuple[str, int, int]] = {}

    def var(c, cap, entries, tag=None):
        j = len(cost)
        cost.append(c)
        ub.append(cap)
        for r, v in entries:
            rows.append(r)
            cols.append(j)
            vals.append(v)
        if tag is not None:
            tags[j] = tag

    for t, (u, w, mp, rp) in enumerate(prices["pods"]):
        s = sig[t]
        var(u, 1, [(t, 1)])                              # unscheduled
        var(w, 1, [(t, 1), (r_cluster + s, 1)],          # wildcard
            ("in", t, r_cluster + s))
        for m, c in mp.items():                          # machine prefs
            if admits[s][midx[m]]:
                var(c, 1, [(t, 1), (r_mach0 + midx[m], 1)],
                    ("to", t, midx[m]))
        for r, c in rp.items():                          # rack prefs
            agg = r_rack0 + ridx[r] * S + s
            var(c, 1, [(t, 1), (agg, 1)], ("in", t, agg))
    for i in range(M):
        for s in range(S):
            if admits[s][i]:
                var(prices["cluster_to_machine"][i], free[i],
                    [(r_cluster + s, -1), (r_mach0 + i, 1)],
                    ("out", r_cluster + s, i))
        if m_rack[i] >= 0:
            for s in range(S):
                if admits[s][i]:
                    agg = r_rack0 + m_rack[i] * S + s
                    var(prices["rack_to_machine"][i], free[i],
                        [(agg, -1), (r_mach0 + i, 1)], ("out", agg, i))
        var(prices["machine_to_sink"][i], free[i], [(r_mach0 + i, -1)])
    n_rows = r_mach0 + M
    a = scipy.sparse.csr_matrix((vals, (rows, cols)),
                                shape=(n_rows, len(cost)))
    b = np.zeros(n_rows)
    b[:T] = 1
    res = scipy.optimize.linprog(
        np.asarray(cost, float), A_eq=a, b_eq=b,
        bounds=np.stack([np.zeros(len(ub)), np.asarray(ub, float)], 1),
        method="highs",
    )
    if res.status != 0:
        raise RuntimeError(f"reference LP failed: {res.message}")
    # decompose the flow: an aggregator's pods, in any order, fill its
    # arcs out (every pod in it shares one signature, so any order is
    # admitted and costs the same)
    flow = np.rint(res.x).astype(np.int64)
    placed: dict[str, str] = {}
    queued: dict[int, list[int]] = {}
    outs = []
    for j, (kind, a, b) in tags.items():
        if flow[j] <= 0:
            continue
        if kind == "to":
            placed[inp.pending[a]] = names[b]
        elif kind == "in":
            queued.setdefault(b, []).append(a)
        else:
            outs.append((a, b, int(flow[j])))
    for agg, i, f in outs:
        for t in queued[agg][:f]:
            placed[inp.pending[t]] = names[i]
        del queued[agg][:f]
    return int(round(res.fun)), placed


def decisions_cost(inp: RoundInput, pricing, placed: dict[str, str]
                   ) -> tuple[int | None, list[str]]:
    """The cost of the program's decisions (pod -> machine for the pods
    it placed; every other pending pod unscheduled), and what in them
    is infeasible."""
    names, racks, prices, free = _priced(inp, pricing)
    sig, admits = admitted(inp)
    midx = {m: i for i, m in enumerate(names)}
    rack_of = {m: r for m, r, _c in inp.machines}
    pending = set(inp.pending)
    bad = [f"{k} was not pending" for k in placed if k not in pending]
    bad += [f"{k} -> unknown machine {m}" for k, m in placed.items()
            if m not in midx]
    use = np.zeros(len(names), np.int64)
    total = 0
    for t, key in enumerate(inp.pending):
        u, w, mp, rp = prices["pods"][t]
        m = placed.get(key)
        if m is None or m not in midx:
            total += u
            continue
        i = midx[m]
        use[i] += 1
        if not admits[sig[t]][i]:
            bad.append(f"{key} -> {m}, which its constraints exclude")
        sink = prices["machine_to_sink"][i]
        routes = [w + prices["cluster_to_machine"][i]]
        if m in mp:
            routes.append(mp[m])
        r = rack_of.get(m, "")
        if r in rp:
            routes.append(rp[r] + prices["rack_to_machine"][i])
        total += min(routes) + sink
    over = np.flatnonzero(use > free)
    bad += [f"{names[i]}: {int(use[i])} placed on {int(free[i])} free "
            f"seats" for i in over]
    return (None if bad else int(total)), bad


def check_round(inp: RoundInput, pricing, placed: dict[str, str]
                ) -> RoundVerdict:
    prog, bad = decisions_cost(inp, pricing, placed)
    return RoundVerdict(
        round_num=inp.round_num, pending=len(inp.pending),
        placed=len(placed), optimum=optimum(inp, pricing), program=prog,
        infeasible=bad,
    )
