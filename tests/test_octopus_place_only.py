"""Place-only Octopus prices the pods already running.

The deployed flagfile runs Octopus (``--flow_scheduling_cost_model=6``)
with ``--enable_preemption=false``. Octopus prices a machine by the pods
running on it ("busy machines price up, so flow spreads"), so the bridge
hands the graph builder's per-machine running count to pricing in every mode
(``BuilderColumns.running_per_machine``). Checked here against the
benchmark's plain reference (``benchmark/reference.py`` with
``benchmark/pricing/octopus.py``, imported by path: it imports nothing
of the program), for every build mode, and for the models that do not
read the count.
"""

from __future__ import annotations

import collections
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.cluster import Machine, Task, TaskPhase
from poseidon_tpu.graph.builder import FlowGraphBuilder
from poseidon_tpu.guards import CompileCounter
from poseidon_tpu.models import build_cost_inputs, get_cost_model
from poseidon_tpu.models.costs import COST_MODELS

from helpers import random_cluster

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


reference = _load("bench_reference", BENCH / "reference.py")
octopus = reference.load_pricing(BENCH / "pricing", "octopus")

SEATS = 10


def _nodes(n: int) -> list[Machine]:
    # the octopus_5k node template: 4 CPU, 32 GiB, no rack
    return [Machine(name=f"m{i:03d}", cpu_capacity=4.0,
                    cpu_allocatable=4.0, memory_capacity_kb=32 << 20,
                    memory_allocatable_kb=32 << 20, max_tasks=SEATS)
            for i in range(n)]


def _pod(uid: str, machine: str = "", job: str = "") -> Task:
    # the octopus_5k pod template: 100m CPU, 500 MiB, no preferences
    return Task(uid=uid, job=job or uid, cpu_request=0.1,
                memory_request_kb=512_000,
                phase=TaskPhase.RUNNING if machine else TaskPhase.PENDING,
                machine=machine)


def _cluster(seed: int, machines: int = 80, pending: int = 200):
    """Nodes with 0-9 pods running each, and ``pending`` identical pods."""
    rng = np.random.default_rng(seed)
    nodes = _nodes(machines)
    running = rng.integers(0, SEATS, size=machines)
    pods = [_pod(f"run-{i}-{k}", nodes[i].name, f"rj-{i}")
            for i, n in enumerate(running) for k in range(n)]
    pods += [_pod(f"pod-{j:04d}", job=f"job-{j // 10}")
             for j in range(pending)]
    return nodes, pods


def _round_input(bridge: SchedulerBridge, round_num: int):
    """What the bridge's next round sees, by name, for the reference."""
    running = collections.Counter(
        t.machine for t in bridge.tasks.values()
        if t.phase == TaskPhase.RUNNING)
    return reference.RoundInput(
        round_num=round_num,
        machines=[(m.name, m.rack, m.max_tasks)
                  for m in bridge.machines.values()],
        running=dict(running),
        pending=[t.uid for t in bridge.tasks.values()
                 if t.phase == TaskPhase.PENDING],
        waits={}, prefs={})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_place_only_round_costs_the_reference_optimum(seed):
    """The daemon path's round (bridge -> resident dense auction) costs
    exactly the plain reference's least cost, priced by the pods already
    running on each machine."""
    nodes, pods = _cluster(seed)
    bridge = SchedulerBridge(cost_model="octopus", small_to_oracle=False)
    bridge.observe_nodes(nodes)
    bridge.observe_pods(pods)
    inp = _round_input(bridge, 1)
    result = bridge.run_scheduler()
    assert result.stats.backend == "dense_auction", result.stats
    v = reference.check_round(inp, octopus, result.bindings)
    assert v.infeasible == []
    assert v.placed == len(inp.pending)
    assert v.program == v.optimum
    assert result.stats.cost == v.optimum
    # the round spread: no pod went to a machine dearer than one left
    # with a free seat
    load = collections.Counter(inp.running)
    placed = collections.Counter(result.bindings.values())
    dearest = max(load[m] for m in placed)
    assert all(load[m.name] + placed[m.name] >= SEATS
               for m in nodes if load[m.name] < dearest)


def _spy_occupancy(bridge: SchedulerBridge) -> list[tuple]:
    """Record (machine_used_slots, task_current) of every dispatch."""
    seen = []
    begin = bridge.solver.begin_round

    def spy(arrays, meta, **kw):
        seen.append((kw["cost_input_kwargs"]["machine_used_slots"].copy(),
                     meta.task_current.copy()))
        return begin(arrays, meta, **kw)

    bridge.solver.begin_round = spy
    return seen


@pytest.mark.parametrize("preemption", [False, True],
                         ids=["place_only", "rebalancing"])
def test_full_and_incremental_builds_price_the_same_occupancy(preemption):
    """Through bindings, deletions and arrivals, a full and an
    incremental build hand pricing the same running count per machine,
    which is the bridge's own count of its RUNNING pods; in rebalancing
    mode it is also the running block's ``bincount(current_m)``."""
    nodes, pods = _cluster(7, machines=24, pending=40)
    # a small instance: the CPU oracle solves it at once, and what the
    # solver is handed does not depend on which solver takes it
    bridges = [SchedulerBridge(cost_model="octopus", small_to_oracle=True,
                               incremental_build=inc,
                               enable_preemption=preemption)
               for inc in (False, True)]
    spies = [_spy_occupancy(b) for b in bridges]
    names = [m.name for m in nodes]
    rng = np.random.default_rng(0)
    for b in bridges:
        b.observe_nodes(nodes)
    for r in range(4):
        for b in bridges:
            b.observe_pods(pods)
        counts = collections.Counter(
            t.machine for t in bridges[0].tasks.values()
            if t.phase == TaskPhase.RUNNING)
        results = [b.run_scheduler() for b in bridges]
        full, inc = spies[0][-1], spies[1][-1]
        want = np.array([counts[n] for n in names], np.int32)
        np.testing.assert_array_equal(full[0], want)
        np.testing.assert_array_equal(inc[0], want)
        if preemption:
            cur = inc[1]
            np.testing.assert_array_equal(
                inc[0], np.bincount(cur[cur >= 0], minlength=len(names)))
        # the apiserver's next snapshot: this round's bindings landed, a
        # few running pods left, a few new ones arrived
        bound = results[1].bindings
        pods = [_pod(p.uid, bound[p.uid], p.job) if p.uid in bound else p
                for p in pods]
        gone = set(rng.choice([p.uid for p in pods if p.machine],
                              size=5, replace=False))
        pods = [p for p in pods if p.uid not in gone]
        pods += [_pod(f"new-{r}-{k}", job=f"new-{r}") for k in range(6)]


@pytest.mark.parametrize("model", sorted(COST_MODELS))
def test_only_octopus_reads_the_occupancy(model):
    """Every model but Octopus prices each arc alike with the running
    count present or absent; Octopus prices it."""
    rng = np.random.default_rng(11)
    cluster = random_cluster(rng, 12, 30)
    net, meta = FlowGraphBuilder().build(cluster)
    used = rng.integers(0, 8, size=len(meta.machine_names)).astype(np.int32)
    fn = get_cost_model(model)
    absent = np.asarray(fn(build_cost_inputs(net, meta)))
    present = np.asarray(fn(build_cost_inputs(
        net, meta, machine_used_slots=used)))
    assert np.array_equal(absent, present) == (model != "octopus")


def test_auction_rounds_counts_the_auction_and_not_the_oracle():
    nodes, pods = _cluster(5)
    auction = SchedulerBridge(cost_model="octopus", small_to_oracle=False)
    auction.observe_nodes(nodes)
    auction.observe_pods(pods)
    stats = auction.run_scheduler().stats
    assert stats.backend == "dense_auction"
    assert stats.auction_rounds > 0
    # a small instance goes to the CPU oracle on purpose
    nodes, pods = _cluster(5, machines=8, pending=20)
    oracle = SchedulerBridge(cost_model="octopus", small_to_oracle=True)
    oracle.observe_nodes(nodes)
    oracle.observe_pods(pods)
    stats = oracle.run_scheduler().stats
    assert stats.backend == "oracle:small-instance"
    assert stats.auction_rounds == 0


def test_a_changed_occupancy_recompiles_nothing():
    """The running count rides the round's one batched upload at the
    machine axis' padded shape: rounds whose occupancy changes, over the
    same pending pods, hit the compiled chain."""
    nodes, pods = _cluster(9, machines=80, pending=60)
    running = [p for p in pods if p.machine]
    pending = [p for p in pods if not p.machine]
    bridge = SchedulerBridge(cost_model="octopus", small_to_oracle=False)
    bridge.observe_nodes(nodes)
    bridge.observe_pods(pods)
    # warm-up: the chain compiles. Placements are not confirmed, so the
    # same pending pods are offered again each round
    for _ in range(3):
        assert bridge.run_scheduler().stats.backend == "dense_auction"
    seen = _spy_occupancy(bridge)
    with CompileCounter() as cc:
        if not cc.supported:
            pytest.skip("jax.monitoring unavailable")
        for r in range(3):
            # a tenth of the running pods leave; as many start elsewhere
            running = running[8:] + [
                _pod(f"moved-{r}-{k}", nodes[(7 * k + r) % 80].name)
                for k in range(8)]
            bridge.observe_pods(running + pending)
            assert bridge.run_scheduler().stats.backend == "dense_auction"
    assert cc.count == 0, f"{cc.count} recompile(s) on an occupancy change"
    assert len({s[0].tobytes() for s in seen}) == 3


def test_waves_of_identical_pods_settle_without_a_crawl():
    """Closed-loop waves (the octopus_5k.poll traffic, cut to 100 nodes):
    each wave deletes the oldest running pods and brings as many
    identical ones. A start from the last wave's prices sat below where
    this wave's cheapest tier clears, and the surplus crawled up the
    pool of tied, full machines at eps = 1 per displacement until the
    20k-round fuse ran out (the fourth wave here). Every round starts
    from the analytic clearing, which seats identical pods outright:
    each wave settles in a round or so at the reference's optimum."""
    rng = np.random.default_rng(0)
    nodes = _nodes(100)
    seats = np.repeat(np.arange(100), SEATS)
    pick = rng.choice(len(seats), size=500, replace=False)
    running = [_pod(f"init-{i}", nodes[seats[j]].name, f"init-{i // 8}")
               for i, j in enumerate(pick)]
    bridge = SchedulerBridge(cost_model="octopus", small_to_oracle=False)
    bridge.observe_nodes(nodes)
    for w in range(5):
        running = running[200:]
        wave = [_pod(f"w{w}-{k}", job=f"wave-{w}") for k in range(200)]
        bridge.observe_pods(running + wave)
        inp = _round_input(bridge, w + 1)
        result = bridge.run_scheduler()
        stats = result.stats
        assert stats.backend == "dense_auction", stats
        assert stats.auction_rounds < 10, (w, stats.auction_rounds)
        v = reference.check_round(inp, octopus, result.bindings)
        assert v.program == v.optimum and v.placed == 200, (w, v)
        running += [_pod(p.uid, result.bindings[p.uid], p.job)
                    for p in wave]
