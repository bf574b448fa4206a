"""Poll observe elides the upsert of an object unchanged since the last poll.

The client hands back the same frozen object for a LIST item ``==`` to
the previous complete LIST's (``K8sApiClient._list_parsed``); the bridge
skips ``_upsert_pod`` / ``_upsert_node`` for an object it received last
poll whose stored object nothing has replaced since, and pushes the
skipped objects' knowledge samples as one batch
(``SchedulerBridge.observe_pods``). The differential here drives the
same seeded poll history through two bridges: one fed by a caching
client, one by a fresh client every poll (fresh objects, so nothing is
ever elided). After every poll the two must hold the same state, bit for
bit: tasks, machines, bindings, the parked displacement, the
incremental builder's churn notes and columns, and the knowledge rings.
"""

from __future__ import annotations

import copy
import dataclasses
import json

import numpy as np
import pytest

from poseidon_tpu.apiclient import K8sApiClient
from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.cluster import TaskPhase
from poseidon_tpu.ha.checkpoint import capture_snapshot, restore_bridge
from poseidon_tpu.models.knowledge import (
    KnowledgeBase,
    MachineSample,
    TaskSample,
)

STEPS = 12
GHOST = "ghost-0"            # a node pods are bound to before it exists
GHOST_POD = "default/p8"     # the pod bound to it
RESHAPE_AT = 4
DROP_AT = 5
HOLD_AT = 6                  # a truncated pod LIST the guard holds
RESTORE_AT = 8
TWICE_AT = 9                 # two observes with nothing in between


def _node_item(name, rack, pods=4, alloc="6"):
    return {
        "metadata": {"name": name, "labels": {"rack": rack}},
        "status": {
            "capacity": {"cpu": "8", "memory": "16Gi", "pods": str(pods)},
            "allocatable": {"cpu": alloc, "memory": "12Gi",
                            "pods": str(pods)},
        },
    }


def _pod_item(name, *, cpu="250m", job="", prefs=None, phase="Pending",
              node=""):
    meta = {"name": name, "namespace": "default",
            "labels": {"job-name": job} if job else {}}
    if prefs:
        meta["annotations"] = {"poseidon.io/data-prefs": json.dumps(prefs)}
    spec = {"containers": [
        {"resources": {"requests": {"cpu": cpu, "memory": "128Mi"}}}]}
    if node:
        spec["nodeName"] = node
    return {"metadata": meta, "spec": spec, "status": {"phase": phase}}


class Cluster:
    """The apiserver's objects, mutated by a seeded script; a LIST is a
    fresh decode of them (what ``json.loads`` of a page gives)."""

    def __init__(self, rng):
        self.rng = rng
        self.nodes = {f"m{i}": _node_item(f"m{i}", f"r{i % 2}",
                                          alloc=str(4 + i % 3))
                      for i in range(6)}
        self.pods = {}
        self.serial = 0
        for i in range(8):
            self._add(phase="Running", node=f"m{i % 6}")
        self._add(phase="Running", node=GHOST)
        for _ in range(6):
            self._add()
        self.truncate = 0
        # bindings the apiserver shows from the next LIST on, and those
        # it shows one LIST later (the pod reads Pending meanwhile)
        self.binds: dict[str, str] = {}
        self.late_binds: dict[str, str] = {}

    def _add(self, phase="Pending", node=""):
        name = f"p{self.serial}"
        self.serial += 1
        r = self.rng
        prefs = ({f"m{int(r.integers(0, 6))}": int(r.integers(10, 90))}
                 if r.random() < 0.5 else None)
        self.pods[f"default/{name}"] = _pod_item(
            name, job=f"j{int(r.integers(0, 3))}", prefs=prefs,
            phase=phase, node=node)

    def listing(self, resource):
        items = list((self.nodes if resource == "nodes"
                      else self.pods).values())
        if resource == "pods" and self.truncate:
            items = items[:self.truncate]
        return json.loads(json.dumps(items)), 0

    def apply_binds(self):
        for key, node in self.binds.items():
            item = self.pods.get(key)
            if item is not None and item["status"]["phase"] == "Pending":
                item["spec"]["nodeName"] = node
                item["status"]["phase"] = "Running"
        self.binds, self.late_binds = self.late_binds, {}

    def _pick(self, phase):
        keys = sorted(k for k, v in self.pods.items()
                      if v["status"]["phase"] == phase and k != GHOST_POD)
        return keys[int(self.rng.integers(0, len(keys)))] if keys else None

    def churn(self, step):
        r = self.rng
        for _ in range(int(r.integers(0, 4))):
            self._add()
        if r.random() < 0.5:
            keys = sorted(k for k in self.pods if k != GHOST_POD)
            self.pods.pop(keys[int(r.integers(0, len(keys)))])
        key = self._pick("Running")
        if key is not None and r.random() < 0.5:
            self.pods[key]["status"]["phase"] = str(
                r.choice(["Succeeded", "Failed"]))
        key = self._pick("Pending")
        if key is not None and r.random() < 0.5:
            self.pods[key]["spec"]["containers"][0]["resources"][
                "requests"]["cpu"] = f"{int(r.integers(1, 9)) * 100}m"
        key = self._pick("Running")
        if key is not None and r.random() < 0.5:
            self.pods[key]["metadata"]["annotations"] = {
                "poseidon.io/data-prefs": json.dumps(
                    {f"m{int(r.integers(0, 6))}": 50})}
        key = self._pick("Running")
        if key is not None:
            # a new resourceVersion alone: a changed item, an equal parse
            self.pods[key]["metadata"]["resourceVersion"] = str(step)
        if step == 3:
            self.nodes[GHOST] = _node_item(GHOST, "r0")
        if step == RESHAPE_AT:
            self.nodes["m1"] = _node_item("m1", "r1", pods=5, alloc="5")
        if step == DROP_AT:
            # the node lifecycle controller orphans the node's pods
            del self.nodes["m2"]
            for item in self.pods.values():
                if item["spec"].get("nodeName") == "m2":
                    del item["spec"]["nodeName"]
                    item["status"]["phase"] = "Pending"
        self.truncate = 3 if step == HOLD_AT else 0


def _client(cluster):
    c = K8sApiClient()
    c._list_rv = lambda resource, selector="": cluster.listing(resource)
    return c


def _bridge(preemption):
    return SchedulerBridge(
        cost_model="quincy", enable_preemption=preemption,
        migration_hysteresis=20, shrink_grace_s=0.0,
    )


def _builder_inputs(b):
    g = b._graph
    notes = {name: copy.copy(getattr(g, name)) for name in (
        "_rebuild", "_added", "_removed", "_updated", "_aged",
        "_slot_delta", "_run_added", "_run_removed", "_run_moved",
        "_run_updated", "_uid_pos", "_run_pos")}
    return notes, g.columns


def _assert_columns_equal(ca, cb, where):
    assert (ca is None) == (cb is None), where
    if ca is None:
        return
    for f in dataclasses.fields(type(ca)):
        a, b = getattr(ca, f.name), getattr(cb, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, (where,
                                                               f.name)
            same = (a.tolist() == b.tolist() if a.dtype == object
                    else a.tobytes() == b.tobytes())
            assert same, (where, f.name)
        else:
            assert a == b, (where, f.name)


def _assert_rings_equal(ka, kb, where):
    for store in ("machines", "tasks"):
        a, b = ka[store], kb[store]
        for name in ("buf", "sum", "count"):
            assert a[name].tobytes() == b[name].tobytes(), (where, store,
                                                             name)
        assert a["idx"] == b["idx"] and a["free"] == b["free"], (where,
                                                                 store)


def _assert_same_state(a, b, where):
    assert list(a.tasks.items()) == list(b.tasks.items()), where
    assert list(a.machines.items()) == list(b.machines.items()), where
    assert list(a.pod_to_machine.items()) == list(
        b.pod_to_machine.items()), where
    assert list(a._displaced_parked) == list(b._displaced_parked), where
    notes_a, cols_a = _builder_inputs(a)
    notes_b, cols_b = _builder_inputs(b)
    assert notes_a == notes_b, where
    _assert_columns_equal(cols_a, cols_b, where)
    _assert_rings_equal(a.knowledge.export_state(),
                        b.knowledge.export_state(), where)


class Expect:
    """The objects an observe should elide, counted from the outside: a
    LIST item equal to the previous LIST's, whose object the bridge has
    not replaced since it observed that LIST (a Running pod on a node
    the bridge does not know is kept Pending and never elided: it must
    be adopted once the node appears)."""

    def __init__(self):
        self.items = {"nodes": {}, "pods": {}}
        self.store = {"nodes": {}, "pods": {}}

    def count(self, resource, items, live):
        key = ((lambda i: i["metadata"]["name"]) if resource == "nodes"
               else (lambda i: "default/" + i["metadata"]["name"]))
        prev_items, prev_store = self.items[resource], self.store[resource]
        n = 0
        for item in items:
            k = key(item)
            if k not in prev_items or prev_items[k] != item:
                continue
            if live.get(k) is not prev_store[k]:
                continue
            held = prev_store[k]
            if (resource == "pods" and item["status"]["phase"] == "Running"
                    and held is not None
                    and held.phase == TaskPhase.PENDING):
                continue
            n += 1
        return n

    def observed(self, resource, items, live):
        key = ((lambda i: i["metadata"]["name"]) if resource == "nodes"
               else (lambda i: "default/" + i["metadata"]["name"]))
        self.items[resource] = {key(i): i for i in items}
        self.store[resource] = {key(i): live.get(key(i)) for i in items}


@pytest.mark.parametrize("preemption", [False, True],
                         ids=["place_only", "rebalancing"])
@pytest.mark.parametrize("seed", [7, 3000002601])
def test_elided_observe_is_bit_identical_to_the_full_path(preemption,
                                                          seed):
    rng = np.random.default_rng(seed)
    cluster = Cluster(rng)
    caching = _client(cluster)
    a, b = _bridge(preemption), _bridge(preemption)
    expect = Expect()
    listed = elided = 0
    counts = {"elided": 0, "held": 0, "adopted": False, "reshaped": False}

    def observe(where):
        nonlocal listed, elided
        fresh = _client(cluster)
        for resource in ("nodes", "pods"):
            items = cluster.listing(resource)[0]
            live = a.machines if resource == "nodes" else a.tasks
            elided += expect.count(resource, items, live)
            listed += len(items)
            if resource == "nodes":
                a.observe_nodes(caching.all_nodes())
                b.observe_nodes(fresh.all_nodes())
            else:
                a.observe_pods(caching.all_pods())
                b.observe_pods(fresh.all_pods())
            live = a.machines if resource == "nodes" else a.tasks
            expect.observed(resource, items, live)
        _assert_same_state(a, b, where)

    for step in range(STEPS):
        cluster.apply_binds()
        if step:
            cluster.churn(step)
        observe(step)
        if step == HOLD_AT:
            counts["held"] = a._pod_shrink_strikes
        if step == TWICE_AT:
            before = a._observe_elided
            observe((step, "again"))
            # nothing changed between the two LISTs or in the bridge
            ghosts = sum(t.phase == TaskPhase.PENDING
                         and cluster.pods[u]["status"]["phase"] == "Running"
                         for u, t in a.tasks.items() if u in cluster.pods)
            assert a._observe_elided - before == (
                len(cluster.nodes) + len(cluster.pods) - ghosts)
        counts["adopted"] |= a.tasks.get(GHOST_POD) is not None and (
            a.tasks[GHOST_POD].machine == GHOST)
        ra, rb = a.run_scheduler(), b.run_scheduler()
        assert ra.bindings == rb.bindings, step
        assert ra.migrations == rb.migrations, step
        assert ra.preemptions == rb.preemptions, step
        assert ra.unscheduled == rb.unscheduled, step
        assert ra.stats.cost == rb.stats.cost, step
        assert ra.stats.build_mode == rb.stats.build_mode, step
        assert (ra.stats.observe_listed, ra.stats.observe_elided) == (
            listed, elided), step
        assert (rb.stats.observe_listed, rb.stats.observe_elided) == (
            listed, 0), step
        counts["elided"] += elided
        listed = elided = 0
        # actuation, identically on both: some bindings fail, some land
        # at the apiserver a LIST late (the pod reads Pending meanwhile)
        for i, (uid, machine) in enumerate(sorted(ra.bindings.items())):
            if i % 5 == 4:
                a.binding_failed(uid)
                b.binding_failed(uid)
                continue
            a.confirm_binding(uid, machine)
            b.confirm_binding(uid, machine)
            (cluster.late_binds if i % 3 == 2 else cluster.binds)[
                uid] = machine
        for uid, (_frm, to) in sorted(ra.migrations.items()):
            a.confirm_migration(uid, to)
            b.confirm_migration(uid, to)
            cluster.pods[uid]["spec"]["nodeName"] = to
        for uid in sorted(ra.preemptions):
            a.confirm_preemption(uid)
            b.confirm_preemption(uid)
            cluster.pods[uid]["spec"].pop("nodeName", None)
            cluster.pods[uid]["status"]["phase"] = "Pending"
        _assert_same_state(a, b, (step, "actuated"))
        if step == RESTORE_AT:
            # a warm restore: fresh bridges adopt each one's checkpoint;
            # the caching client keeps its objects across it
            snap_a, snap_b = capture_snapshot(a), capture_snapshot(b)
            a, b = _bridge(preemption), _bridge(preemption)
            restore_bridge(a, snap_a)
            restore_bridge(b, snap_b)
            expect = Expect()
    # the history really exercised the elision and its exclusions
    assert counts["elided"] > 0
    assert counts["held"] > 0
    assert counts["adopted"]


def test_an_unchanged_snapshot_is_elided_whole():
    """Two polls of an unchanged cluster with no round between: the
    second elides every node and pod, and the rings still take one
    sample per node and running pod."""
    cluster = Cluster(np.random.default_rng(1))
    del cluster.pods[GHOST_POD]              # no pod on an unknown node
    client = _client(cluster)
    bridge = _bridge(False)
    for _ in range(2):
        bridge.observe_nodes(client.all_nodes())
        bridge.observe_pods(client.all_pods())
    n = len(cluster.nodes) + len(cluster.pods)
    assert (bridge._observe_listed, bridge._observe_elided) == (
        2 * n, n)
    counts = bridge.knowledge.export_state()
    running = sum(t.phase == TaskPhase.RUNNING
                  for t in bridge.tasks.values())
    assert int(counts["machines"]["count"].sum()) == 2 * len(cluster.nodes)
    assert int(counts["tasks"]["count"].sum()) == 2 * running


def test_a_pod_on_an_unknown_node_is_adopted_when_the_node_appears():
    """The one upsert outcome that depends on another object: a Running
    pod bound to a node the bridge does not know stays Pending, and is
    adopted on the poll that lists the node, although the pod's own
    item never changed."""
    cluster = Cluster(np.random.default_rng(2))
    client = _client(cluster)
    bridge = _bridge(False)
    for _ in range(2):
        bridge.observe_nodes(client.all_nodes())
        bridge.observe_pods(client.all_pods())
    assert bridge.tasks[GHOST_POD].phase == TaskPhase.PENDING
    cluster.nodes[GHOST] = _node_item(GHOST, "r0")
    bridge.observe_nodes(client.all_nodes())
    bridge.observe_pods(client.all_pods())
    assert bridge.tasks[GHOST_POD].phase == TaskPhase.RUNNING
    assert bridge.pod_to_machine[GHOST_POD] == GHOST


@pytest.mark.parametrize("repeat", [False, True],
                         ids=["distinct", "repeated_name"])
def test_batched_samples_match_one_by_one(repeat):
    """``add_*_samples`` leaves the rings as ``add_*_sample`` in turn
    does, bit for bit, past a ring's wrap and with a name given twice."""
    rng = np.random.default_rng(5)
    one, many = KnowledgeBase(queue_size=3), KnowledgeBase(queue_size=3)
    names = [f"n{i}" for i in range(40)]
    for _ in range(5):
        batch = list(names) + (names[:3] if repeat else [])
        cpu = rng.random(len(batch)) * 7.3
        mem = rng.integers(0, 1 << 40, len(batch))
        tasks = [TaskSample(cpu_usage=float(c), mem_usage_kb=int(m))
                 for c, m in zip(cpu, mem)]
        machines = [MachineSample(cpu_idle=float(c) / 7.3,
                                  mem_free_frac=float(c) / 9.1)
                    for c in cpu]
        for name, t, m in zip(batch, tasks, machines):
            one.add_task_sample(name, t)
            one.add_machine_sample(name, m)
        many.add_task_samples(batch, tasks)
        many.add_machine_samples(batch, machines)
        one.retire_task(names[7])
        many.retire_task(names[7])
    _assert_rings_equal(one.export_state(), many.export_state(), "rings")


def test_a_watch_event_between_polls_voids_the_elision():
    """A watch event replaces the stored node or pod; the next full
    snapshot hands back the object held from the LIST before the event,
    and the bridge must apply it, not elide it."""
    cluster = Cluster(np.random.default_rng(3))
    caching = _client(cluster)
    a, b = _bridge(False), _bridge(False)

    def poll(fresh):
        for bridge, client in ((a, caching), (b, fresh)):
            bridge.observe_nodes(client.all_nodes())
            bridge.observe_pods(client.all_pods())

    poll(_client(cluster))
    poll(_client(cluster))
    fresh = _client(cluster)
    reshaped = K8sApiClient._parse_node(
        _node_item("m1", "r0", pods=9, alloc="1"))
    done = dataclasses.replace(a.tasks["default/p0"],
                               phase=TaskPhase.SUCCEEDED)
    for bridge in (a, b):
        bridge.observe_node_event("MODIFIED", reshaped)
        bridge.observe_pod_event("MODIFIED", done)
    poll(fresh)
    assert a.machines["m1"].max_tasks == 4
    assert a.tasks["default/p0"].phase == TaskPhase.RUNNING
    _assert_same_state(a, b, "resynced")
