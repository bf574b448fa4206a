"""Placement constraints in the harness: their semantics, the plain
reference against brute force, the committed configurations unchanged by
the vocabulary, and a run of a constrained configuration on the CPU."""

import hashlib
import itertools
import json
import pathlib
import shutil

import numpy as np
import pytest
import scipy.optimize

import apiserver
import constraints
import reference
import traffic_gen
from test_faults import failed, run_case

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
ZONE = traffic_gen.ZONE_LABEL
REQ = constraints.REQUIRED


def affinity(*terms):
    return {REQ: {"nodeSelectorTerms": [{"matchExpressions": list(t)}
                                        for t in terms]}}


def expr(key, op, *values):
    return {"key": key, "operator": op, **({"values": list(values)}
                                           if values else {})}


GPU = {"key": "dedicated", "value": "gpu", "effect": "NoSchedule"}
DRAIN = {"key": "drain", "value": "", "effect": "NoExecute"}
SOFT = {"key": "spot", "value": "yes", "effect": "PreferNoSchedule"}
LABELS = {ZONE: "zone-1", "pool": "gpu"}

# (constraint, node labels, node taints, admitted)
CASES = {
    "none": (None, {}, [], True),
    "selector_matches": ({"node_selector": {"pool": "gpu"}}, LABELS, [],
                         True),
    "selector_other_value": ({"node_selector": {"pool": "cpu"}}, LABELS,
                             [], False),
    "selector_absent_key": ({"node_selector": {"disk": "ssd"}}, LABELS, [],
                            False),
    "in_matches": ({"affinity": affinity([expr(ZONE, "In", "zone-0",
                                               "zone-1")])}, LABELS, [],
                   True),
    "in_misses": ({"affinity": affinity([expr(ZONE, "In", "zone-2")])},
                  LABELS, [], False),
    "in_absent_key": ({"affinity": affinity([expr("disk", "In", "ssd")])},
                      LABELS, [], False),
    "notin_matches": ({"affinity": affinity([expr(ZONE, "NotIn",
                                                  "zone-2")])}, LABELS, [],
                      True),
    "notin_misses": ({"affinity": affinity([expr(ZONE, "NotIn",
                                                 "zone-1")])}, LABELS, [],
                     False),
    "notin_absent_key": ({"affinity": affinity([expr("disk", "NotIn",
                                                     "ssd")])}, LABELS, [],
                         True),
    "exists": ({"affinity": affinity([expr("pool", "Exists")])}, LABELS,
               [], True),
    "exists_absent": ({"affinity": affinity([expr("disk", "Exists")])},
                      LABELS, [], False),
    "doesnotexist": ({"affinity": affinity([expr("disk", "DoesNotExist")])},
                     LABELS, [], True),
    "doesnotexist_present": ({"affinity": affinity([expr(
        "pool", "DoesNotExist")])}, LABELS, [], False),
    "terms_ored": ({"affinity": affinity([expr(ZONE, "In", "zone-2")],
                                         [expr("pool", "Exists")])},
                   LABELS, [], True),
    "expressions_anded": ({"affinity": affinity([expr(ZONE, "In", "zone-1"),
                                                 expr("disk", "Exists")])},
                          LABELS, [], False),
    "empty_term_matches_nothing": ({"affinity": affinity([])}, LABELS, [],
                                   False),
    "no_terms_match_nothing": ({"affinity": {REQ: {"nodeSelectorTerms": []}}},
                               LABELS, [], False),
    "selector_and_affinity_both_hold": (
        {"node_selector": {"pool": "cpu"},
         "affinity": affinity([expr(ZONE, "In", "zone-1")])},
        LABELS, [], False),
    "noschedule_untolerated": (None, {}, [GPU], False),
    "noexecute_untolerated": (None, {}, [DRAIN], False),
    "preferNoSchedule_is_soft": (None, {}, [SOFT], True),
    "equal_tolerates": ({"tolerations": [{"key": "dedicated",
                                          "operator": "Equal",
                                          "value": "gpu",
                                          "effect": "NoSchedule"}]},
                        {}, [GPU], True),
    "equal_is_the_default_operator": ({"tolerations": [
        {"key": "dedicated", "value": "gpu"}]}, {}, [GPU], True),
    "equal_other_value": ({"tolerations": [{"key": "dedicated",
                                            "operator": "Equal",
                                            "value": "tpu"}]},
                          {}, [GPU], False),
    "exists_key_any_value": ({"tolerations": [{"key": "dedicated",
                                               "operator": "Exists"}]},
                             {}, [GPU], True),
    "exists_other_key": ({"tolerations": [{"key": "drain",
                                           "operator": "Exists"}]},
                         {}, [GPU], False),
    "empty_key_exists_tolerates_all": ({"tolerations": [
        {"operator": "Exists"}]}, {}, [GPU, DRAIN], True),
    "empty_effect_matches_every_effect": ({"tolerations": [
        {"key": "drain", "operator": "Exists"}]}, {}, [DRAIN], True),
    "effect_mismatch": ({"tolerations": [{"key": "dedicated",
                                          "operator": "Exists",
                                          "effect": "NoExecute"}]},
                        {}, [GPU], False),
    "every_taint_needs_a_toleration": ({"tolerations": [
        {"key": "dedicated", "operator": "Exists"}]}, {}, [GPU, DRAIN],
        False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_feasibility(case):
    con, labels, taints, want = CASES[case]
    assert constraints.feasible(con, labels, taints) is want


@pytest.mark.parametrize("bad", [
    {"cluster": {"node_pools": [{"share": 1, "taints": [SOFT]}]}},
    {"pods": {"constraints": [{"share": 0.5, "affinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": []}}]}},
    {"pods": {"constraints": [{"share": 0.5, "affinity": affinity(
        [expr("cpu", "Gt", "4")])}]}},
    {"pods": {"constraints": [{"share": 0.7}, {"share": 0.7}]}},
    {"pods": {"constraints": [{"share": 0.5, "tolerations": [
        {"operator": "Equal", "value": "x"}]}]}},
])
def test_soft_or_unknown_forms_are_refused(bad):
    cfg = {"cluster": {}, "pods": {}}
    for k, v in bad.items():
        cfg[k].update(v)
    with pytest.raises(ValueError):
        constraints.validate(cfg)


# ---- the reference against brute force -------------------------------------

CANDIDATES = [
    None,
    {"node_selector": {"pool": "gpu"}, "tolerations": [
        {"key": "dedicated", "operator": "Exists"}]},
    {"affinity": affinity([expr(ZONE, "In", "zone-0", "zone-1")])},
    {"affinity": affinity([expr(ZONE, "NotIn", "zone-0")],
                          [expr("pool", "DoesNotExist")])},
    {"tolerations": [{"operator": "Exists"}]},
    {"node_selector": {ZONE: "zone-2"}},
]


def tiny_instance(seed: int):
    rng = np.random.default_rng(seed)
    M, T = int(rng.integers(1, 6)), int(rng.integers(1, 7))
    racks = ["r0", "r1"][:int(rng.integers(1, 3))]
    machines, labels, taints, running = [], {}, {}, {}
    for i in range(M):
        name, cap = f"m{i}", int(rng.integers(1, 4))
        machines.append((name, racks[i % len(racks)], cap))
        running[name] = int(rng.integers(0, cap + 1))
        labels[name] = {ZONE: f"zone-{int(rng.integers(0, 3))}"}
        if rng.random() < 0.4:
            labels[name]["pool"] = "gpu"
            taints[name] = [GPU]
    picks = rng.choice(len(CANDIDATES), size=int(rng.integers(1, 4)),
                       replace=False)
    pending, prefs, cons, waits = [], {}, {}, {}
    for t in range(T):
        key = f"p{t}"
        pending.append(key)
        c = CANDIDATES[int(rng.choice(picks))]
        if c is not None:
            cons[key] = c
        prefs[key] = {f"m{int(m)}": int(rng.integers(20, 200))
                      for m in rng.choice(M, size=min(M, 2), replace=False)}
        if rng.random() < 0.5:
            prefs[key][racks[0]] = int(rng.integers(10, 100))
        waits[key] = int(rng.integers(0, 4))
    return reference.RoundInput(
        round_num=seed, machines=machines, running=running,
        pending=pending, waits=waits, prefs=prefs, labels=labels,
        taints=taints, constraints=cons)


def brute_force(inp, pricing):
    """The least cost over every assignment, from the priced arcs alone:
    each pod unscheduled or on a machine its constraint admits, by its
    cheapest route there, no machine over its free seats."""
    names = [m for m, _r, _c in inp.machines]
    rack_of = {m: r for m, r, _c in inp.machines}
    pods = [(inp.waits[k], {n: w for n, w in inp.prefs[k].items()
                            if n in rack_of},
             {n: w for n, w in inp.prefs[k].items() if n not in rack_of})
            for k in inp.pending]
    prices = pricing.price(pods, [(m, r) for m, r, _c in inp.machines],
                           sorted(set(rack_of.values())),
                           [inp.running[m] for m in names])
    free = [c - inp.running[m] for m, _r, c in inp.machines]
    options = []
    for t, key in enumerate(inp.pending):
        u, w, mp, rp = prices["pods"][t]
        opts = [(None, u)]
        for i, m in enumerate(names):
            if not constraints.feasible(inp.constraints.get(key),
                                        inp.labels[m], inp.taints.get(m,
                                                                      [])):
                continue
            routes = [w + prices["cluster_to_machine"][i]]
            if m in mp:
                routes.append(mp[m])
            if rack_of[m] in rp:
                routes.append(rp[rack_of[m]] + prices["rack_to_machine"][i])
            opts.append((i, min(routes) + prices["machine_to_sink"][i]))
        options.append(opts)
    best = None
    for combo in itertools.product(*options):
        use = np.bincount([i for i, _c in combo if i is not None],
                          minlength=len(names))
        if (use > free).any():
            continue
        cost = sum(c for _i, c in combo)
        if best is None or cost < best[0]:
            best = (cost, {k: names[i] for k, (i, _c) in
                           zip(inp.pending, combo) if i is not None})
    return best


@pytest.mark.parametrize("model", ["quincy", "octopus"])
def test_reference_equals_brute_force(model):
    pricing = reference.load_pricing(BENCH / "pricing", model)
    named = 0
    for seed in range(120):
        inp = tiny_instance(seed * 2 + (model == "octopus"))
        want, decisions = brute_force(inp, pricing)
        got, placed = reference.solve(inp, pricing)
        assert got == want, (seed, inp)
        # the brute force's and the LP's decisions both cost the optimum
        for d in (decisions, placed):
            v = reference.check_round(inp, pricing, d)
            assert v.infeasible == [] and v.gap == 0, (seed, v)
        # a pod put where its constraint excludes it is named
        for key in inp.pending:
            out = [m for m, _r, _c in inp.machines
                   if not constraints.feasible(inp.constraints.get(key),
                                               inp.labels[m],
                                               inp.taints.get(m, []))]
            if out:
                cost, bad = reference.decisions_cost(inp, pricing,
                                                     {key: out[0]})
                assert cost is None
                assert f"{key} -> {out[0]}, which its constraints " \
                       f"exclude" in bad
                named += 1
                break
    assert named > 40


# ---- the committed configurations are untouched ----------------------------

SEED = 3000000301
# computed on the parent of the constraint vocabulary, by the same code
DIGESTS = {
    "quincy_1k.burst": ("quincy_1k.json", "burst.json", {
        "nodes": "6cf45bf48faf69f0", "running": "d731fefe2b13a46b",
        "node_page": "7d365b478924d71e", "pod_page": "b16a42849d98d38d",
        "arrivals": "8b88da730751122d"}),
    "quincy_1k.trickle": ("quincy_1k.json", "trickle_1pod.json", {
        "nodes": "6cf45bf48faf69f0", "running": "d731fefe2b13a46b",
        "node_page": "7d365b478924d71e", "pod_page": "b16a42849d98d38d",
        "arrivals": "e005fe4bd7a28297"}),
    "octopus_5k.poll": ("octopus_5k_poll.json", "backlog.json", {
        "nodes": "87018ea95523c79a", "running": "231c3466f07a99b9",
        "node_page": "ab6f40646ac516e1", "pod_page": "f8091a1a1e0f2c8d",
        "arrivals": "99efe78e50ff4f7a"}),
}
# the reference LP on a recorded round of each: (variables, rows, optimum)
RECORDED = {"quincy_1k.burst": (4283, 1333, 24303),
            "octopus_5k.poll": (10600, 5301, 7800)}


def sha(parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else repr(p).encode())
        h.update(b"\n")
    return h.hexdigest()[:16]


def child(cell: str) -> apiserver.Cluster:
    cfg_name, traffic_name, _ = DIGESTS[cell]
    cl = apiserver.Cluster(
        traffic_gen.load_json(BENCH / "configs" / cfg_name),
        traffic_gen.load_json(BENCH / "traffic" / traffic_name), SEED, 51.0)
    cl.populate()
    return cl


def arrivals(cl: apiserver.Cluster) -> list:
    """The window's first pods, as the child would serve them."""
    if cl.kind == "open":
        return [(j.offset_s, apiserver.Pod(s).raw)
                for j in traffic_gen.open_jobs(cl.traffic, cl.factory, 51.0,
                                               cl.seed)
                for s in j.pods]
    return [(0.0, p.raw) for p in cl.built_wave(0)]


@pytest.mark.parametrize("cell", sorted(DIGESTS))
def test_committed_configurations_serve_the_same_bytes(cell):
    cl = child(cell)
    assert all(not n.labels and not n.taints for n in cl.nodes)
    with cl.cond:
        got = {
            "nodes": sha((n.name, n.rack, n.cpu_milli, n.memory_kb, n.pods)
                         for n in cl.nodes),
            "running": sha(cl.pods[k].raw for k in cl.initial),
            "node_page": sha([cl.page("nodes", {"limit": ["500"]})]),
            "pod_page": sha([cl.page("pods", {"limit": ["500"]})]),
        }
    got["arrivals"] = sha(arrivals(cl)[:1000])
    assert got == DIGESTS[cell][2]


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_unconstrained_lp_is_unchanged(cell, monkeypatch):
    cl = child(cell)
    if cl.kind == "open":
        specs = [s for j in traffic_gen.open_jobs(cl.traffic, cl.factory,
                                                  51.0, SEED)
                 for s in j.pods][:300]
    else:
        specs = [p.spec for p in cl.built_wave(0)][:300]
    running: dict[str, int] = {}
    for k in cl.initial:
        running[cl.pods[k].node] = running.get(cl.pods[k].node, 0) + 1
    keys = [f"default/{s.name}" for s in specs]
    inp = reference.RoundInput(
        round_num=1, machines=[(n.name, n.rack, n.pods) for n in cl.nodes],
        running=running, pending=keys,
        waits={k: i % 5 for i, k in enumerate(keys)},
        prefs={k: s.prefs for k, s in zip(keys, specs)})
    seen = []
    real = scipy.optimize.linprog

    def spy(c, **kw):
        seen.append((len(c), kw["A_eq"].shape[0]))
        return real(c, **kw)

    monkeypatch.setattr(reference.scipy.optimize, "linprog", spy)
    pricing = reference.load_pricing(BENCH / "pricing",
                                     cl.cfg["cost_model"])
    opt = reference.optimum(inp, pricing)
    assert (*seen[-1], opt) == RECORDED[cell]


# ---- a constrained configuration, generated and served ---------------------

ZONED = {
    "cluster": {"nodes": 80, "machines_per_rack": 16, "max_tasks_per_pu": 10,
                "node_cpu_milli": [4000], "node_memory_kb": [33554432],
                "zones": 3,
                "node_pools": [
                    {"share": 0.25, "labels": {"pool": "gpu"},
                     "taints": [GPU]},
                    {"share": 0.75, "labels": {"pool": "general"}}]},
    "pods": {"cpu_milli": [100], "memory_kb": [512000], "machine_prefs": 2,
             "machine_pref_weight": [20, 200], "rack_pref_share": 0.3,
             "rack_pref_weight": [10, 100],
             "constraints": [
                 {"share": 0.4, "node_selector": {ZONE: "zone-0"}},
                 {"share": 0.3, "affinity": affinity(
                     [expr(ZONE, "In", "zone-1", "zone-2")])},
                 {"share": 0.2, "node_selector": {"pool": "gpu"},
                  "tolerations": [{"key": "dedicated", "operator": "Equal",
                                   "value": "gpu",
                                   "effect": "NoSchedule"}]}]},
}


def test_generator_draws_pools_zones_and_job_constraints():
    traffic_gen.check_config(ZONED)
    nodes = traffic_gen.make_nodes(ZONED, 7)
    assert sum(n.labels["pool"] == "gpu" for n in nodes) == 20
    assert all(n.taints == (GPU,) for n in nodes
               if n.labels["pool"] == "gpu")
    # a rack lies in one zone
    zone_of = {}
    for n in nodes:
        assert zone_of.setdefault(n.rack, n.labels[ZONE]) == n.labels[ZONE]
    assert sorted(set(zone_of.values())) == ["zone-0", "zone-1", "zone-2"]
    # the pools' draw is the seed's own: other seeds, other nodes
    assert [n.taints for n in nodes] != [
        n.taints for n in traffic_gen.make_nodes(ZONED, 8)]
    # one constraint per job, the same from any factory
    a = traffic_gen.PodFactory(ZONED, nodes, 7, 4)
    b = traffic_gen.PodFactory(ZONED, nodes, 7, 6)
    jobs = [f"job-{j:05d}" for j in range(400)]
    pods = [a.make(f"{j}-{k}", j) for j in jobs for k in range(3)]
    assert all(p.constraint == pods[3 * (i // 3)].constraint
               for i, p in enumerate(pods))
    assert [b.constraint_of(j) for j in jobs] == [a.constraint_of(j)
                                                  for j in jobs]
    drawn = [json.dumps(a.constraint_of(j), sort_keys=True) for j in jobs]
    shares = [drawn.count(json.dumps(c, sort_keys=True)) / len(jobs)
              for c in [{k: v for k, v in c.items() if k != "share"}
                        for c in ZONED["pods"]["constraints"]] + [None]]
    assert np.allclose(shares, [0.4, 0.3, 0.2, 0.1], atol=0.07), shares
    assert not a.make("init-0", "init-00000", with_prefs=False).constraint


def test_child_serves_the_constraints_and_counts_violations():
    cl = apiserver.Cluster(ZONED, {"kind": "open", "rate_pods_per_s": 20,
                                   "job_size": {"alpha": 1.1, "min": 1,
                                                "max": 3}},
                           seed=7, seconds=1.0)
    gpu = next(n for n in cl.nodes if n.taints)
    plain = next(n for n in cl.nodes if not n.taints)
    node = json.loads(cl.node_raw[gpu.name])
    assert node["spec"]["taints"] == [GPU]
    assert node["metadata"]["labels"] == {"rack": gpu.rack, **gpu.labels}
    assert "spec" not in json.loads(cl.node_raw[plain.name])
    con = ZONED["pods"]["constraints"][2]
    spec = traffic_gen.PodSpec("g", "g", 100, 1024, {}, {
        "node_selector": con["node_selector"],
        "tolerations": con["tolerations"]})
    free = traffic_gen.PodSpec("f", "f", 100, 1024, {})
    with cl.cond:
        g = cl.add_pod(spec, 0.0)
        f = cl.add_pod(free, 0.0)
        doc = json.loads(g.raw)["spec"]
        assert doc["nodeSelector"] == {"pool": "gpu"}
        assert doc["tolerations"] == con["tolerations"]
        assert "affinity" not in doc
        assert set(json.loads(f.raw)["spec"]) == {"containers"}
        # accepted, as a real apiserver would; the second is counted
        assert cl.bind(g.key, gpu.name)[0] == 201
        assert cl.bind(f.key, gpu.name)[0] == 201
        cl.t0 = cl.t1 = 0.0     # no window was opened
    rep = cl.report()
    assert rep["constraint_violations"] == 1
    row = next(r for r in rep["nodes"] if r[0] == gpu.name)
    assert row[3:] == [{"rack": gpu.rack, **gpu.labels}, [GPU]]
    assert next(r for r in rep["pods"] if r[0] == g.key)[13] == \
        spec.constraint


def test_waves_and_the_warm_up_split_into_jobs():
    """A wave, or a warm-up burst, of many jobs carries many of the
    configuration's constraints; by default each is one job."""
    waves = {"kind": "waves", "wave_pods": 8, "wave_jobs": 4,
             "warmup_job_pods": 3}
    cl = apiserver.Cluster(ZONED, waves, seed=7, seconds=1.0)
    pods = cl.built_wave(0)
    assert [p.spec.job for p in pods] == [f"wave-0000-j{k // 2:03d}"
                                          for k in range(8)]
    assert len({p.spec.name for p in pods}) == 8
    assert all(p.spec.constraint == cl.factory.constraint_of(p.spec.job)
               for p in pods)
    cl.warm_burst(7)
    assert [cl.pods[k].spec.job for k in cl.warm_pods] == [
        f"warm-{k // 3:05d}" for k in range(7)]
    cl = apiserver.Cluster(ZONED, {"kind": "waves", "wave_pods": 4},
                           seed=7, seconds=1.0)
    cl.warm_burst(2)
    assert {p.spec.job for p in cl.built_wave(0)} == {"wave-0000"}
    assert {cl.pods[k].spec.job for k in cl.warm_pods} == {"warm"}


@pytest.fixture(scope="module")
def zoned_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("zoned")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "traffic").mkdir()
    shutil.copytree(BENCH / "pricing", root / "benchmark" / "pricing")
    shutil.copytree(BENCH / "metrics", root / "benchmark" / "metrics")
    base = json.loads((BENCH / "configs" / "quincy_1k.json").read_text())
    cfg = dict(base, cluster=ZONED["cluster"], pods=ZONED["pods"])
    (root / "benchmark" / "configs" / "zoned.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open", "rate_pods_per_s": 150,
        "job_size": {"alpha": 1.1, "min": 1, "max": 300}, "strata": 4,
        "initial_running": 300, "occupancy_target_pods": 300,
        "warmup_pods": 400, "settle_s": 1.0}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "zoned", "source": "x",
                        "file": "benchmark/configs/zoned.json",
                        "reduced": [], "why": "x"}]
    spec["workloads"] = [{"name": "zoned.burst", "config": "zoned",
                          "traffic": "burst", "chips": 1, "why": "x"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_program_blind_to_constraints_is_not_correct(zoned_root):
    """The program places with no regard to constraints: the child counts
    the bindings that break them, and the reference finds the drawn
    rounds infeasible."""
    line = run_case(zoned_root, "zoned.burst", "", 2**31 + 7)
    assert line["correct"] is False
    assert {"constraint_violations", "round_infeasible"} <= failed(line), \
        line["checks"]
    assert line["checks"]["constraint_violations"]["value"] > 0
