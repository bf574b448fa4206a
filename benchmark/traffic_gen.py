"""The benchmark's one general traffic generator: stdlib and numpy only.

It reads a configuration file (the cluster's shape) and a traffic file
(the mix), both plain JSON, and turns them into nodes, the pods running
at the start, and the schedule of arrivals. Every number comes from the
seed. Node and pod shapes are a copy of ``poseidon_tpu.synth.
make_synthetic_cluster`` (rack-interleaved nodes, rack-affine machine
preferences with weights 20-200, a rack preference on 30% of the pods
with weight 10-100): copied, not imported, because the program's package
imports JAX and the apiserver child must not.

Two kinds of traffic, chosen by the file's ``kind``:

- ``open``: jobs arrive on a schedule fixed before the run, whatever the
  scheduler does (an open loop). Inter-arrival gaps are exponential and
  job sizes a bounded Pareto. Both are taken as the quantiles of their
  distribution and shuffled by the seed, so every seed offers the same
  set of jobs and gaps in another order (the largest jobs spread evenly
  over the window, see ``stratified_order``): the seed changes the order of
  the work, never its amount. Each pod runs for an exponential lifetime
  (again a shuffled set of quantiles) from the moment its binding
  lands, then it is deleted.
- ``waves``: a closed loop. A wave of ``wave_pods`` pods is created at
  once, and at the same moment the ``wave_pods`` oldest running pods are
  deleted; the next wave comes when every pod of the previous one is
  bound. A wave is one job, or ``wave_jobs`` jobs of equal size.

Placement constraints are optional (``constraints.py`` has their
semantics): ``cluster.zones`` puts each rack (each node, where there are
no racks) in one of that many zones, interleaved as racks are;
``cluster.node_pools`` labels and taints the nodes, each pool on its
``share`` of them; ``pods.constraints`` gives each job one of its
entries with probability ``share`` (no constraint for the rest), as a
Deployment or Job template does for all its pods. Their draws come from
streams of their own, so a configuration without them draws exactly
what it did before they existed.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

import constraints

ZONE_LABEL = "topology.kubernetes.io/zone"
# rng_for streams: 1 nodes, 2 initial placement, 3 open jobs, 4 and 6 pod
# factories, 5 lifetimes, 7 node pools, 8 (with the job's name) a job's
# constraint
POOL_STREAM = 7
CONSTRAINT_STREAM = 8


def load_json(path: str | pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose, all from the one seed
    (any whole number; negative seeds fold into 64 bits)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


# ---- distributions, as quantile sets ---------------------------------

def bounded_pareto_quantile(u: np.ndarray, alpha: float, lo: float,
                            hi: float) -> np.ndarray:
    """Inverse CDF of the Pareto(alpha) law cut to [lo, hi]."""
    r = (lo / hi) ** alpha
    return lo / (1.0 - u * (1.0 - r)) ** (1.0 / alpha)


def bounded_pareto_mean(alpha: float, lo: float, hi: float) -> float:
    r = (lo / hi) ** alpha
    return (alpha * lo ** alpha / (1.0 - r) / (alpha - 1.0)
            * (lo ** (1.0 - alpha) - hi ** (1.0 - alpha)))


def quantiles(n: int) -> np.ndarray:
    """The n mid-point probabilities (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / max(n, 1)


def exponential_set(n: int, mean: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """n exponential quantiles of the given mean, shuffled."""
    out = -np.log1p(-quantiles(n)) * mean
    rng.shuffle(out)
    return out


# ---- the cluster -------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Node:
    name: str
    rack: str
    cpu_milli: int
    memory_kb: int
    pods: int
    # labels besides the rack's, and taints: empty unless configured
    labels: dict[str, str] = dataclasses.field(default_factory=dict,
                                               hash=False)
    taints: tuple[dict, ...] = dataclasses.field(default=(), hash=False)

    @property
    def all_labels(self) -> dict[str, str]:
        """Every label the node object carries."""
        return {**({"rack": self.rack} if self.rack else {}), **self.labels}


@dataclasses.dataclass
class PodSpec:
    name: str
    job: str
    cpu_milli: int
    memory_kb: int
    prefs: dict[str, int]
    constraint: dict | None = None    # constraints.py's shape


def make_nodes(cfg: dict, seed: int) -> list[Node]:
    c = cfg["cluster"]
    rng = rng_for(seed, 1)
    n = int(c["nodes"])
    per_rack = int(c.get("machines_per_rack", 0))
    n_racks = max(1, -(-n // per_rack)) if per_rack else 0
    cpu = rng.choice(np.asarray(c["node_cpu_milli"]), size=n)
    mem = rng.choice(np.asarray(c["node_memory_kb"]), size=n)
    pool = node_pools(c, n, seed)
    pools = c.get("node_pools", [])
    zones = int(c.get("zones", 0))
    nodes = []
    for i in range(n):
        labels: dict[str, str] = {}
        if zones:   # a rack lies in one zone
            z = (i % n_racks if n_racks else i) % zones
            labels[ZONE_LABEL] = f"zone-{z}"
        taints: tuple[dict, ...] = ()
        if pool[i] >= 0:
            labels.update(pools[pool[i]].get("labels", {}))
            taints = tuple(pools[pool[i]].get("taints", []))
        nodes.append(Node(
            name=f"m{i:05d}",
            rack=f"rack{i % n_racks:03d}" if n_racks else "",
            cpu_milli=int(cpu[i]),
            memory_kb=int(mem[i]),
            pods=int(c["max_tasks_per_pu"]),
            labels=labels,
            taints=taints,
        ))
    return nodes


def node_pools(cluster: dict, n: int, seed: int) -> np.ndarray:
    """Each node's pool (-1: none): pool k on round(share_k x n) nodes
    (largest remainders), the nodes picked by the seed."""
    pools = cluster.get("node_pools", [])
    out = np.full(n, -1)
    if not pools:
        return out
    want = np.asarray([float(p["share"]) for p in pools]) * n
    count = np.floor(want).astype(np.int64)
    left = min(n, int(round(want.sum()))) - int(count.sum())
    count[np.argsort(-(want - count), kind="stable")[:max(left, 0)]] += 1
    order = rng_for(seed, POOL_STREAM).permutation(n)
    out[order[:int(count.sum())]] = np.repeat(np.arange(len(pools)), count)
    return out


class PodFactory:
    """Draws pod specs from the configuration's pod template."""

    def __init__(self, cfg: dict, nodes: list[Node], seed: int,
                 stream: int):
        self.p = cfg["pods"]
        self.seed = seed
        self.rng = rng_for(seed, stream)
        self.job_constraint: dict[str, dict | None] = {}
        racks = sorted({n.rack for n in nodes if n.rack})
        self.racks = racks
        by_rack: dict[str, list[str]] = {r: [] for r in racks}
        for n in nodes:
            if n.rack:
                by_rack[n.rack].append(n.name)
        self.by_rack = by_rack

    def constraint_of(self, job: str) -> dict | None:
        """The job's constraint, drawn from the seed and the job's name
        alone, so it is the same whichever factory or thread asks."""
        table = self.p.get("constraints")
        if not table:
            return None
        if job not in self.job_constraint:
            u = np.random.default_rng([int(self.seed) % (1 << 64),
                                       CONSTRAINT_STREAM,
                                       *job.encode()]).random()
            edges = np.cumsum([float(c["share"]) for c in table])
            k = int(np.searchsorted(edges, u, side="right"))
            self.job_constraint[job] = None if k == len(table) else {
                key: table[k][key] for key in constraints.KEYS
                if key in table[k]}
        return self.job_constraint[job]

    def make(self, name: str, job: str, with_prefs: bool = True
             ) -> PodSpec:
        """A pod of ``job``; ``with_prefs`` False: a pod running from
        before the run, with no preferences and no constraint."""
        p, rng = self.p, self.rng
        cpu = int(rng.choice(np.asarray(p["cpu_milli"])))
        mem = int(rng.choice(np.asarray(p["memory_kb"])))
        prefs: dict[str, int] = {}
        k = int(p.get("machine_prefs", 0))
        if with_prefs and (k or p.get("rack_pref_share", 0)) and self.racks:
            # rack-affine preferences: most of a pod's input lives in
            # one rack, so its preferred machines (and its one rack
            # preference) do too
            home = self.racks[int(rng.integers(0, len(self.racks)))]
            members = self.by_rack[home]
            lo, hi = p["machine_pref_weight"]
            for m in rng.choice(len(members), size=min(k, len(members)),
                                replace=False):
                prefs[members[int(m)]] = int(rng.integers(lo, hi))
            if rng.random() < float(p.get("rack_pref_share", 0)):
                lo, hi = p["rack_pref_weight"]
                prefs[home] = int(rng.integers(lo, hi))
        return PodSpec(name, job, cpu, mem, prefs,
                       self.constraint_of(job) if with_prefs else None)


def initial_placement(nodes: list[Node], n_running: int, seed: int
                      ) -> list[str]:
    """Node of each pod running at the start: seats drawn uniformly
    without replacement, so no node is over its capacity."""
    seats = np.repeat(np.arange(len(nodes)), [n.pods for n in nodes])
    if n_running > len(seats):
        raise ValueError(
            f"{n_running} running pods do not fit {len(seats)} seats")
    pick = rng_for(seed, 2).choice(len(seats), size=n_running,
                                   replace=False)
    return [nodes[int(seats[i])].name for i in pick]


# ---- the open-loop schedule --------------------------------------------

@dataclasses.dataclass
class Job:
    offset_s: float          # due time, from the start of the window
    pods: list[PodSpec]


def stratified_order(sizes: np.ndarray, strata: int,
                     rng: np.random.Generator) -> np.ndarray:
    """An arrival order of the jobs: the ``strata`` largest fall one to
    each of ``strata`` equal stretches of the order, at its middle; the
    next ``strata`` likewise, and so on, each at a place inside its
    stretch that comes from the seed. So the largest jobs arrive as far
    apart on every seed, never on top of one another on one seed and
    close on the next, and each stretch of the window carries the same
    work. ``strata`` 1 is a plain shuffle."""
    rank = np.argsort(-sizes, kind="stable")
    key = np.empty(sizes.size)
    for g in range(0, sizes.size, strata):
        idx = rank[g:g + strata]
        place = np.full(idx.size, 0.5) if g == 0 and strata > 1 \
            else rng.random(idx.size)
        key[idx] = rng.permutation(strata)[:idx.size] + place
    return np.argsort(key, kind="stable")


def open_jobs(traffic: dict, factory: PodFactory, seconds: float,
              seed: int) -> list[Job]:
    """The window's jobs, sorted by due time."""
    rate = float(traffic["rate_pods_per_s"])
    js = traffic["job_size"]
    alpha, lo, hi = float(js["alpha"]), float(js["min"]), float(js["max"])
    n_jobs = max(1, round(rate * seconds
                          / bounded_pareto_mean(alpha, lo, hi)))
    rng = rng_for(seed, 3)
    sizes = np.ceil(bounded_pareto_quantile(quantiles(n_jobs), alpha, lo,
                                            hi)).astype(np.int64)
    sizes = sizes[stratified_order(sizes, int(traffic.get("strata", 1)),
                                   rng)]
    gaps = exponential_set(n_jobs, seconds / n_jobs, rng)
    t = np.cumsum(gaps) - gaps[0]
    jobs = []
    for j in range(n_jobs):
        job = f"job-{j:05d}"
        jobs.append(Job(float(t[j]), [
            factory.make(f"{job}-{k:04d}", job) for k in range(sizes[j])
        ]))
    return jobs


def lifetime_mean_s(traffic: dict) -> float:
    """Little's law: occupancy = rate x lifetime."""
    return (float(traffic["occupancy_target_pods"])
            / float(traffic["rate_pods_per_s"]))


def check_config(cfg: dict) -> None:
    """Refuse a configuration the generator cannot honour."""
    c = cfg["cluster"]
    for key in ("nodes", "max_tasks_per_pu", "node_cpu_milli",
                "node_memory_kb"):
        if key not in c:
            raise ValueError(f"configuration lacks cluster.{key}")
    if int(c["nodes"]) < 1:
        raise ValueError("cluster.nodes must be a positive count")
    constraints.validate(cfg)
