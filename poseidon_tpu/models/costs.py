"""L3' cost models: pure vectorized arc-pricing functions + registry.

The reference selects a pluggable Firmament cost policy by integer flag —
``--flow_scheduling_cost_model=6`` with the comment "Load-balancing
policy" (reference deploy/poseidon.cfg:6-7, README.md:85-87); the policies
themselves live in the absent Firmament tree (SURVEY.md section 2.2), so
these are re-designs of the documented intent, not ports: Trivial,
Random, Quincy (data locality), Whare-Map (interference from samples),
CoCo (multi-dimensional co-location), Octopus (load balancing — the
selector the shipped config uses).

Each model is a pure function ``(CostInputs) -> int32[E]`` over the padded
arc table, safe under ``jax.jit`` and ``jax.vmap`` (the what-if batching
path, SURVEY.md section 2.4): recomputing costs per round is one fused
device op, not a graph rebuild. Costs are bounded to [0, COST_CAP] so the
solvers' scaled integer domains stay inside int32.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from poseidon_tpu.graph.builder import ArcKind, GraphMeta
from poseidon_tpu.graph.network import FlowNetwork, pad_bucket

# Bound on any single arc cost. With the solvers' n-scaling this keeps the
# price domain well inside int32 for clusters up to ~100k node slots.
COST_CAP = 10_000
_SCALE = 10

# Flagship-domain ceiling. The dense auction requires 2*cmax*(T+1) <
# MAX_SCALED_COST (ops/dense_auction.py overflow analysis), which at the
# flagship envelope T = 10k admits per-arc costs up to ~6.7k. Every
# structurally-unbounded input a registry model prices must therefore be
# clamped under DOMAIN_SAFE_COST, or rounds at flagship scale silently
# demote to the CPU oracle (round-3 advisor finding):
# - wait-rounds aging grows every starved round -> capped at WAIT_CAP
#   (beyond it a parked task already exerts maximum pressure); worst
#   cases quincy 5*_SCALE*(WAIT_CAP+1) = 3.05k, coco COST_CAP//4 +
#   5*_SCALE*WAIT_CAP = 5.5k;
# - quincy's task_input (summed locality weights, data-dependent) ->
#   clamped so TASK_TO_CLUSTER = total + _SCALE stays at 6k;
# - the rebalancing preemption overlay (a RUNNING task's unsched arc =
#   model unsched price + PREEMPTION_PENALTY) -> min-clamped at
#   DOMAIN_SAFE_COST inside ``_finish``.
# Genuinely pathological data (e.g. octopus with >600 running tasks on
# one machine) can still exceed the ceiling; those rounds fall back to
# the oracle loudly, which is the intended envelope behavior.
DOMAIN_SAFE_COST = 6_000
WAIT_CAP = 60

# Rebalancing overlay: what preempting (parking) a RUNNING task adds on
# top of the model's unscheduled price. High enough that preemption only
# wins when the packing is badly wrong (or capacity shrank), low enough
# to stay inside the flagship cost domain after the DOMAIN_SAFE_COST
# clamp in ``_finish``.
PREEMPTION_PENALTY = 100 * _SCALE


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CostInputs:
    """Device-resident pricing inputs, padded to static buckets.

    Per-arc arrays are aligned to the FlowNetwork's arc slots; ``task`` /
    ``machine`` / ``rack`` are clipped to 0 where not applicable so they
    are always safe gather indices — ``valid``/kind masks decide whether
    the gathered value is used.
    """

    kind: jax.Array          # int32[E] ArcKind (padding: -1)
    task: jax.Array          # int32[E] gather-safe task index
    machine: jax.Array       # int32[E] gather-safe machine index
    weight: jax.Array        # int32[E] data-locality weight
    discount: jax.Array      # int32[E] hysteresis discount
                             # (rebalancing continuation arcs; else 0)
    valid: jax.Array         # bool[E]  real (non-padding) arcs
    task_wait: jax.Array     # int32[Tp] rounds waited per task
    task_running: jax.Array  # bool[Tp] RUNNING (rebalancing) tasks —
                             # their unsched arc is the preemption price
    task_input: jax.Array    # int32[Tp] total input data units per task
    task_cpu: jax.Array      # int32[Tp] requested milli-cores
    task_mem_kb: jax.Array   # int32[Tp] requested memory
    task_usage: jax.Array    # f32[Tp] sampled cpu usage (cores)
    machine_load: jax.Array  # f32[Mp] 1 - mean idle, in [0, 1]
    machine_mem_free: jax.Array  # f32[Mp] mean free-mem fraction [0, 1]
    machine_used_slots: jax.Array  # int32[Mp] running tasks per machine


def build_cost_inputs(
    net: FlowNetwork,
    meta: GraphMeta,
    **kwargs,
) -> CostInputs:
    """Assemble padded pricing inputs and upload them to device.

    See ``build_cost_inputs_host`` for the fields; this variant is the
    convenience path for tests and one-shot solves (each ``jnp.asarray``
    is its own transfer). The production round batches the host variant
    into one ``jax.device_put`` (ops/resident.py).
    """
    host = build_cost_inputs_host(net.num_arc_slots, meta, **kwargs)
    return jax.tree_util.tree_map(jnp.asarray, host)


def build_cost_inputs_host(
    arc_slots: int,
    meta: GraphMeta,
    *,
    task_cpu_milli: np.ndarray | None = None,
    task_mem_kb: np.ndarray | None = None,
    task_usage: np.ndarray | None = None,
    machine_load: np.ndarray | None = None,
    machine_mem_free: np.ndarray | None = None,
    machine_used_slots: np.ndarray | None = None,
    t_min: int = 1,
    m_min: int = 1,
) -> CostInputs:
    """Assemble padded pricing inputs from builder metadata + KB aggregates,
    as HOST numpy arrays (no device traffic).

    The sample-derived arrays (``machine_load`` etc.) come from
    ``KnowledgeBase`` aggregates; they default to an idle, unsampled
    cluster. Shapes: per-task arrays length n_tasks, per-machine length
    n_machines (padded here).

    ``t_min``/``m_min`` are grow-only padding-bucket floors from the
    owning solver (the same anti-recompile hysteresis ``pad_topology``
    applies): without them, a pending pool draining across a bucket
    boundary shrinks the per-task input shapes and recompiles the
    whole fused chain on a round whose topology padding stayed put
    (``tests/test_obs.py::TestZeroRecompileUnderDrain`` pins the fix).
    """
    E = arc_slots
    T = len(meta.task_uids)
    M = len(meta.machine_names)
    Tp = pad_bucket(max(T, 1), minimum=t_min)
    Mp = pad_bucket(max(M, 1), minimum=m_min)

    def pad_arc(a: np.ndarray, fill: int) -> np.ndarray:
        out = np.full(E, fill, np.int32)
        out[: meta.n_arcs] = a
        return out

    def padv(a, n, dtype):
        out = np.zeros(n, dtype)
        if a is not None:
            a = np.asarray(a)
            out[: a.shape[0]] = a
        return out

    # Total input data per task = sum of its pref-arc weights (Quincy's
    # "how much data could be local" denominator).
    tin = np.zeros(Tp, np.int64)
    np.add.at(tin, np.maximum(meta.arc_task, 0),
              np.where(meta.arc_task >= 0, meta.arc_weight, 0))
    tin = np.minimum(tin, DOMAIN_SAFE_COST - _SCALE)
    return CostInputs(
        kind=pad_arc(meta.arc_kind.astype(np.int32), -1),
        task=pad_arc(np.maximum(meta.arc_task, 0), 0),
        machine=pad_arc(np.maximum(meta.arc_machine, 0), 0),
        weight=pad_arc(meta.arc_weight, 0),
        discount=pad_arc(meta.arc_discount, 0),
        valid=np.arange(E) < meta.n_arcs,
        task_wait=padv(meta.task_wait, Tp, np.int32),
        task_running=padv(meta.task_current >= 0, Tp, bool),
        task_input=tin.astype(np.int32),
        task_cpu=padv(task_cpu_milli, Tp, np.int32),
        task_mem_kb=padv(task_mem_kb, Tp, np.int32),
        task_usage=padv(task_usage, Tp, np.float32),
        machine_load=padv(machine_load, Mp, np.float32),
        machine_mem_free=(
            padv(machine_mem_free, Mp, np.float32)
            if machine_mem_free is not None else np.ones(Mp, np.float32)
        ),
        machine_used_slots=padv(machine_used_slots, Mp, np.int32),
    )


def _finish(inputs: CostInputs, cost: jax.Array) -> jax.Array:
    """Clamp to the documented domain and zero the padding slots.

    Also applies the rebalancing overlays shared by every model — the
    identity when the graph carries no running tasks / discounts, so
    place-only pricing is unchanged:

    - a RUNNING task's unscheduled arc is its preemption price (model
      unsched price + PREEMPTION_PENALTY, clamped at DOMAIN_SAFE_COST
      so the flagship dense domain holds);
    - continuation arcs subtract their hysteresis discount, so staying
      put beats migrating unless the solver finds at least that much
      improvement elsewhere.
    """
    cost = jnp.clip(cost, 0, COST_CAP).astype(jnp.int32)
    running = inputs.task_running[inputs.task]
    preempt = running & (
        inputs.kind == jnp.int32(int(ArcKind.TASK_TO_UNSCHED))
    )
    cost = jnp.where(
        preempt,
        jnp.minimum(cost + PREEMPTION_PENALTY, DOMAIN_SAFE_COST),
        cost,
    )
    cost = jnp.maximum(cost - inputs.discount, 0)
    return jnp.where(inputs.valid, cost, 0)


def _kind(inputs: CostInputs, k: ArcKind) -> jax.Array:
    return inputs.kind == jnp.int32(int(k))


# ---- the models ----

def trivial_cost(inputs: CostInputs) -> jax.Array:
    """Fixed-fee policy: schedule anywhere, mildly prefer scheduling.

    Wildcard (cluster) routing costs a small constant, leaving a task
    unscheduled a larger one; every other arc is free.
    """
    c = jnp.zeros_like(inputs.kind)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED), 5 * _SCALE, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_CLUSTER), 2 * _SCALE, c)
    return _finish(inputs, c)


def random_cost(inputs: CostInputs, seed: int = 42) -> jax.Array:
    """Deterministic pseudo-random arc costs (debug / fuzz policy)."""
    x = (inputs.kind.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + inputs.task.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + inputs.machine.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
         + jnp.uint32(seed))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    c = (x % jnp.uint32(100)).astype(jnp.int32)
    # keep unsched clearly the worst option so random still schedules
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED), COST_CAP // 2, c)
    return _finish(inputs, c)


def quincy_cost(inputs: CostInputs) -> jax.Array:
    """Data-locality policy (Quincy-style).

    A preference arc's cost is the data the task would have to fetch
    remotely if placed there (total input minus what is local at the
    target); the wildcard path assumes nothing is local; the unscheduled
    arc grows with how long the task has waited, so starvation pressure
    eventually overrides locality.
    """
    total = inputs.task_input[inputs.task]
    remote = jnp.maximum(total - inputs.weight, 0)
    c = jnp.zeros_like(inputs.kind)
    pref = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
            | _kind(inputs, ArcKind.TASK_TO_RACK))
    c = jnp.where(pref, remote, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_CLUSTER), total + _SCALE, c)
    wait = jnp.minimum(inputs.task_wait[inputs.task], WAIT_CAP)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED),
                  5 * _SCALE * (wait + 1), c)
    # crossing a rack boundary to reach the machine costs a hop
    c = jnp.where(_kind(inputs, ArcKind.RACK_TO_MACHINE), _SCALE // 2, c)
    return _finish(inputs, c)


def octopus_cost(inputs: CostInputs) -> jax.Array:
    """Load-balancing policy — the reference's shipped selector
    (deploy/poseidon.cfg:6-7): busy machines price up, so flow spreads.
    """
    load = (inputs.machine_load * 100).astype(jnp.int32)
    slots = inputs.machine_used_slots * _SCALE
    per_machine = (load + slots)[inputs.machine]
    c = jnp.zeros_like(inputs.kind)
    to_machine = (_kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
                  | _kind(inputs, ArcKind.RACK_TO_MACHINE)
                  | _kind(inputs, ArcKind.TASK_TO_MACHINE))
    c = jnp.where(to_machine, per_machine, c)
    c = jnp.where(_kind(inputs, ArcKind.MACHINE_TO_SINK), per_machine, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_CLUSTER), _SCALE, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED), COST_CAP // 4, c)
    return _finish(inputs, c)


def wharemap_cost(inputs: CostInputs) -> jax.Array:
    """Interference scoring from observed samples (Whare-Map-style).

    Prices a task onto a machine by the product of the machine's observed
    load and the task's observed hunger — co-locating a hot task on a hot
    machine is the expensive corner. Unsampled entities degrade to pure
    load balancing.
    """
    hunger = jnp.clip(inputs.task_usage[inputs.task]
                      + inputs.task_cpu[inputs.task].astype(jnp.float32)
                      / 1000.0, 0.1, 8.0)
    load = inputs.machine_load[inputs.machine]
    interf = (hunger * load * 100.0).astype(jnp.int32)
    c = jnp.zeros_like(inputs.kind)
    direct = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
              | _kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
              | _kind(inputs, ArcKind.RACK_TO_MACHINE))
    c = jnp.where(direct, interf, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_CLUSTER), 2 * _SCALE, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED), COST_CAP // 4, c)
    return _finish(inputs, c)


def coco_cost(inputs: CostInputs) -> jax.Array:
    """Multi-dimensional co-location policy (CoCo-style).

    Cost is the tightest normalized resource fit across CPU and memory:
    placing a demanding task on a machine with little headroom is
    penalized superlinearly, so the solver packs across dimensions.
    """
    cpu_req = inputs.task_cpu[inputs.task].astype(jnp.float32) / 1000.0
    mem_req = inputs.task_mem_kb[inputs.task].astype(jnp.float32)
    cpu_head = jnp.maximum(1.0 - inputs.machine_load[inputs.machine], 0.05)
    mem_head = jnp.maximum(inputs.machine_mem_free[inputs.machine], 0.05)
    fit = jnp.maximum(cpu_req / cpu_head,
                      mem_req / (mem_head * (1 << 20)))
    sq = jnp.clip(fit, 0.0, 4.0)
    score = (sq * sq * 100.0).astype(jnp.int32)
    c = jnp.zeros_like(inputs.kind)
    placing = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
               | _kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
               | _kind(inputs, ArcKind.RACK_TO_MACHINE))
    c = jnp.where(placing, score, c)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_CLUSTER), 3 * _SCALE, c)
    wait = jnp.minimum(inputs.task_wait[inputs.task], WAIT_CAP)
    c = jnp.where(_kind(inputs, ArcKind.TASK_TO_UNSCHED),
                  COST_CAP // 4 + 5 * _SCALE * wait, c)
    return _finish(inputs, c)


CostModelFn = Callable[[CostInputs], jax.Array]

# Name registry + the reference's integer selector compatibility
# (deploy/poseidon.cfg:7 selects 6, the load-balancing policy).
COST_MODELS: dict[str, CostModelFn] = {
    "trivial": trivial_cost,
    "random": random_cost,
    "quincy": quincy_cost,
    "wharemap": wharemap_cost,
    "coco": coco_cost,
    "octopus": octopus_cost,
}

COST_MODEL_SELECTORS: dict[int, str] = {
    0: "trivial",
    1: "random",
    3: "quincy",
    4: "wharemap",
    5: "coco",
    6: "octopus",
}


def resolve_cost_model_name(name_or_selector: str | int) -> str:
    """Canonical registry name for a name or the reference's integer
    flag. Digit strings count as integer selectors — the flag surface
    is stringly typed (``--flow_scheduling_cost_model=6``)."""
    if isinstance(name_or_selector, str) and name_or_selector.isdigit():
        name_or_selector = int(name_or_selector)
    if isinstance(name_or_selector, int):
        try:
            return COST_MODEL_SELECTORS[name_or_selector]
        except KeyError:
            raise KeyError(
                f"unknown cost model selector {name_or_selector}; "
                f"known: {sorted(COST_MODEL_SELECTORS)}"
            ) from None
    return name_or_selector


def get_cost_model(name_or_selector: str | int) -> CostModelFn:
    """Look up a cost model by name or by the reference's integer flag."""
    name = resolve_cost_model_name(name_or_selector)
    try:
        return COST_MODELS[name]
    except KeyError:
        raise KeyError(
            f"unknown cost model {name!r}; known: {sorted(COST_MODELS)}"
        ) from None


# ---------------------------------------------------------------------------
# per-term attribution: the explainer's API (obs/explain.py)
# ---------------------------------------------------------------------------
#
# Each model's cost is, by construction, a SUM of named terms per arc
# (locality, load, wait-aging, fixed channel fees...). The term
# functions below recompute each model with the identical expressions,
# split into those named components, and ``_overlay_terms`` applies the
# ``_finish`` overlays (domain clamp, preemption penalty, hysteresis
# discount) as explicit adjustment terms — so for every arc the term
# values sum BIT-EXACTLY to the registry model's priced output on the
# same backend (asserted by ``tests/test_explain.py`` across models,
# and by ``arc_cost_terms`` itself at call time). Float-derived
# quantities (octopus load, wharemap interference, coco fit) are kept
# as single terms: splitting them would reassociate float arithmetic
# and break the bit-exactness contract.


def _zmask(inputs: CostInputs, mask, value):
    z = jnp.zeros_like(inputs.kind)
    return jnp.where(mask, value, z)


def _trivial_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    return {
        "unsched_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_UNSCHED), 5 * _SCALE
        ),
        "wildcard_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_CLUSTER), 2 * _SCALE
        ),
    }


def _random_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    # the hash is one indivisible term (there is nothing to attribute)
    x = (inputs.kind.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + inputs.task.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + inputs.machine.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
         + jnp.uint32(42))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    c = (x % jnp.uint32(100)).astype(jnp.int32)
    c = jnp.where(
        _kind(inputs, ArcKind.TASK_TO_UNSCHED), COST_CAP // 2, c
    )
    return {"hash": c}


def _quincy_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    total = inputs.task_input[inputs.task]
    remote = jnp.maximum(total - inputs.weight, 0)
    pref = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
            | _kind(inputs, ArcKind.TASK_TO_RACK))
    cluster = _kind(inputs, ArcKind.TASK_TO_CLUSTER)
    unsched = _kind(inputs, ArcKind.TASK_TO_UNSCHED)
    wait = jnp.minimum(inputs.task_wait[inputs.task], WAIT_CAP)
    z = jnp.zeros_like(inputs.kind)
    return {
        "remote_data": jnp.where(
            pref, remote, jnp.where(cluster, total, z)
        ),
        "wildcard_base": _zmask(inputs, cluster, _SCALE),
        "wait_aging": _zmask(inputs, unsched, 5 * _SCALE * wait),
        "unsched_base": _zmask(inputs, unsched, 5 * _SCALE),
        "rack_hop": _zmask(
            inputs, _kind(inputs, ArcKind.RACK_TO_MACHINE), _SCALE // 2
        ),
    }


def _octopus_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    load = (inputs.machine_load * 100).astype(jnp.int32)
    slots = inputs.machine_used_slots * _SCALE
    routed = (_kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
              | _kind(inputs, ArcKind.RACK_TO_MACHINE)
              | _kind(inputs, ArcKind.TASK_TO_MACHINE)
              | _kind(inputs, ArcKind.MACHINE_TO_SINK))
    return {
        "machine_load": _zmask(inputs, routed, load[inputs.machine]),
        "used_slots": _zmask(inputs, routed, slots[inputs.machine]),
        "wildcard_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_CLUSTER), _SCALE
        ),
        "unsched_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_UNSCHED),
            COST_CAP // 4,
        ),
    }


def _wharemap_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    hunger = jnp.clip(inputs.task_usage[inputs.task]
                      + inputs.task_cpu[inputs.task].astype(jnp.float32)
                      / 1000.0, 0.1, 8.0)
    load = inputs.machine_load[inputs.machine]
    interf = (hunger * load * 100.0).astype(jnp.int32)
    direct = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
              | _kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
              | _kind(inputs, ArcKind.RACK_TO_MACHINE))
    return {
        "interference": _zmask(inputs, direct, interf),
        "wildcard_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_CLUSTER), 2 * _SCALE
        ),
        "unsched_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_UNSCHED),
            COST_CAP // 4,
        ),
    }


def _coco_terms(inputs: CostInputs) -> dict[str, jax.Array]:
    cpu_req = inputs.task_cpu[inputs.task].astype(jnp.float32) / 1000.0
    mem_req = inputs.task_mem_kb[inputs.task].astype(jnp.float32)
    cpu_head = jnp.maximum(1.0 - inputs.machine_load[inputs.machine], 0.05)
    mem_head = jnp.maximum(inputs.machine_mem_free[inputs.machine], 0.05)
    fit = jnp.maximum(cpu_req / cpu_head,
                      mem_req / (mem_head * (1 << 20)))
    sq = jnp.clip(fit, 0.0, 4.0)
    score = (sq * sq * 100.0).astype(jnp.int32)
    placing = (_kind(inputs, ArcKind.TASK_TO_MACHINE)
               | _kind(inputs, ArcKind.CLUSTER_TO_MACHINE)
               | _kind(inputs, ArcKind.RACK_TO_MACHINE))
    unsched = _kind(inputs, ArcKind.TASK_TO_UNSCHED)
    wait = jnp.minimum(inputs.task_wait[inputs.task], WAIT_CAP)
    return {
        "resource_fit": _zmask(inputs, placing, score),
        "wildcard_base": _zmask(
            inputs, _kind(inputs, ArcKind.TASK_TO_CLUSTER), 3 * _SCALE
        ),
        "wait_aging": _zmask(inputs, unsched, 5 * _SCALE * wait),
        "unsched_base": _zmask(inputs, unsched, COST_CAP // 4),
    }


COST_TERM_FNS: dict[str, Callable[[CostInputs], dict[str, jax.Array]]] = {
    "trivial": _trivial_terms,
    "random": _random_terms,
    "quincy": _quincy_terms,
    "wharemap": _wharemap_terms,
    "coco": _coco_terms,
    "octopus": _octopus_terms,
}


def _overlay_terms(
    inputs: CostInputs, terms: dict[str, jax.Array]
) -> dict[str, jax.Array]:
    """Apply the shared ``_finish`` overlays as explicit adjustment
    terms, so the returned dict sums to the model's final arc cost on
    every slot (padding slots included — everything masks to 0)."""
    raw = None
    for v in terms.values():
        raw = v if raw is None else raw + v
    clipped = jnp.clip(raw, 0, COST_CAP).astype(jnp.int32)
    running = inputs.task_running[inputs.task]
    preempt = running & (
        inputs.kind == jnp.int32(int(ArcKind.TASK_TO_UNSCHED))
    )
    after_pre = jnp.where(
        preempt,
        jnp.minimum(clipped + PREEMPTION_PENALTY, DOMAIN_SAFE_COST),
        clipped,
    )
    after_disc = jnp.maximum(after_pre - inputs.discount, 0)
    out = dict(terms)
    out["domain_clamp"] = clipped - raw
    out["preemption_penalty"] = after_pre - clipped
    out["hysteresis_discount"] = after_disc - after_pre
    return {
        k: jnp.where(inputs.valid, v, 0) for k, v in out.items()
    }


def arc_cost_terms(
    name_or_selector: str | int, inputs: CostInputs
) -> dict[str, np.ndarray]:
    """Named per-arc cost terms for a registry model, as HOST arrays.

    The returned ``{term_name: int32[E]}`` values sum bit-exactly to
    ``get_cost_model(name)(inputs)`` on the same backend — verified at
    call time (a mismatch raises, so the explainer can never report a
    breakdown that does not add up to the solver's arc cost). Zero-
    everywhere terms are kept: consumers drop them per decision."""
    name = resolve_cost_model_name(name_or_selector)
    try:
        raw_fn = COST_TERM_FNS[name]
    except KeyError:
        raise KeyError(
            f"no term attribution for cost model {name!r}; "
            f"known: {sorted(COST_TERM_FNS)}"
        ) from None
    terms_dev = _overlay_terms(inputs, raw_fn(inputs))
    total_dev = COST_MODELS[name](inputs)
    host = jax.device_get((terms_dev, total_dev))
    terms = {k: np.asarray(v, np.int32) for k, v in host[0].items()}
    total = np.asarray(host[1], np.int32)
    acc = np.zeros_like(total, np.int64)
    for v in terms.values():
        acc += v
    if not np.array_equal(acc, total.astype(np.int64)):
        bad = int(np.flatnonzero(acc != total)[0])
        raise AssertionError(
            f"term breakdown for model {name!r} does not sum to the "
            f"priced arc cost (first mismatch at arc {bad}: "
            f"{int(acc[bad])} != {int(total[bad])})"
        )
    return terms
