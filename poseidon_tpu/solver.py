"""The solver front door: priced flow network in, placements-ready flows out.

This is the seam where the reference shells out to an external MCMF
binary per scheduling round (``--flow_scheduling_solver`` /
``--flow_scheduling_binary``, reference deploy/poseidon.cfg:8-10, invoked
from Firmament inside ``ScheduleAllJobs``, reference
src/firmament/scheduler_bridge.cc:170-172). Here the same seam dispatches
to the TPU dense-auction kernel, with two honest fallbacks:

- a graph that does not match the builder taxonomy (hand-written DIMACS,
  exotic topologies) cannot use the transportation form — it solves on
  the C++ CPU oracle instead;
- the auction certifies its own exactness (primal-dual gap < scale); if
  certification fails (adversarial tie structures can exhaust the round
  fuse), the solve re-runs on the oracle. No silent wrong answers.

The returned ``SolveOutcome.state`` is the device-resident warm handle:
pass it back as ``warm`` on the next round over the same cluster shape
and the solve skips the eps ladder entirely — the TPU-native equivalent of the reference's ``--run_incremental_scheduler``
mode (deploy/poseidon.cfg:12).
"""

from __future__ import annotations

import dataclasses
import logging
import time
import weakref

import numpy as np

from poseidon_tpu.graph.builder import GraphMeta
from poseidon_tpu.graph.network import FlowNetwork
from poseidon_tpu.ops.dense_auction import (
    CostDomainTooLarge,
    DenseMemoryTooLarge,
    DenseState,
    solve_transport_dense,
)
from poseidon_tpu.ops.transport import (
    NotSchedulingShaped,
    TransportInstance,
    TransportTopology,
    extract_topology,
    flows_from_assignment,
    instance_from_topology,
)

log = logging.getLogger(__name__)

# Small-instance dispatch thresholds. Below this size the TPU launch
# and sync cost is expected to exceed the whole subprocess-oracle solve
# (a 100-task solve is ~1 round of real work). The crossover has not
# been measured on an attached chip (ROADMAP C10); the bounds are
# conservative.
SMALL_INSTANCE_TASKS = 256
SMALL_INSTANCE_MACHINES = 64


def is_small_instance(n_tasks: int, n_machines: int) -> bool:
    """True when the subprocess oracle beats the TPU launch floor
    (shared by the front door and the resident solver)."""
    return (
        0 < n_tasks <= SMALL_INSTANCE_TASKS
        and n_machines <= SMALL_INSTANCE_MACHINES
    )


@dataclasses.dataclass(frozen=True)
class SolveOutcome:
    """Result of one scheduling solve, whatever backend produced it."""

    flows: np.ndarray        # int32 per-arc flows over the real arcs
    cost: int                # exact integer objective
    backend: str             # "dense_auction" | "oracle"
    exact: bool              # certified optimal (always True on return)
    solve_ms: float          # wall time of the successful solve
    state: DenseState | None  # warm handle for the next round (TPU path)
    instance: TransportInstance | None
    # per-task machine index (or -1) when the backend produced an
    # assignment directly — lets callers skip flow decomposition
    # entirely (the general path-peeling costs ~130 ms at the flagship
    # scale; the auction already knows every task's machine)
    assignment: np.ndarray | None = None


def assignment_from_outcome(
    outcome: SolveOutcome, meta: GraphMeta, net: FlowNetwork
) -> np.ndarray:
    """Per-task machine indices (-1 = unscheduled) for any outcome.

    This is the delta extractor's input (``graph.deltas
    .extract_deltas``): backends that assign directly (the dense
    auction) return it as-is; flow-only backends (oracle fallbacks, the
    general lane) decompose their flows into placements first.
    """
    if outcome.assignment is not None:
        return np.asarray(outcome.assignment, np.int32)
    from poseidon_tpu.graph.decompose import extract_placements

    host = net.to_host()
    placements = extract_placements(
        np.asarray(outcome.flows, np.int64), meta,
        host["src"], host["dst"],
    )
    midx = {name: i for i, name in enumerate(meta.machine_names)}
    asg = np.full(len(meta.task_uids), -1, np.int32)
    for i, uid in enumerate(meta.task_uids):
        m = placements.get(uid)
        if m is not None:
            asg[i] = midx[m]
    return asg


# Topology cache: repeated solves over the SAME GraphMeta object (what-
# if sweeps, repeated test solves, warm re-solves over an unchanged
# graph) skip the O(arcs) taxonomy re-validation — only the cost refill
# (``instance_from_topology``, pure vectorized numpy) runs per call.
# Keyed by id(meta) with a weakref finalizer so entries die with their
# meta (GraphMeta holds ndarrays and is not hashable).
_TOPO_CACHE: dict[int, TransportTopology] = {}


def _topology_for(net: FlowNetwork, meta: GraphMeta):
    """(topology, host arrays) for a priced net — cached per meta."""
    if int(net.n_arcs) != int(meta.n_arcs) or int(net.n_nodes) != int(
        meta.n_nodes
    ):
        raise NotSchedulingShaped(
            f"network ({net.n_nodes} nodes / {net.n_arcs} arcs) does "
            f"not match the builder metadata ({meta.n_nodes} / "
            f"{meta.n_arcs})"
        )
    host = net.to_host()
    cached = _TOPO_CACHE.get(id(meta))
    if cached is not None:
        # capacities live in the NET, not the meta: refill the
        # cap-derived fields from this call's arc table so a re-solve
        # over the same meta with changed caps is not answered from a
        # stale skeleton. The cheap parallel-cap consistency rule
        # (cluster->machine and rack->machine caps mirror the
        # machine->sink slots) is re-checked; a mismatch means the
        # caller mutated caps outside the taxonomy — fall through to
        # the full validating extraction (which raises).
        cap = np.asarray(host["cap"], np.int64)
        slots = cap[cached.arc_m2s].astype(np.int32)
        r2m_ok = cached.arc_r2m >= 0
        if (cap[cached.arc_c2m] == slots).all() and (
            cap[cached.arc_r2m[r2m_ok]] == slots[r2m_ok]
        ).all():
            return dataclasses.replace(
                cached,
                slots=slots,
                job_sink_cap=cap[cached.arc_job_sink],
            ), host
        _TOPO_CACHE.pop(id(meta), None)
    topo = extract_topology(meta, host["src"], host["dst"], host["cap"])
    _TOPO_CACHE[id(meta)] = topo
    weakref.finalize(meta, _TOPO_CACHE.pop, id(meta), None)
    return topo, host


def solve_scheduling(
    net: FlowNetwork,
    meta: GraphMeta,
    *,
    warm: DenseState | None = None,
    oracle_fallback: bool = True,
    oracle_timeout_s: float = 1000.0,
    small_to_oracle: bool = True,
    topology: TransportTopology | None = None,
) -> SolveOutcome:
    """Solve a priced scheduling network exactly; prefer the TPU kernel.

    ``warm`` is a previous round's ``SolveOutcome.state`` over the same
    padded shapes — prices and assignments carry over on-device and the
    solve re-settles at eps = 1 (the incremental path). Shape changes
    (cluster grew past a padding bucket) silently fall back to a cold
    solve.

    ``small_to_oracle`` lets the dispatcher route instances under the
    SMALL_INSTANCE_* thresholds straight to the subprocess oracle, where
    the TPU per-launch floor exceeds the whole CPU solve. Differential
    tests that specifically exercise the dense kernel pass False.

    ``topology`` (optional) is a pre-derived transport skeleton (e.g.
    ``topology_from_columns`` from the incremental builder) — passing
    it skips the O(arcs) taxonomy validation; repeated calls over the
    same ``meta`` object hit an internal topology cache either way.

    Error surface: with ``oracle_fallback=False``, kernel-envelope
    guards re-raise their typed exceptions (``CostDomainTooLarge``,
    ``DenseMemoryTooLarge``, ``ValueError``), while a solve that runs
    but cannot certify — the dense auction exhausting its round fuse,
    or the general-graph backend failing its guards — surfaces
    ``RuntimeError`` (NOT ``NotSchedulingShaped``: a non-taxonomy graph
    routes to the general JAX backend, not to an exception).
    """
    t0 = time.perf_counter()
    # size dispatch BEFORE extraction: meta alone names the instance
    # size, and paying even the (cheap) transportation extract on a
    # path whose whole point is "the oracle solves this faster than
    # any device overhead" would hand the comparison back
    if (
        small_to_oracle
        and oracle_fallback
        and warm is None
        and is_small_instance(
            len(meta.task_uids), len(meta.machine_names)
        )
    ):
        return _solve_on_oracle(
            net, t0, why="small-instance", timeout_s=oracle_timeout_s
        )
    try:
        if topology is not None:
            host = net.to_host()
        else:
            topology, host = _topology_for(net, meta)
        inst = instance_from_topology(topology, host["cost"])
    except NotSchedulingShaped:
        return _solve_general(
            net, t0, oracle_fallback=oracle_fallback,
            timeout_s=oracle_timeout_s,
        )

    try:
        res, state = solve_transport_dense(inst, warm=warm)
    except CostDomainTooLarge:
        if not oracle_fallback:
            raise
        return _solve_on_oracle(net, t0, why="cost-domain", timeout_s=oracle_timeout_s)
    except DenseMemoryTooLarge:
        # the [Tp, Mp] table would blow the HBM budget: degrade loudly
        # (the guard, not an OOM, decides) — same seam as cost-domain
        log.warning(
            "instance %dx%d exceeds the dense HBM budget; degrading "
            "to oracle", inst.n_tasks, inst.n_machines,
        )
        if not oracle_fallback:
            raise
        return _solve_on_oracle(net, t0, why="memory-envelope", timeout_s=oracle_timeout_s)
    except ValueError:
        # defensive: an instance outside the kernel's envelope (e.g.
        # negative costs from a custom model) must degrade, not crash —
        # but loudly, so a masked kernel regression stays discoverable
        log.exception(
            "dense kernel rejected the instance; degrading to oracle"
        )
        if not oracle_fallback:
            raise
        return _solve_on_oracle(net, t0, why="kernel-envelope", timeout_s=oracle_timeout_s)
    if not res.converged and warm is not None:
        # a stale warm start can strand the eps=1 settle; retry cold
        res, state = solve_transport_dense(inst, warm=None)
    if res.converged:
        flows = flows_from_assignment(inst, res, int(net.n_arcs))
        return SolveOutcome(
            flows=flows,
            cost=res.cost,
            backend="dense_auction",
            exact=True,
            solve_ms=(time.perf_counter() - t0) * 1000,
            state=state,
            instance=inst,
            assignment=res.assignment,
        )
    if not oracle_fallback:
        raise RuntimeError(
            f"dense auction did not certify (gap still open after "
            f"{res.rounds} rounds) and oracle fallback is disabled"
        )
    return _solve_on_oracle(net, t0, why="uncertified", timeout_s=oracle_timeout_s)


def _solve_general(
    net: FlowNetwork, t0: float, *, oracle_fallback: bool,
    timeout_s: float,
) -> SolveOutcome:
    """Non-taxonomy graphs (hand-written DIMACS, exotic topologies):
    the exact general-graph JAX backend (``ops/cost_scaling``, the
    device-side cs2 analog), with the C++ oracle only on its guards —
    the int32 excess-wrap precheck, a blown sweep fuse, or an instance
    the forcing-arc construction reports capacity-infeasible. The
    reference solves every graph through the same external-solver seam
    (scheduler_bridge.cc:170-172); this is that seam's general lane.
    """
    import jax

    from poseidon_tpu.ops.cost_scaling import (
        solve_cost_scaling,
        solution_cost,
    )

    guard_err: ValueError | None = None
    try:
        res = solve_cost_scaling(net)
        conv, feas = jax.device_get((res.converged, res.feasible))
        if bool(conv) and bool(feas):
            return SolveOutcome(
                flows=np.asarray(jax.device_get(res.flows), np.int32),
                cost=solution_cost(net, res),
                backend="cost_scaling",
                exact=True,
                solve_ms=(time.perf_counter() - t0) * 1000,
                state=None,
                instance=None,
            )
        why = "general-unconverged" if not bool(conv) else "general-infeasible"
    except ValueError as e:
        # the excess-wrap precheck (capacities too large for the int32
        # accumulators) — a documented guard, not a kernel bug
        log.warning("general JAX backend rejected the graph: %s", e)
        guard_err = e
        why = "general-guard"
    if not oracle_fallback:
        # chain the guard's ValueError so the RuntimeError's traceback
        # names WHICH precheck tripped (ADVICE round 5)
        raise RuntimeError(
            f"general JAX solve failed ({why}) and oracle fallback is "
            f"disabled"
        ) from guard_err
    return _solve_on_oracle(net, t0, why=why, timeout_s=timeout_s)


def _solve_on_oracle(
    net: FlowNetwork, t0: float, why: str, timeout_s: float = 1000.0
) -> SolveOutcome:
    from poseidon_tpu.oracle import solve_oracle

    o = solve_oracle(net, algorithm="cost_scaling", timeout_s=timeout_s)
    return SolveOutcome(
        flows=np.asarray(o.flows, np.int32),
        cost=int(o.cost),
        backend=f"oracle:{why}",
        exact=True,
        solve_ms=(time.perf_counter() - t0) * 1000,
        state=None,
        instance=None,
    )
