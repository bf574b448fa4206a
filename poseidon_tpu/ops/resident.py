"""Device-resident scheduling rounds: one upload in, one download out.

This is the TPU-native replacement for the reference's graph-change
batching seam (``--only_read_assignment_changes`` /
``--remove_duplicate_changes`` / ``--merge_changes_to_same_arc``,
reference deploy/poseidon.cfg:12-19): where the reference amortizes
re-serializing its flow graph to a solver subprocess by batching graph
*changes*, here the whole price->densify->solve chain is device-side, so
there is nothing to re-ship in the first place.

Round-3 postmortem (VERDICT.md): the previous hot path priced arcs ON
device, downloaded them (`net.to_host()`), rebuilt the dense instance on
host, and re-uploaded it — 5+ host<->device crossings per round. The
resident round
does exactly ONE batched ``jax.device_put`` (pricing inputs + topology
index maps), ONE fused compiled program (``_resident_chain``: cost
model → densify → eps-ladder auction → channel/objective finalize),
and ONE batched ``jax.device_get`` (assignment + certificate). That
single readback is the round's one unavoidable host sync.

The scale lane (PR 6) composes two attacks onto the same fused chain:
**equivalence-class aggregation** (graph/aggregate.py) collapses the
machine axis to one column per cost-equivalence class before densify —
the plan is computed host-side from the cost model's per-machine INPUT
signature, so no pricing sync is needed — and the fetched assignment
expands back to real machines in finish_round (current placements
preserved); **sharded resident rounds** (parallel/) lay the round's one
batched upload out task-sharded over a ``--mesh_width`` device mesh, so
the dense table, bid windows and seat sorts are Tp/width rows per
device and HBM/compute scale with mesh width. Both are exact: class
members are interchangeable by construction and the SPMD program
computes the same function bit-for-bit (tests/test_aggregate.py,
tests/test_scale.py).

Fallbacks: a cost table outside the auction's integer domain (checked
on device, read back with the result batch), a dense table beyond the
HBM budget, or an uncertified solve degrades to the C++ CPU oracle —
one extra download of the priced arc table, only on the rare round
that needs it. One deliberate divergence from ``solve_scheduling``:
non-taxonomy graphs go straight to the oracle here rather than the
general JAX backend, because the resident path's whole value — the
round's state kept on HBM for the lanes that patch it between rounds —
does not exist for them (the front door owns the general-graph JAX
lane).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from poseidon_tpu.guards import (
    FetchTimeout,
    no_implicit_transfers,
    sanctioned_transfer,
)
from poseidon_tpu.graph.aggregate import (
    aggregate_topology,
    expand_assignment,
    plan_from_signatures,
    prune_topology_prefs,
)
from poseidon_tpu.graph.builder import ArcKind, GraphMeta
from poseidon_tpu.graph.network import FlowNetwork, pad_bucket
from poseidon_tpu.models import get_cost_model
from poseidon_tpu.models.costs import (
    COST_MODEL_SELECTORS,
    build_cost_inputs_host,
)
from poseidon_tpu.obs.spans import span
from poseidon_tpu.ops.dense_auction import (
    I32,
    INF,
    MAX_SCALED_COST,
    DenseInstance,
    DenseMemoryTooLarge,
    DenseState,
    _budget_need,
    _densify,
    _solve,
    check_table_budget,
    cold_start,
    default_fuse,
)
from poseidon_tpu.ops.transport import (
    CH_CLUSTER,
    CH_PREF,
    CH_UNSCHED,
    NotSchedulingShaped,
    TransportTopology,
    extract_topology,
    instance_from_topology,
)

log = logging.getLogger(__name__)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseTopology:
    """Padded device copy of the TransportTopology index maps.

    Index value -1 marks padding / absent arcs; gathers clip and mask.
    ``n_tasks`` is a traced scalar so one compiled program serves every
    round within a (Tp, Mp, P) bucket.
    """

    arc_unsched: jax.Array   # i32[Tp]
    arc_cluster: jax.Array   # i32[Tp]
    arc_u2s: jax.Array       # i32[Tp]
    arc_pref: jax.Array      # i32[Tp, P]
    pref_machine: jax.Array  # i32[Tp, P]
    pref_rack: jax.Array     # i32[Tp, P]
    arc_c2m: jax.Array       # i32[Mp]
    arc_r2m: jax.Array       # i32[Mp]
    arc_m2s: jax.Array       # i32[Mp]
    rack_of: jax.Array       # i32[Mp]
    slots: jax.Array         # i32[Mp] (0 on padding)
    n_tasks: jax.Array       # i32 scalar


def pad_topology(
    topo: TransportTopology, *, t_min: int = 16, m_min: int = 16,
    p_min: int = 0,
) -> DenseTopology:
    """Host-side padding of the skeleton (numpy; upload happens batched).

    ``t_min``/``m_min`` are grow-only bucket floors from the owning
    solver: with the fine (multiple-of-1024) bucket ladder, a task
    count oscillating across a bucket boundary would otherwise
    recompile the whole device chain every other round. ``p_min``
    floors the preference-column axis the same way (extra columns are
    all-absent, fill -1): the max pref count over PENDING tasks is
    data-dependent, and a multi-pref pod draining out of the pending
    pool would otherwise shrink the static ``n_prefs`` and recompile
    the chain mid-steady-state
    (``tests/test_obs.py::TestZeroRecompileUnderDrain`` pins it).
    """
    T, M = topo.n_tasks, topo.n_machines
    P = max(topo.max_prefs, p_min)
    Tp = pad_bucket(max(T, 1), minimum=t_min)
    Mp = pad_bucket(max(M, 1), minimum=m_min)

    def pad1(x, size, fill):
        out = np.full(size, fill, np.int32)
        out[: len(x)] = x
        return out

    def pad2(x, shape, fill):
        out = np.full(shape, fill, np.int32)
        out[: x.shape[0], : x.shape[1]] = x
        return out

    return DenseTopology(
        arc_unsched=pad1(topo.arc_unsched, Tp, -1),
        arc_cluster=pad1(topo.arc_cluster, Tp, -1),
        arc_u2s=pad1(topo.arc_u2s, Tp, -1),
        arc_pref=pad2(topo.arc_pref, (Tp, P), -1),
        pref_machine=pad2(topo.pref_machine, (Tp, P), -1),
        pref_rack=pad2(topo.pref_rack, (Tp, P), -1),
        arc_c2m=pad1(topo.arc_c2m, Mp, -1),
        arc_r2m=pad1(topo.arc_r2m, Mp, -1),
        arc_m2s=pad1(topo.arc_m2s, Mp, -1),
        rack_of=pad1(topo.rack_of, Mp, -1),
        slots=pad1(topo.slots, Mp, 0),
        n_tasks=np.int32(T),
    )


@partial(jax.jit, static_argnames=("n_prefs", "smax"))
def _redensify(dt: DenseTopology, cost: jax.Array, n_prefs: int, smax: int):
    """Gather the priced arc table into a scaled DenseInstance, on device.

    Returns (DenseInstance, domain_ok, pc_scaled, ra_scaled). The domain
    check (non-negative costs, 2*cmax*(T+1) < MAX_SCALED_COST) is a
    device boolean read back with the result batch — the device-side
    analog of ``build_dense_instance``'s CostDomainTooLarge guard.
    """
    Tp = dt.arc_unsched.shape[0]
    scale = dt.n_tasks + 1

    def gat(idx, fill):
        return jnp.where(
            idx >= 0, cost[jnp.maximum(idx, 0)], jnp.int32(fill)
        )

    g = gat(dt.arc_m2s, INF)                      # [Mp] m->sink leg
    d_u = jnp.minimum(gat(dt.arc_c2m, INF) + g, INF)
    ra_u = jnp.minimum(gat(dt.arc_r2m, INF) + g, INF)
    u_u = gat(dt.arc_unsched, 0) + gat(dt.arc_u2s, 0)   # 0 on padding
    w_u = gat(dt.arc_cluster, INF)
    pm_leg = jnp.where(
        dt.pref_machine >= 0, g[jnp.maximum(dt.pref_machine, 0)], 0
    )
    pc_u = jnp.minimum(gat(dt.arc_pref, INF) + pm_leg, INF)

    # integer-domain guard, in int64 (call sites run under enable_x64)
    def finmax(x):
        return jnp.max(jnp.where(x < INF, x, 0))

    def finmin(x):
        return jnp.min(jnp.where(x < INF, x, 0))

    cmax_u = jnp.maximum(
        jnp.maximum(jnp.maximum(finmax(u_u), finmax(w_u)), finmax(pc_u)),
        jnp.maximum(finmax(d_u), finmax(ra_u)),
    )
    cmin_u = jnp.minimum(
        jnp.minimum(jnp.minimum(finmin(u_u), finmin(w_u)), finmin(pc_u)),
        jnp.minimum(finmin(d_u), finmin(ra_u)),
    )
    cmax_scaled = (
        2 * cmax_u.astype(jnp.int64) * scale.astype(jnp.int64)
    )
    domain_ok = (cmin_u >= 0) & (cmax_scaled < MAX_SCALED_COST)

    def sc(x):
        # the x*scale lanes where x is INF-saturated may wrap; the
        # where() discards them before anything reads the value
        return jnp.where(x >= INF, INF, x * scale).astype(I32)

    u_s, w_s, d_s, ra_s = sc(u_u), sc(w_u), sc(d_u), sc(ra_u)
    pc_s = sc(pc_u)
    task_valid = jnp.arange(Tp, dtype=I32) < dt.n_tasks
    u_s = jnp.where(task_valid, u_s, 0)

    c = _densify(
        w_s, d_s, ra_s, dt.rack_of, dt.slots, pc_s,
        dt.pref_machine, dt.pref_rack, n_prefs=n_prefs,
    )
    dev = DenseInstance(
        c=c,
        u=u_s,
        w=w_s,
        dgen=d_s,
        s=dt.slots,
        task_valid=task_valid,
        scale=scale.astype(I32),
        cmax=jnp.minimum(cmax_scaled, INF - 1).astype(I32),
        smax=smax,
    )
    return dev, domain_ok, pc_s, ra_s


@jax.jit
def _finalize(dev: DenseInstance, dt: DenseTopology, pc_s, ra_s, asg):
    """Channel codes + scaled primal objective for a final assignment."""
    Tp, Mp = dev.c.shape
    P = pc_s.shape[1]
    on = (asg >= 0) & (asg < Mp) & dev.task_valid
    m = jnp.clip(asg, 0, Mp - 1)
    best = jnp.where(on, jnp.minimum(dev.w + dev.dgen[m], INF), INF)
    ch = jnp.where(on, CH_CLUSTER, CH_UNSCHED).astype(I32)
    for k in range(P):
        pm = dt.pref_machine[:, k]
        pr = dt.pref_rack[:, k]
        pck = pc_s[:, k]
        val = jnp.where(on & (pm == asg), pck, INF)
        hit_r = on & (pr >= 0) & (pr == dt.rack_of[m])
        val = jnp.minimum(
            val,
            jnp.where(hit_r, jnp.minimum(pck + ra_s[m], INF), INF),
        )
        better = val < best
        best = jnp.where(better, val, best)
        ch = jnp.where(better, CH_PREF + k, ch)
    c_asg = jnp.take_along_axis(dev.c, m[:, None], axis=1)[:, 0]
    per = jnp.where(dev.task_valid, jnp.where(on, c_asg, dev.u), 0)
    primal = jnp.sum(per.astype(jnp.int64))
    return ch, primal


def _decision_stats(dev: DenseInstance, asg):
    """Per-decision attribution over the final assignment: the chosen
    route's SCALED cost and the runner-up alternative's SCALED cost.

    For a placed task the runner-up is the cheapest of {any other
    machine column, going unscheduled}; for an unscheduled task it is
    the cheapest machine column. Both ride the round's one batched
    fetch; the caller unscales (costs are scale multiples) and maps an
    INF alternative to "no finite runner-up". Under aggregation the
    columns are equivalence classes, so the margin is vs the next
    DISTINCT alternative — same-class members are cost-equal by
    construction. Traced inside ``_resident_chain`` (one program, no
    extra dispatch); the masked row-min is one O(Tp·Mp) pass over a
    table the solve already materialized."""
    Tp, Mp = dev.c.shape
    on = (asg >= 0) & (asg < Mp) & dev.task_valid
    m = jnp.clip(asg, 0, Mp - 1)
    c_asg = jnp.take_along_axis(dev.c, m[:, None], axis=1)[:, 0]
    chosen = jnp.where(on, c_asg, dev.u)
    cols = jnp.arange(Mp, dtype=I32)
    masked = jnp.where(
        (cols[None, :] == asg[:, None]) & on[:, None], INF, dev.c
    )
    alt_m = jnp.min(masked, axis=1)
    alt = jnp.where(on, jnp.minimum(alt_m, dev.u), alt_m)
    return chosen, alt


# ---------------------------------------------------------------------------
# the express lane: on-HBM patch + bounded eps=1 repair between rounds
# ---------------------------------------------------------------------------

# Bounded repair fuse: an express batch is 1-K arrivals/completions
# against warm prices, so the repair is sparse local work; a batch that
# genuinely needs a price war this long is cheaper as a full round
# (converged=False -> EXPRESS_DEGRADE, the next round handles it).
EXPRESS_FUSE = 5_000


@jax.jit
def _express_patch(u, w, task_valid, s, asg, lvl, rows, slot_col,
                   slot_delta):
    """Deactivate table rows + apply slot-capacity deltas, on device.

    The retire half of the express patch vocabulary: a pod whose
    binding POST landed leaves the pending set, so its (seated) row
    deactivates and its machine's capacity drops by one — net zero on
    the auction's feasible set, so warm prices stay eps-CS and NO
    repair is needed (that is why this is a separate cheap scatter
    program, chunkable for arbitrarily large retire backlogs, while
    arrivals go through ``_express_chain``'s repair). Also carries
    bare slot deltas (completions of running pods free a seat, +1).
    ``rows``/``slot_col`` use -1 for unused entries (mapped out of
    range so the scatters drop them)."""
    Tp = task_valid.shape[0]
    Mp = s.shape[0]
    ri = jnp.where(rows >= 0, rows, Tp)
    valid2 = task_valid.at[ri].set(False, mode="drop")
    u2 = u.at[ri].set(0, mode="drop")
    w2 = w.at[ri].set(INF, mode="drop")
    asg2 = asg.at[ri].set(Mp, mode="drop")
    lvl2 = lvl.at[ri].set(0, mode="drop")
    ci = jnp.where(slot_col >= 0, slot_col, Mp)
    s2 = jnp.maximum(s.at[ci].add(slot_delta, mode="drop"), 0)
    return u2, w2, valid2, s2, asg2, lvl2


def _express_patch_chunks(rows, cols, deltas):
    """Pad retire/slot patches into fixed-width chunks so the patch
    kernel compiles once (a variable-length scatter would recompile
    per backlog size)."""
    out = []
    n = len(rows)
    W = _EXPRESS_PATCH_CHUNK
    for i in range(0, n, W):
        r = np.full(W, -1, np.int32)
        c = np.full(W, -1, np.int32)
        d = np.zeros(W, np.int32)
        r[: min(W, n - i)] = rows[i: i + W]
        c[: min(W, n - i)] = cols[i: i + W]
        d[: min(W, n - i)] = deltas[i: i + W]
        out.append((r, c, d))
    return out


def _stream_event_ints(kmax: int, pk: int, pw: int, m_in: int) -> int:
    """Per-window i32 count of the stream event-stream encoding, for
    the HBM budget guard: the mini cost inputs (~8 arc-axis arrays over
    the kmax x (3 + pk) mini arc budget plus task/machine side arrays),
    the arrival row/pref slices, and the patch triple at width ``pw``.
    An upper-bound estimate — the guard doubles it for the staging
    twin, so erring high keeps the degrade loud and early."""
    e_mini = kmax * (3 + pk)
    return (
        e_mini * 8          # mini arc-axis cost-input arrays
        + kmax * 6          # mini task-axis arrays
        + m_in * 4          # mini machine-axis arrays
        + 2 * kmax * pk     # add_pm / add_pr
        + kmax              # add_row
        + 3 * pw            # prow / pcol / pdelta
    )


def _express_step(
    dev: DenseInstance,
    dt: DenseTopology,
    cost_dev,
    mini_inputs,
    asg, lvl, floor,
    add_row,      # i32[kmax] padded row to activate (-1 unused)
    add_pm,       # i32[kmax, pk] pref machine COLUMN (-1 none)
    add_pr,       # i32[kmax, pk] pref rack index (-1 none)
    *,
    model_fn,
    kmax: int,
    pk: int,
    alpha: int,
    max_rounds: int,
    smax: int,
    change_cap: int,
):
    """One express window's device program: price the arrivals'
    task-side arcs with the round's cost model, activate their table
    rows against the warm on-HBM instance, run a bounded eps=1 repair
    from the existing prices, and compact the changed placements for
    the sanctioned fetch.

    This is the SHARED step body: ``_express_chain`` jits it directly
    (the synced lane: one window per dispatch per fetch) and
    ``_stream_chain`` scans it over K pre-uploaded windows (the
    streaming lane: one fetch per K windows). It must stay a pure
    function of its arguments so both tracers see the same program.

    No rebuild, no cold eps ladder: machine-side routes (``dev.dgen``,
    the m->sink / rack legs gathered from ``cost_dev``) are the LAST
    round's prices by design — the periodic correction round re-prices
    everything and differential-verifies what express placed. The
    repair reuses the unchanged ``_solve`` kernel, so the exactness
    certificate gates every batch: converged means the patched
    instance's optimum, full stop (the gap < scale argument needs no
    new analysis — scaled costs are multiples of the scale).

    Static args pin one compiled variant per (model, shape bucket,
    kmax, pk, change_cap) — zero recompiles in steady state.
    """
    Tp, Mp = dev.c.shape
    pos = jnp.arange(Tp, dtype=I32)
    mids = jnp.arange(Mp, dtype=I32)

    # ---- price the arrivals' task-side arcs (shared cost model) ----
    cost_mini = model_fn(mini_inputs)
    u_u = (cost_mini[:kmax]
           + cost_mini[2 * kmax + kmax * pk: 3 * kmax + kmax * pk])
    w_u = cost_mini[kmax: 2 * kmax]
    pc_raw = cost_mini[2 * kmax: 2 * kmax + kmax * pk].reshape(kmax, pk)

    # machine-side legs from the round's priced arc table (same gathers
    # as _redensify, [Mp]-cheap)
    def gat(idx, fill):
        return jnp.where(
            idx >= 0, cost_dev[jnp.maximum(idx, 0)], jnp.int32(fill)
        )

    g = gat(dt.arc_m2s, INF)
    ra_u = jnp.minimum(gat(dt.arc_r2m, INF) + g, INF)
    scale = dev.scale

    has_pref = (add_pm >= 0) | (add_pr >= 0)
    pm_leg = jnp.where(add_pm >= 0, g[jnp.maximum(add_pm, 0)], 0)
    pc_route = jnp.where(
        has_pref, jnp.minimum(pc_raw + pm_leg, INF), INF
    )

    # integer-domain guard for the batch (int64 under enable_x64)
    def finmax(x):
        return jnp.max(jnp.where(x < INF, x, 0))

    cmax_new = jnp.maximum(
        jnp.maximum(finmax(u_u), finmax(w_u)), finmax(pc_route)
    )
    # the min side MUST mask the unused arrival lanes (add_row == -1):
    # _express_mini_inputs fills them with a synthetic zero pod the
    # cost model still prices, so a model pricing that phantom below
    # zero would fail domain_ok for EVERY batch — degrading every
    # express window to the slow path on lanes no real pod occupies
    arr_valid = add_row >= 0
    cmin_new = jnp.minimum(
        jnp.min(jnp.where(arr_valid, u_u, 0)),
        jnp.minimum(
            jnp.min(jnp.where(arr_valid, w_u, 0)),
            jnp.min(jnp.where(has_pref, pc_route, 0)),
        ),
    )
    domain_ok = (cmin_new >= 0) & (
        2 * cmax_new.astype(jnp.int64) * scale.astype(jnp.int64)
        < MAX_SCALED_COST
    )

    def sc(x):
        return jnp.where(x >= INF, INF, x * scale).astype(I32)

    u_s, w_s = sc(u_u), sc(w_u)
    pc_s = sc(pc_route)
    ra_s = sc(ra_u)

    # ---- build + scatter the arrival rows ----
    row = jnp.minimum(w_s[:, None] + dev.dgen[None, :], INF)
    for j in range(pk):
        pm_j = add_pm[:, j: j + 1]
        pr_j = add_pr[:, j: j + 1]
        pc_j = pc_s[:, j: j + 1]
        hit_m = (pm_j == mids[None, :]) & (pm_j >= 0)
        row = jnp.minimum(row, jnp.where(hit_m, pc_j, INF))
        hit_r = (pr_j == dt.rack_of[None, :]) & (pr_j >= 0)
        row = jnp.minimum(
            row,
            jnp.where(hit_r, jnp.minimum(pc_j + ra_s[None, :], INF),
                      INF),
        )
    row = jnp.where(dev.s[None, :] > 0, row, INF)

    addi = jnp.where(add_row >= 0, add_row, Tp)
    c2 = dev.c.at[addi].set(row, mode="drop")
    u2 = dev.u.at[addi].set(u_s, mode="drop")
    w2 = dev.w.at[addi].set(w_s, mode="drop")
    valid2 = dev.task_valid.at[addi].set(True, mode="drop")
    asg0 = asg.at[addi].set(-1, mode="drop")
    lvl0 = lvl.at[addi].set(0, mode="drop")
    dev2 = DenseInstance(
        c=c2, u=u2, w=w2, dgen=dev.dgen, s=dev.s,
        task_valid=valid2, scale=dev.scale, cmax=dev.cmax, smax=smax,
    )

    # ---- bounded eps=1 repair from the existing prices ----
    asg_f, lvl_f, floor_f, gap, conv, rounds, phases, _ = _solve(
        dev2, asg0, lvl0, floor, jnp.int32(1), alpha=alpha,
        max_rounds=max_rounds, smax=smax, analytic_init=False,
    )

    # ---- compact ONLY the affected placements for the fetch ----
    report = valid2 & (asg_f >= 0) & (asg_f < Mp) & (asg_f != asg0)
    n_changes = jnp.sum(report, dtype=I32)
    key = jax.lax.sort(jnp.where(report, pos, Tp))
    rows_out = key[:change_cap]
    asg_out = jnp.where(
        rows_out < Tp, asg_f[jnp.minimum(rows_out, Tp - 1)], -1
    )

    # exact objective of the active rows (the express cost, scaled)
    on_m = (asg_f >= 0) & (asg_f < Mp)
    c_asg = jnp.take_along_axis(
        c2, jnp.clip(asg_f, 0, Mp - 1)[:, None], axis=1
    )[:, 0]
    per = jnp.where(
        valid2, jnp.where(on_m, c_asg, jnp.where(asg_f == Mp, u2, INF)),
        0,
    )
    primal = jnp.sum(per.astype(jnp.int64))
    n_active = jnp.sum(valid2, dtype=I32)

    return (dev2, asg_f, lvl_f, floor_f, gap, conv, rounds, phases,
            rows_out, asg_out, n_changes, domain_ok, primal, n_active,
            report)


@partial(
    jax.jit,
    static_argnames=(
        "model_fn", "kmax", "pk", "alpha", "max_rounds", "smax",
        "change_cap",
    ),
)
def _express_chain(
    dev: DenseInstance,
    dt: DenseTopology,
    cost_dev,
    mini_inputs,
    asg, lvl, floor,
    add_row,
    add_pm,
    add_pr,
    *,
    model_fn,
    kmax: int,
    pk: int,
    alpha: int,
    max_rounds: int,
    smax: int,
    change_cap: int,
):
    """The synced express lane: ONE fused dispatch of one window
    (``_express_step``'s program, unchanged). Static args pin one
    compiled variant per (model, shape bucket, kmax, pk, change_cap)
    — zero recompiles in steady state. The trailing ``report`` mask
    rides on device and is only fetched by the change-cap-overflow
    degrade path (the full sanctioned placement fetch)."""
    return _express_step(
        dev, dt, cost_dev, mini_inputs, asg, lvl, floor,
        add_row, add_pm, add_pr,
        model_fn=model_fn, kmax=kmax, pk=pk, alpha=alpha,
        max_rounds=max_rounds, smax=smax, change_cap=change_cap,
    )


@partial(
    jax.jit,
    static_argnames=(
        "model_fn", "kmax", "pk", "alpha", "max_rounds", "smax",
        "change_cap",
    ),
)
def _stream_chain(
    dev: DenseInstance,
    dt: DenseTopology,
    cost_dev,
    mini_stack,    # CostInputs pytree, each leaf stacked [K, ...]
    asg, lvl, floor,
    add_row_s,     # i32[K, kmax]
    add_pm_s,      # i32[K, kmax, pk]
    add_pr_s,      # i32[K, kmax, pk]
    prow_s,        # i32[K, pw] retire/removal rows (-1 unused)
    pcol_s,        # i32[K, pw] slot columns (-1 unused)
    pdelta_s,      # i32[K, pw] seat deltas
    *,
    model_fn,
    kmax: int,
    pk: int,
    alpha: int,
    max_rounds: int,
    smax: int,
    change_cap: int,
):
    """The streaming lane: K express windows as ONE ``lax.scan`` over a
    pre-uploaded event-stream buffer — one dispatch, ONE sanctioned
    fetch of K compacted per-window decision logs, amortizing the
    host<->device sync across the whole stream batch.

    Each scan step replays exactly what the synced lane does per
    window: apply the window's retire/removal/slot patch
    (``_express_patch``'s math), then ``_express_step``'s price →
    activate → bounded-repair → compact program. Two stream-only
    pieces keep the K-window composition equivalent to K synced
    dispatches:

    - **auto-retire**: the synced lane retires each window's reported
      placements via the NEXT window's patch list (bindings confirm
      between fetches). Mid-stream there is no host in the loop, so
      the step retires its own report in-device — deactivate the row,
      consume the seat at the winning column — before handing the
      carry to the next window. Bit-identical to the synced sequence
      with every binding confirmed between windows (the steady state;
      the host-side twin drops the later confirm-driven retire).
    - **per-window certificate latching**: ``live`` starts True and
      latches False on the first window whose certificate fails
      (uncertified repair, cost-domain overflow, or a changed-row
      count past the compaction cap). A dead window's carry freezes at
      the last good state and its outputs are masked, so the host sees
      exactly which window failed and replays from there via the
      synced/round path — never a silent partial commit.

    Static args + the [K, ...] buffer shapes (grow-only floors on K's
    padding and the patch width) pin one compiled variant — zero
    recompiles in steady state, including draining flushes (short
    batches pad with no-op windows of the same shape).
    """
    Tp, Mp = dev.c.shape

    def step(carry, xs):
        c, u, w, s, valid, asg_c, lvl_c, floor_c, live = carry
        mini, add_row, add_pm, add_pr, prow, pcol, pdelta = xs
        u1, w1, valid1, s1, asg1, lvl1 = _express_patch(
            u, w, valid, s, asg_c, lvl_c, prow, pcol, pdelta
        )
        dev_w = DenseInstance(
            c=c, u=u1, w=w1, dgen=dev.dgen, s=s1, task_valid=valid1,
            scale=dev.scale, cmax=dev.cmax, smax=smax,
        )
        (dev2, asg_f, lvl_f, floor_f, _gap, conv, rounds, _phases,
         rows_out, asg_out, n_changes, domain_ok, primal, _n_active,
         report) = _express_step(
            dev_w, dt, cost_dev, mini, asg1, lvl1, floor_c,
            add_row, add_pm, add_pr,
            model_fn=model_fn, kmax=kmax, pk=pk, alpha=alpha,
            max_rounds=max_rounds, smax=smax, change_cap=change_cap,
        )
        win_ok = conv & domain_ok & (n_changes <= jnp.int32(change_cap))
        live2 = live & win_ok
        # auto-retire the window's reported placements (the synced
        # lane's next-batch retire patch, applied in-device): row
        # deactivates, seat consumed at the winning column
        valid_r = dev2.task_valid & ~report
        u_r = jnp.where(report, 0, dev2.u)
        w_r = jnp.where(report, INF, dev2.w)
        s_r = dev2.s.at[
            jnp.where(report, jnp.clip(asg_f, 0, Mp - 1), Mp)
        ].add(-1, mode="drop")
        s_r = jnp.maximum(s_r, 0)
        asg_r = jnp.where(report, Mp, asg_f)
        lvl_r = jnp.where(report, 0, lvl_f)

        def sel(new, old):
            return jnp.where(live2, new, old)

        carry2 = (
            sel(dev2.c, c), sel(u_r, u), sel(w_r, w), sel(s_r, s),
            jnp.where(live2, valid_r, valid), sel(asg_r, asg_c),
            sel(lvl_r, lvl_c), sel(floor_f, floor_c), live2,
        )
        ys = (
            jnp.where(live2, rows_out, Tp),
            jnp.where(live2, asg_out, -1),
            n_changes, live2, conv, domain_ok, rounds,
            jnp.where(live2, primal, jnp.int64(0)),
        )
        return carry2, ys

    carry0 = (
        dev.c, dev.u, dev.w, dev.s, dev.task_valid, asg, lvl, floor,
        jnp.asarray(True),
    )
    xs = (mini_stack, add_row_s, add_pm_s, add_pr_s,
          prow_s, pcol_s, pdelta_s)
    return jax.lax.scan(step, carry0, xs)


_MODEL_JIT_CACHE: dict[object, object] = {}


def _jitted_model(name: str):
    """Jit each registry cost model once (fresh jax.jit wrappers per
    round would re-trace every call). Keyed by the function object, not
    the name, so re-registering a name in COST_MODELS takes effect."""
    fn = get_cost_model(name)
    jitted = _MODEL_JIT_CACHE.get(fn)
    if jitted is None:
        jitted = jax.jit(fn)  # noqa: PTA003 -- cached in _MODEL_JIT_CACHE keyed by fn: one wrapper per model for the process lifetime, not per call
        _MODEL_JIT_CACHE[fn] = jitted
    return jitted


class _AsyncFetch:
    """Single-shot background download with a bounded join.

    Replaces the previous shared ThreadPoolExecutor: the worker is a
    daemon thread, so a fetch wedged on a dead device link can neither
    block interpreter exit nor poison a shared pool for the next round
    — a timed-out fetch is simply abandoned (one parked daemon thread,
    loudly logged by the caller). The ``_done`` Event set/wait pair is
    the documented cross-thread handoff (analysis/contracts.py,
    PTA004): ``_value``/``_exc`` are written before ``set()`` and read
    only after ``wait()`` returns.
    """

    def __init__(self, fn):
        self._fn = fn
        self._done = threading.Event()
        self._value = None
        self._exc: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, name="resident-fetch", daemon=True
        )
        self._thread.start()

    def _run(self):  # pta: background-thread
        try:
            self._value = self._fn()
        except BaseException as e:  # delivered to the joining thread
            self._exc = e
        finally:
            self._done.set()

    def result(self, timeout_s: float | None = None):
        """Join the fetch; raises ``FetchTimeout`` past the deadline
        (the fetch keeps running — the caller decides to abandon)."""
        if not self._done.wait(timeout_s):
            raise FetchTimeout(
                f"background placement fetch still pending after "
                f"{timeout_s:g}s (--max_solver_runtime)"
            )
        if self._exc is not None:
            raise self._exc
        return self._value


@partial(
    jax.jit,
    static_argnames=(
        "model_fn", "n_prefs", "smax", "alpha", "max_rounds",
    ),
)
def _resident_chain(
    dt: DenseTopology,
    inputs_dev,
    *,
    model_fn,
    n_prefs: int,
    smax: int,
    alpha: int,
    max_rounds: int,
):
    """The WHOLE resident round as ONE compiled program: cost model →
    densify → eps-ladder auction → channel/objective finalize.

    Fusing replaces the previous chain of four separately-dispatched
    programs (model, redensify, solve, finalize) with one: three fewer
    launches per round, and no intermediate result leaves the device.

    ``model_fn`` is static: one compiled variant per cost model per
    shape bucket. Every round starts cold, from the analytic clearing
    (``cold_start`` + ``analytic_init``): on the rounds measured
    (Quincy bursts and churned flagship rounds, place-only Octopus
    waves of identical pods) it settles in as few auction rounds as a
    start from the last round's prices or fewer, and a carried start
    can strand the fuse where the cold one does not. The round's final
    state is still kept (``ResidentSolver._warm``) for the express and
    stream lanes, which patch it between full rounds.
    """
    # named scopes: each stage's HLO carries its name in its op_name
    # metadata (pricing / redensify / auction / finalize)
    with jax.named_scope("pricing"):
        cost = model_fn(inputs_dev)
    with jax.named_scope("redensify"):
        dev, domain_ok, pc_s, ra_s = _redensify(
            dt, cost, n_prefs=n_prefs, smax=smax
        )
    with jax.named_scope("auction"):
        asg0, lvl0, floor0, eps0 = cold_start(dev, alpha)
        asg, lvl, floor, gap, converged, rounds, phases, _ = _solve(
            dev, asg0, lvl0, floor0, eps0, alpha=alpha,
            max_rounds=max_rounds, smax=smax, analytic_init=True,
        )
    with jax.named_scope("finalize"):
        ch, primal = _finalize(dev, dt, pc_s, ra_s, asg)
        chosen, alt = _decision_stats(dev, asg)
    # flat tuple out (DenseState is not a registered pytree); the
    # caller reassembles the warm handle host-side. ``cost`` rides
    # along so oracle-fallback paths reuse the priced arc table
    # instead of re-running the model as a separate program, and
    # ``dev`` (the densified on-HBM instance — its arrays are aliases
    # of buffers the program produced anyway) rides along so the
    # express lane can keep the warm table resident and patch it in
    # place between rounds instead of re-densifying. ``chosen``/``alt``
    # are the per-decision attribution pair (scaled chosen route cost +
    # runner-up alternative) the decision log and the explainer
    # consume — computed here so they ride the round's ONE fetch.
    return (asg, lvl, floor, gap, converged, rounds, phases, ch,
            primal, domain_ok, chosen, alt, cost, dev)


@dataclasses.dataclass
class ResidentOutcome:
    """One resident round's result, fully host-side."""

    assignment: np.ndarray   # int32[T] machine index or -1
    channel: np.ndarray      # int32[T] CH_* code
    cost: int                # exact unscaled objective
    backend: str             # "dense_auction" | "oracle:<why>"
    converged: bool
    rounds: int              # auction rounds; 0 from the oracle
    phases: int
    # None only on a non-taxonomy graph (oracle path); without it the
    # outcome cannot be flow-decomposed
    topology: TransportTopology | None
    timings: dict[str, float]
    # per-decision attribution (int64 over task order, unscaled): the
    # chosen route's exact objective contribution and runner-up-minus-
    # chosen (deltas.MARGIN_UNKNOWN = no finite runner-up / margin not
    # computed on this backend). None only when the path cannot price
    # decisions at all (non-taxonomy oracle graphs).
    task_cost: np.ndarray | None = None
    task_margin: np.ndarray | None = None


@dataclasses.dataclass
class InflightSolve:
    """A dispatched-but-not-fetched resident round.

    ``begin_round`` returns one of these; the placement download runs
    on a background thread from the moment of dispatch (the fetch
    clock starts immediately, so the device program and its download
    elapse concurrently with whatever host work the caller overlaps).
    ``finish_round`` joins the fetch and completes the
    round. Rounds that resolved synchronously (degrade paths) carry
    ``outcome`` directly.
    """

    outcome: ResidentOutcome | None = None
    future: object = None            # Future -> fetched host tuple
    state: object = None             # device DenseState (warm candidate)
    cost_dev: object = None          # priced arc table (oracle fallback)
    dev: object = None               # device DenseInstance (express lane)
    machine_kwargs: dict | None = None  # host machine-side cost inputs
                                        # (express mini-pricing reuse)
    arrays: dict | None = None
    meta: GraphMeta | None = None
    topo: TransportTopology | None = None
    dt: object = None                # device DenseTopology
    model_fn: object = None
    n_prefs: int = 0
    smax: int = 1
    Tp: int = 0
    Mp: int = 0
    T: int = 0
    n_machines: int = 0
    # scale lane: the machine-axis equivalence partition this round
    # solved over (None = all-pairs), and the base topology's per-
    # machine slots for the class -> machine expansion
    agg_plan: object = None
    base_slots: object = None
    timings: dict | None = None
    t_dispatch: float = 0.0
    # set by finish_round on first join; guards double-finish (a
    # driver's cancel path must not re-run the certificate/fallback)
    consumed: bool = False


@dataclasses.dataclass(frozen=True)
class ExpressArrival:
    """One new pending pod for the express lane, in builder-column
    vocabulary: ``prefs`` are the (machine_idx, rack_idx, weight) rows
    ``FlowGraphBuilder.task_arc_rows`` resolves — the SAME single-event
    column patch the incremental builder applies, so the periodic
    correction round builds an identical graph for this pod."""

    uid: str
    wait_rounds: int = 0
    cpu_milli: int = 0
    mem_kb: int = 0
    prefs: tuple = ()    # ((machine_idx | -1, rack_idx | -1, weight), ...)


@dataclasses.dataclass
class ExpressBatch:
    """One coalesced watch-event batch for ``express_round``.

    ``retires`` are pods whose binding POST landed since the last
    dispatch (row deactivates, target machine's capacity drops one);
    ``removals`` are pending pods that left the cluster; ``slot_deltas``
    are bare capacity changes (a running pod completing frees a seat)."""

    arrivals: list[ExpressArrival] = dataclasses.field(
        default_factory=list)
    retires: list[tuple[str, str]] = dataclasses.field(
        default_factory=list)      # (uid, machine name)
    removals: list[str] = dataclasses.field(default_factory=list)
    slot_deltas: list[tuple[str, int]] = dataclasses.field(
        default_factory=list)      # (machine name, +/- seats)


@dataclasses.dataclass
class ExpressOutcome:
    """One express dispatch's result. ``ok=False`` means the batch
    DEGRADED (reason says why): nothing was placed, the express context
    is invalidated, and the events simply wait for the next full round
    — never a silent wrong placement (the in-kernel certificate gates
    every batch)."""

    ok: bool
    placements: list[tuple[str, str]] = dataclasses.field(
        default_factory=list)      # (uid, machine name)
    cost: int = 0
    rounds: int = 0
    reason: str = ""
    # ok=True but something degraded LOUDLY along the way (change-cap
    # overflow's full placement fetch): the bridge traces/counts an
    # EXPRESS_DEGRADE with this reason while still binding everything
    degrade_reason: str = ""
    timings: dict = dataclasses.field(default_factory=dict)


class ExpressDegrade(Exception):
    """This batch cannot take the express path. Raised internally by
    the patch/repair chain (``express_round`` turns it into an
    ``ExpressOutcome(ok=False)``) and by ``express_maps`` when
    finalizing the context degrades (the bridge invalidates + counts)."""


@dataclasses.dataclass
class _ExpressContext:
    """The warm on-HBM state the express lane patches between rounds.

    Created by ``finish_round`` on every certified dense round (express
    lane on), dropped by the next ``begin_round``. Device handles keep
    the round's densified table / topology / priced arcs resident; the
    host maps are built LAZILY on first express use so rounds that see
    no inter-round events pay nothing beyond the references.
    """

    dev: object                 # device DenseInstance (the warm table)
    dt: object                  # device DenseTopology
    cost_dev: object            # device priced arc table (round prices)
    meta: object                # GraphMeta of the round's build
    topo: object                # base TransportTopology
    agg_plan: object            # AggregatePlan | None
    assignment: np.ndarray      # round's final base-machine assignment
    machine_kwargs: dict        # host machine-side cost inputs (stale
                                # by design: "from the existing prices")
    model_fn: object
    n_prefs: int
    smax: int
    Tp: int
    Mp: int                     # solve-axis width (columns under agg)
    T: int
    scale: int
    # ---- lazy host maps (built on first express dispatch) ----
    ready: bool = False
    uid_row: dict | None = None
    row_uid: dict | None = None
    free_rows: list | None = None
    midx: dict | None = None
    rack_idx: dict | None = None
    # rebalancing mode: running rows frozen out of the express auction
    # (their seats become used capacity), applied with the first batch
    pending_freeze: tuple | None = None
    col_of: np.ndarray | None = None
    col_bounds: np.ndarray | None = None
    col_order: np.ndarray | None = None
    members_per_col: np.ndarray | None = None
    member_slots_left: np.ndarray | None = None
    batches: int = 0
    # uids the streaming lane already retired IN-DEVICE (the scan's
    # auto-retire): the later confirm-driven retire for the same uid
    # must not double-apply its seat decrement
    stream_retired: set = dataclasses.field(default_factory=set)


# chunk width for the retire/slot patch kernel: backlogs larger than
# one chunk (a big round's bindings, a preemption-mode freeze of every
# running row) apply as several cheap scatter dispatches
_EXPRESS_PATCH_CHUNK = 1024


@dataclasses.dataclass
class StreamOutcome:
    """One stream flush's result (K windows, one sanctioned fetch).

    ``ok=False`` with ``failed_window >= 0`` means a mid-stream window
    failed its certificate: ``placements`` still carries every GOOD
    window's bindings (windows before ``failed_window`` — the scan's
    latch froze the carry there, so they are exactly what a synced
    replay would have produced), the context is invalidated, and the
    failed window's events onward wait for the next full round."""

    ok: bool
    placements: list[tuple[str, str, int]] = dataclasses.field(
        default_factory=list)     # (uid, machine name, window idx)
    window_costs: list[int] = dataclasses.field(default_factory=list)
    window_rounds: list[int] = dataclasses.field(default_factory=list)
    windows: int = 0              # real (non-padding) windows flushed
    failed_window: int = -1
    reason: str = ""
    fetches: int = 0              # sanctioned fetches this flush (1)
    timings: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _StreamWindow:
    """One accumulated-but-not-flushed stream window: the host event
    encoding plus its staged device twin (uploaded at accumulate time,
    so batch k+1's uploads overlap batch k's in-flight scan — the
    double buffer)."""

    host: tuple                   # (mini, add_row, add_pm, add_pr,
                                  #  prow, pcol, pdelta)
    dev: tuple                    # staged device twin of ``host``
    pw: int                       # patch width the staging padded to
    journal: list                 # [(row, old_uid|None, new_uid|None)]
    prep_ms: float = 0.0
    upload_ms: float = 0.0


@dataclasses.dataclass
class _InflightStream:
    """A dispatched-but-not-fetched stream batch. The background
    download of the K compacted decision logs runs from dispatch time;
    the next batch's windows accumulate (and stage their uploads)
    while this one is in flight."""

    future: object                # _AsyncFetch of the K-window log
    carry: tuple                  # final device carry (c,u,w,s,valid,
                                  #  asg,lvl,floor,live)
    ctx: object                   # the _ExpressContext it solved under
    n_windows: int                # real windows (rest are no-op pads)
    journals: list                # per real window row-map journals
    row_uid_end: dict             # ctx.row_uid snapshot at flush time
    timings: dict = dataclasses.field(default_factory=dict)
    t_dispatch: float = 0.0


# ---------------------------------------------------------------------------
# per-tenant warm contexts (the service lane, poseidon_tpu/service/)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TenantContext:
    """One tenant's warm solve context in the multi-tenant service.

    The per-tenant analog of ``ResidentSolver``'s warm handle + grow-
    only padding floors: ``state`` is the tenant's on-HBM ``DenseState``
    from its last certified in-bucket solve (asg/lvl/floor feed the
    next dispatch's eps=1 warm settle), valid only while the tenant's
    padded dims stay (Tp, Mp). The floors are the anti-recompile
    hysteresis — a tenant whose task/arc counts oscillate across a fine
    bucket boundary must not flip its shape bucket (and recompile the
    member kernel) every other round.
    """

    state: DenseState | None = None
    Tp: int = 0
    Mp: int = 0
    # grow-only bucket floors (reset by the pool on budget overflow,
    # mirroring ResidentSolver's reset-on-DenseMemoryTooLarge)
    e_floor: int = 16     # arc-count bucket (cost-input pricing pad)
    t_floor: int = 16     # task-axis padding bucket
    m_floor: int = 16     # machine-axis padding bucket
    p_floor: int = 0      # preference-column floor
    s_floor: int = 1      # smax (max free slots) floor
    ti_floor: int = 1     # build_cost_inputs_host per-task pad
    mi_floor: int = 1     # build_cost_inputs_host per-machine pad


class TenantWarmPool:
    """Warm per-tenant contexts keyed by tenant id.

    Owned by the service's ``BatchDispatcher``; single-threaded by
    contract (every access happens on the service pump thread, like
    the bridge). Nothing here touches the device — the pool only holds
    references to device arrays the member solves produced.
    """

    def __init__(self) -> None:
        self._ctx: dict[str, TenantContext] = {}

    def context(self, tenant_id: str) -> TenantContext:
        ctx = self._ctx.get(tenant_id)
        if ctx is None:
            ctx = TenantContext()
            self._ctx[tenant_id] = ctx
        return ctx

    def warm(self, tenant_id: str, Tp: int, Mp: int) -> DenseState | None:
        """The tenant's warm handle, or None when cold / the tenant
        outgrew its padding bucket (shape change = cold solve, the same
        silent fallback the resident lane makes)."""
        ctx = self._ctx.get(tenant_id)
        if ctx is None or ctx.state is None:
            return None
        if ctx.Tp != Tp or ctx.Mp != Mp:
            return None
        return ctx.state

    def commit(
        self, tenant_id: str, state: DenseState, Tp: int, Mp: int
    ) -> None:
        ctx = self.context(tenant_id)
        ctx.state = state
        ctx.Tp = Tp
        ctx.Mp = Mp

    def invalidate(self, tenant_id: str | None = None) -> None:
        """Drop warm state (one tenant, or everyone when None). Floors
        survive — invalidation means "next solve is cold", not "the
        tenant shrank"."""
        if tenant_id is not None:
            ctx = self._ctx.get(tenant_id)
            if ctx is not None:
                ctx.state = None
            return
        for ctx in self._ctx.values():
            ctx.state = None

    def reset_floors(self, tenant_id: str) -> None:
        """Budget-overflow escape: a floor raised by a past larger
        cluster must not keep re-padding a fitting tenant over budget
        forever (the cost is one recompile) — same rule as
        ``ResidentSolver``'s DenseMemoryTooLarge path."""
        self._ctx[tenant_id] = TenantContext()


class ResidentSolver:
    """Owns the device-resident solve chain + warm state across rounds.

    One instance per scheduling loop (the bridge holds it). Warm state
    (``DenseState``) lives on HBM between rounds; it survives task-set
    churn because a stale assignment is only a starting point — the
    auction's violator release + certificate repair it exactly (the trim
    in ``_solve`` enforces capacity before the loop).
    """

    def __init__(
        self,
        *,
        alpha: int = 1024,
        max_rounds: int | None = None,
        oracle_fallback: bool = True,
        oracle_timeout_s: float = 1000.0,
        small_to_oracle: bool = True,
        fetch_timeout_s: float | None = None,
        mesh_width: int = 0,
        aggregate_classes: bool = False,
        topk_prefs: int = 0,
        express_lane: bool = False,
        express_max_batch: int = 16,
        express_change_cap: int = 256,
        stream_windows: int = 0,
        metrics=None,
    ):
        self.alpha = alpha
        # observability (obs.SchedulerMetrics or None): the solver
        # reports its sanctioned-fetch counts and warm/express-context
        # liveness at finish time — host ints/bools it already holds,
        # never a device sync (PTA001)
        self.metrics = metrics
        self.max_rounds = max_rounds
        self.oracle_fallback = oracle_fallback
        self.oracle_timeout_s = oracle_timeout_s
        # ---- the scale lane (graph/aggregate.py + parallel/) ----
        # mesh_width 0 = the plain single-device layout; >= 1 lays the
        # round out over a task-axis mesh of that width (width 1 is a
        # 1-device mesh — bit-identical to plain, the equivalence
        # anchor tests/test_scale.py pins). aggregate_classes collapses
        # the machine axis to its equivalence classes before densify;
        # topk_prefs caps preference columns (0 = keep all).
        self.mesh_width = mesh_width
        self.aggregate_classes = aggregate_classes
        self.topk_prefs = topk_prefs
        self._mesh = None
        if mesh_width:
            from poseidon_tpu.parallel.mesh import make_mesh

            self._mesh = make_mesh(mesh_width)
        # deadline on the background placement fetch (the pipelined
        # path's analog of --max_solver_runtime, which previously only
        # bounded the oracle subprocess); None = same budget as the
        # oracle. A miss raises FetchTimeout: counted, traced by the
        # bridge, and the round abandoned — never a silent forever-wait
        self.fetch_timeout_s = fetch_timeout_s
        # dispatch heuristic: tiny instances go straight to the oracle
        # (the TPU per-launch floor exceeds the whole subprocess solve
        # there — solver.SMALL_INSTANCE_* documents the measurement)
        self.small_to_oracle = small_to_oracle
        self._warm: DenseState | None = None
        # grow-only padding-bucket floors (anti-recompile hysteresis)
        self._e_floor = 16
        self._t_floor = 16
        self._m_floor = 16
        # cost-input floors: build_cost_inputs_host pads per-task /
        # per-machine arrays by ITS OWN buckets of the raw counts, so
        # a draining pending pool would shrink those shapes (and
        # recompile the fused chain) even while pad_topology's floors
        # hold — the floors travel together
        # (tests/test_obs.py::TestZeroRecompileUnderDrain)
        self._ti_floor = 1
        self._mi_floor = 1
        self._s_floor = 1
        self._p_floor = 0
        # one round in flight at a time
        self._inflight = False
        # observability: lifetime fetch-deadline misses, and how many
        # sanctioned downloads the LAST round performed (1 on the
        # certified dense path — the "exactly one host sync" contract,
        # asserted by tests/test_guards.py)
        self.fetch_timeouts = 0
        self.last_round_fetches = 0
        # ---- the express lane (between-rounds fast path) ----
        # express_lane keeps each certified round's densified table /
        # topology / prices resident on HBM so small watch-event
        # batches re-solve in ONE fused dispatch + ONE sanctioned
        # fetch (express_round); express_max_batch bounds arrivals per
        # dispatch (a static shape: one compiled variant), and
        # express_change_cap bounds the compacted changed-placement
        # fetch (more changes than that degrades to a full round)
        self.express_lane = express_lane
        self.express_max_batch = express_max_batch
        self.express_change_cap = express_change_cap
        self._express: _ExpressContext | None = None
        # lifetime sanctioned express fetches (one per express batch)
        self.express_fetches = 0
        # ---- the streaming lane (K express windows per fetch) ----
        # stream_windows K > 1 accumulates K express windows and solves
        # them as ONE lax.scan dispatch with ONE sanctioned fetch of K
        # compacted decision logs (_stream_chain) — one host sync per
        # K windows. 0/1 = off (synced express).
        self.stream_windows = stream_windows
        # grow-only per-window patch-width bucket (anti-recompile
        # hysteresis for the retire/removal/slot slice of the event-
        # stream buffer; kmax/pk already pin the arrival slice)
        self._stream_pw_floor = 16
        self._stream_pending: list[_StreamWindow] = []
        self._stream_inflight: _InflightStream | None = None
        # observability: lifetime sanctioned stream fetches (one per
        # flush), the window count the LAST flush amortized, and the
        # stream twin of last_round_fetches (exactly 1 on the
        # certified stream path — asserted by tests/test_stream.py)
        self.stream_fetches = 0
        self.last_stream_windows = 0
        self.last_stream_fetches = 0
        # defensive: flushed-but-unjoined stream batches a full round
        # had to abandon (the cli drains streams before every tick, so
        # nonzero means a driver bug worth surfacing)
        self.stream_abandoned = 0
        # host mirror of the warm state (asg/lvl/floor from the round's
        # own batched fetch) + whether an express batch has since
        # mutated the on-HBM warm state without a full-state fetch —
        # the flight recorder's replay-seed surface (obs/flightrec.py)
        self._warm_seed: tuple | None = None
        self._warm_mutated = True

    def reset(self) -> None:
        self._warm = None
        self._express = None
        self._warm_seed = None
        self._warm_mutated = True
        self._stream_pending = []
        self._stream_inflight = None

    @property
    def warm_seed_host(self) -> tuple | None:
        """Host (asg, lvl, floor) int32 mirror of the live warm state,
        or None when cold / the mirror is stale (an express batch
        patched the warm state on device since the last full-state
        fetch — replaying the recorded express batches reproduces it
        instead)."""
        if self._warm is None or self._warm_mutated:
            return None
        return self._warm_seed

    @property
    def pad_floors(self) -> dict[str, int]:
        """The grow-only padding-bucket floors as of now. Captured by
        the flight recorder AFTER ``begin_round`` (which updates them),
        so a replay padding with these floors reproduces the round's
        exact static shapes (Tp/Mp/P/smax) regardless of what earlier
        rounds grew them to."""
        return {
            "e": self._e_floor, "t": self._t_floor, "m": self._m_floor,
            "ti": self._ti_floor, "mi": self._mi_floor,
            "s": self._s_floor, "p": self._p_floor,
        }

    def restore_for_replay(
        self, floors: dict[str, int] | None,
        warm_seed: tuple | None,
    ) -> None:
        """Replay/restore seeding: restore recorded padding floors and
        (optionally) upload a recorded warm (asg, lvl, floor) mirror
        as the live warm state. The next full round then runs the
        exact compiled program the recorded round ran (it starts cold
        from the analytic clearing whatever the warm state), so
        assignment/cost are bit-identical and the restored floors keep
        the steady state at zero recompiles; the express and stream
        lanes patch the uploaded state until that round. Two callers,
        both OFF the round's hot path: the offline replay harness
        (obs/replay.py) and the startup warm restore
        (ha/checkpoint.restore_bridge)."""
        if floors:
            self._e_floor = floors["e"]
            self._t_floor = floors["t"]
            self._m_floor = floors["m"]
            self._ti_floor = floors["ti"]
            self._mi_floor = floors["mi"]
            self._s_floor = floors["s"]
            self._p_floor = floors["p"]
        if warm_seed is None:
            return
        asg = np.asarray(warm_seed[0], np.int32)  # noqa: PTA001 -- recorded host arrays (offline replay path, never the live round)
        lvl = np.asarray(warm_seed[1], np.int32)  # noqa: PTA001 -- recorded host arrays (offline replay path)
        floor = np.asarray(warm_seed[2], np.int32)  # noqa: PTA001 -- recorded host arrays (offline replay path)
        if self._mesh is not None:
            # the sharded lane's warm state lives task-sharded /
            # machine-replicated; committing the seed to one device
            # would make the next dispatch a disallowed reshard
            from jax.sharding import NamedSharding, PartitionSpec

            axis = self._mesh.axis_names[0]
            task_s = NamedSharding(self._mesh, PartitionSpec(axis))
            repl = NamedSharding(self._mesh, PartitionSpec())
            asg_d, lvl_d, floor_d = jax.device_put(
                (asg, lvl, floor), (task_s, task_s, repl)
            )
        else:
            asg_d, lvl_d, floor_d = jax.device_put((asg, lvl, floor))
        # gap/converged/rounds/phases are never read from a warm state
        # (the express and stream chains consume asg/lvl/floor only),
        # so int32 placeholders avoid an x64-mode dependency here
        self._warm = DenseState(
            asg=asg_d, lvl=lvl_d, floor=floor_d,
            gap=jnp.int32(0), converged=jnp.asarray(True),
            rounds=jnp.int32(0), phases=jnp.int32(0),
        )
        self._warm_seed = (asg, lvl, floor)
        self._warm_mutated = False

    @property
    def express_ready(self) -> bool:
        """True when a warm express context exists (a certified dense
        round finished and no full round has begun since)."""
        return self._express is not None

    def invalidate_express(self) -> None:
        """Drop the express context: the next batches wait for a full
        round. Called by the bridge whenever cluster state moves in a
        way the on-HBM patch vocabulary cannot represent. Pending
        (unflushed) stream windows reference the context, so they drop
        with it — their events are already in bridge state and wait
        for the round like any degraded batch."""
        self._express = None
        self._stream_pending = []

    # ---- the streaming lane (K windows per sanctioned fetch) ----------

    @property
    def stream_pending_windows(self) -> int:
        """Accumulated-but-not-flushed stream windows."""
        return len(self._stream_pending)

    @property
    def stream_inflight(self) -> bool:
        """True while a flushed stream batch's fetch is in flight."""
        return self._stream_inflight is not None

    def _stream_abandon(self) -> None:
        """Defensive round-boundary cleanup: drop pending windows and
        abandon any in-flight stream fetch (its daemon thread finishes
        harmlessly; the round replaces all device state). The cli
        drains streams before every tick, so a nonzero abandon count
        flags a driver bug — counted, never silent."""
        self._stream_pending = []
        if self._stream_inflight is not None:
            self._stream_inflight = None
            self.stream_abandoned += 1

    @property
    def warm(self) -> DenseState | None:
        """The on-HBM warm handle carried across rounds (None = cold)."""
        return self._warm

    def _fetch_deadline_s(self) -> float:
        return (
            self.fetch_timeout_s if self.fetch_timeout_s is not None
            else self.oracle_timeout_s
        )

    def run_round(
        self,
        arrays: dict[str, np.ndarray],
        meta: GraphMeta,
        *,
        cost_model: str,
        cost_input_kwargs: dict | None = None,
        topology: TransportTopology | None = None,
    ) -> ResidentOutcome:
        """One full scheduling round from builder host arrays (serial:
        ``begin_round`` immediately joined by ``finish_round``).

        ``arrays`` is ``FlowGraphBuilder.build_arrays``'s output;
        ``cost_input_kwargs`` are the KnowledgeBase aggregates passed to
        ``build_cost_inputs_host``; ``topology`` (optional) skips the
        O(arcs) taxonomy re-validation when the caller already derived
        the skeleton (the incremental builder does).
        """
        return self.finish_round(self.begin_round(
            arrays, meta, cost_model=cost_model,
            cost_input_kwargs=cost_input_kwargs, topology=topology,
        ))

    def begin_round(
        self,
        arrays: dict[str, np.ndarray],
        meta: GraphMeta,
        *,
        cost_model: str,
        cost_input_kwargs: dict | None = None,
        topology: TransportTopology | None = None,
    ) -> InflightSolve:
        """Prep + upload + async dispatch of one resident round.

        Returns an ``InflightSolve`` whose placement download is already
        running on a background thread — the caller overlaps host work
        (next poll parse, delta build, binding POSTs) and then calls
        ``finish_round``. Degrade paths (small instance, non-taxonomy,
        HBM envelope) solve synchronously on the oracle and come back
        with ``outcome`` already set. One round may be in flight at a
        time; a second ``begin_round`` before ``finish_round`` raises.
        """
        if self._inflight:
            raise RuntimeError(
                "a resident round is already in flight; finish_round() "
                "must be called before the next begin_round()"
            )
        # a full round supersedes the inter-round express state; drop
        # the context FIRST so its HBM (the retained dense table) is
        # free before this round's chain allocates a fresh one
        self._express = None
        self._stream_abandon()
        self.last_round_fetches = 0
        self.last_stream_windows = 0
        self.last_stream_fetches = 0
        timings: dict[str, float] = {}
        t0 = time.perf_counter()
        # grow-only bucket floors: arc/task counts oscillating across a
        # fine bucket boundary must not recompile the chain every round
        self._e_floor = pad_bucket(
            max(meta.n_arcs, 1), minimum=self._e_floor
        )
        E = self._e_floor
        self._ti_floor = pad_bucket(
            max(len(meta.task_uids), 1), minimum=self._ti_floor
        )
        self._mi_floor = pad_bucket(
            max(len(meta.machine_names), 1), minimum=self._mi_floor
        )
        inputs_host = build_cost_inputs_host(
            E, meta, t_min=self._ti_floor, m_min=self._mi_floor,
            **(cost_input_kwargs or {}),
        )

        def degrade(why: str, topo, *, price_on_cpu: bool = False):
            # price the arcs (the models want device inputs) and solve
            # this round on the oracle. The small lane prices on the
            # host CPU backend: the registry models are pure jnp, and a
            # tiny round whose whole point is "skip the TPU launch
            # floor" must not pay a TPU device_put + model dispatch
            # either (ADVICE round 5).
            cpu = None
            if price_on_cpu:
                try:
                    cpu = jax.local_devices(backend="cpu")[0]
                except RuntimeError:
                    cpu = None  # no CPU backend registered: default dev
            inputs_dev = (
                jax.device_put(inputs_host, cpu)
                if cpu is not None else jax.device_put(inputs_host)
            )
            cost = _jitted_model(cost_model)(inputs_dev)
            return InflightSolve(outcome=self._oracle_round(
                arrays, meta, topo, cost, timings, why=why
            ))

        topo = topology
        if topo is None:
            try:
                topo = extract_topology(
                    meta, arrays["src"], arrays["dst"], arrays["cap"]
                )
            except NotSchedulingShaped:
                # not a builder-taxonomy graph: price it anyway (the
                # models only need the arc metadata) and solve on the
                # oracle, the same degradation solve_scheduling provides
                return degrade("not-scheduling-shaped", None)
        # ---- the scale lane: prune prefs, aggregate the machine axis
        # (graph/aggregate.py). The BASE topology (original machine
        # axis, pruned pref columns) is what the outcome reports and
        # the oracle degrade path prices; the SOLVE topology is what
        # the dense chain runs over — identical unless aggregation is
        # on, in which case its machine axis is the equivalence-class
        # columns and the fetched assignment expands back through the
        # plan in finish_round.
        if self.topk_prefs:
            topo = prune_topology_prefs(
                topo, meta.arc_weight, meta.arc_discount,
                self.topk_prefs,
            )
        base_topo = topo
        T, P = topo.n_tasks, topo.max_prefs
        from poseidon_tpu.solver import is_small_instance

        if (
            self.small_to_oracle
            and self.oracle_fallback
            and self._warm is None
            # T == 0 keeps the pre-dedup behavior: an empty round is
            # trivially "small" and pays neither a TPU compile nor a
            # TPU pricing dispatch (the small lane prices on CPU)
            and (T == 0 or is_small_instance(T, topo.n_machines))
        ):
            # tiny instance: the subprocess oracle beats the TPU launch
            # floor (solver.SMALL_INSTANCE_* documents the measurement)
            return degrade("small-instance", base_topo,
                           price_on_cpu=True)
        agg_plan = None
        if self.aggregate_classes:
            name = cost_model
            if isinstance(name, str) and name.isdigit():
                name = COST_MODEL_SELECTORS.get(int(name), name)
            if name == "random":
                raise ValueError(
                    "aggregate_classes requires a cost model that "
                    "prices machines by their signature; 'random' "
                    "hashes the machine index (see graph/aggregate.py)"
                )
            kw = cost_input_kwargs or {}
            agg_plan = plan_from_signatures(
                base_topo,
                machine_load=kw.get("machine_load"),
                machine_mem_free=kw.get("machine_mem_free"),
                machine_used_slots=kw.get("machine_used_slots"),
            )
            topo = aggregate_topology(base_topo, agg_plan)
        # pref-axis floor: grow-only like t/m (the pref width is the
        # static n_prefs — see pad_topology's p_min docstring)
        self._p_floor = max(topo.max_prefs, self._p_floor)
        P = self._p_floor
        dt_host = pad_topology(
            topo, t_min=self._t_floor, m_min=self._m_floor,
            p_min=self._p_floor,
        )
        Tp = dt_host.arc_unsched.shape[0]
        Mp = dt_host.slots.shape[0]
        try:
            stream_k = (
                self.stream_windows
                if self.express_lane and self.stream_windows > 0
                else 0
            )
            check_table_budget(
                Tp, Mp, mesh_width=max(self.mesh_width, 1),
                stream_windows=stream_k,
                stream_ints=_stream_event_ints(
                    self.express_max_batch, P,
                    self._stream_pw_floor, self._mi_floor,
                ) if stream_k else 0,
            )
        except DenseMemoryTooLarge as e:
            # degrade loudly BEFORE any device allocation: the guard,
            # not an OOM mid-_redensify, decides oversize instances.
            # The grow-only padding floors reset too: a floor raised by
            # a past larger cluster must not keep re-padding a fitting
            # instance over budget forever (the cost is one recompile)
            self._warm = None
            self._t_floor = 16
            self._m_floor = 16
            self._ti_floor = 1
            self._mi_floor = 1
            self._s_floor = 1
            self._p_floor = 0
            if not self.oracle_fallback:
                raise
            log.warning(
                "resident round exceeds the dense HBM budget (%s); "
                "degrading to oracle", e,
            )
            return degrade("memory-envelope", base_topo)
        if self.metrics is not None:
            # the budget guard's per-device estimate, published next
            # to the backend's LIVE bytes-in-use (cli records that
            # side): the predicted-vs-real HBM cross-check. Pure host
            # arithmetic — the same _budget_need the guard just ran.
            self.metrics.record_predicted_bytes(_budget_need(
                Tp, Mp, 1, 0, 0, max(self.mesh_width, 1)
            ))
        self._t_floor = Tp
        self._m_floor = Mp
        # power-of-two smax bound: top_k cost grows mildly with smax but
        # the static argument stays stable as per-round free slots
        # churn. Grow-only like the other floors: a packing cluster
        # shrinks its max free seats across bucket boundaries, and
        # since smax is a STATIC argument each shrink would recompile
        # the fused chain (smax is a bound, not an exact count, so
        # holding the floor changes nothing but the top_k window)
        self._s_floor = pad_bucket(
            max(int(topo.slots.max(initial=1)), 1),
            minimum=self._s_floor,
        )
        smax = min(self._s_floor, dt_host.arc_unsched.shape[0])
        timings["prep_ms"] = (time.perf_counter() - t0) * 1000

        # ---- upload + ONE fused program + ONE (async) sync -----------
        # The whole device round (cost model → densify → solve →
        # finalize) is a single compiled program (``_resident_chain``,
        # see its docstring). No intermediate block_until_ready — the
        # program pipelines into the single device_get below, the
        # round's one host sync. The download runs
        # on a background thread starting NOW, so its latency elapses
        # while the caller does next-round host work; ``solve_ms``
        # covers dispatch + execution + completion regardless of where
        # the caller was when it completed.
        #
        # The block runs under jax.transfer_guard("disallow"): the one
        # upload is an EXPLICIT device_put (permitted), the one
        # download an explicit sanctioned device_get on the fetch
        # thread — any other host sync slipping into this window
        # raises instead of silently re-adding a per-round sync.
        max_rounds = (
            self.max_rounds if self.max_rounds is not None
            else default_fuse()
        )
        model_fn = get_cost_model(cost_model)

        with no_implicit_transfers():
            with span("upload", timings, "upload_ms"):
                if self._mesh is not None:
                    # the parallel/ production lane: one batched upload
                    # laid out task-sharded / machine-replicated — the
                    # fused chain compiles as an SPMD program whose
                    # dense table is Tp/width rows per device, bit-
                    # identical to the plain layout
                    from poseidon_tpu.parallel.sharded import (
                        resident_round_shardings,
                    )

                    in_spec, dt_spec = resident_round_shardings(
                        self._mesh, dt_host
                    )
                    inputs_dev, dt = jax.device_put(
                        (inputs_host, dt_host), (in_spec, dt_spec)
                    )
                else:
                    inputs_dev, dt = jax.device_put(
                        (inputs_host, dt_host)
                    )

            t_dispatch = time.perf_counter()
            with enable_x64(True):
                (asg_d, lvl_d, floor_d, gap_d, conv_d, rounds_d,
                 phases_d, ch_dev, primal, domain_ok, chosen_d, alt_d,
                 cost_dev, dev_inst) = (
                    _resident_chain(
                        dt, inputs_dev,
                        model_fn=model_fn, n_prefs=P, smax=smax,
                        alpha=self.alpha, max_rounds=max_rounds,
                    )
                )
            state = DenseState(
                asg=asg_d, lvl=lvl_d, floor=floor_d, gap=gap_d,
                converged=conv_d, rounds=rounds_d, phases=phases_d,
            )

        def _fetch():
            # one batched download: placements + certificate + the per-
            # decision attribution pair + the warm-state mirror (lvl/
            # floor) the flight recorder seeds replays from — MORE
            # bytes on the same single sync, never a second sync
            with sanctioned_transfer():
                vals = jax.device_get((  # noqa: PTA001 -- THE round's one sanctioned placement fetch (module docstring)
                    state.asg, ch_dev, state.converged, state.rounds,
                    state.phases, primal, domain_ok, chosen_d, alt_d,
                    state.lvl, state.floor,
                ))
            return vals, time.perf_counter()

        self._inflight = True
        self.last_round_fetches = 1
        return InflightSolve(
            future=_AsyncFetch(_fetch),
            state=state,
            cost_dev=cost_dev,
            dev=dev_inst,
            machine_kwargs={
                k: (cost_input_kwargs or {}).get(k)
                for k in ("machine_load", "machine_mem_free",
                          "machine_used_slots")
            },
            arrays=arrays,
            meta=meta,
            topo=base_topo,
            dt=dt,
            model_fn=model_fn,
            n_prefs=P,
            smax=smax,
            Tp=Tp,
            Mp=Mp,
            T=T,
            n_machines=base_topo.n_machines,
            agg_plan=agg_plan,
            base_slots=base_topo.slots,
            timings=timings,
            t_dispatch=t_dispatch,
        )

    def discard_round(self, inflight: InflightSolve) -> None:
        """Join and drop an in-flight solve the caller is abandoning.

        Unlike ``finish_round`` this never re-certifies: no cold retry,
        no oracle fallback (which could block for the full oracle
        timeout inside an error-recovery path) — it only drains the
        fetch future so the worker thread is idle and the next
        ``begin_round`` starts clean. Warm state is left as it was.
        """
        if inflight.outcome is not None or inflight.consumed:
            return
        self._inflight = False
        inflight.consumed = True
        try:
            inflight.future.result(timeout_s=self._fetch_deadline_s())
        except FetchTimeout:
            # the worker is a daemon thread on an abandoned handle:
            # leak it loudly rather than block the recovery path
            self.fetch_timeouts += 1
            log.error(
                "discard_round: abandoning a placement fetch still "
                "pending after %gs", self._fetch_deadline_s(),
            )
        except Exception:
            log.exception("discard_round: in-flight fetch failed")

    def finish_round(self, inflight: InflightSolve) -> ResidentOutcome:
        """Join the async placement fetch and complete the round
        (certificate checks, warm-state commit)."""
        if inflight.outcome is not None:
            return inflight.outcome
        self._inflight = False
        inflight.consumed = True
        timings = inflight.timings
        topo = inflight.topo
        T = inflight.T
        try:
            # fetch_wait is the part of the sync the caller actually
            # blocked on; the rest elapsed under overlapped host work
            with span("fetch_wait", timings, "fetch_wait_ms"):
                (asg_np, ch_np, conv, rounds, phases, primal_np, dom_ok,
                 chosen_np, alt_np, lvl_np, floor_np), \
                    t_done = inflight.future.result(
                        timeout_s=self._fetch_deadline_s()
                    )
        except FetchTimeout:
            # degrade LOUDLY instead of blocking the round forever:
            # count it, drop the warm handle (device health unknown),
            # and re-raise — the bridge traces FETCH_TIMEOUT and the
            # driver skips the tick. The daemon fetch thread is
            # abandoned with its handle.
            self.fetch_timeouts += 1
            self._warm = None
            log.error(
                "placement fetch missed its %gs deadline "
                "(--max_solver_runtime); abandoning the round",
                self._fetch_deadline_s(),
            )
            raise
        timings["solve_ms"] = (t_done - inflight.t_dispatch) * 1000
        timings["fetch_ms"] = 0.0
        state = inflight.state

        if not bool(dom_ok):
            self._warm = None
            return self._oracle_round(
                inflight.arrays, inflight.meta, topo, inflight.cost_dev,
                timings, why="cost-domain",
            )
        if not bool(conv):
            self._warm = None
            return self._oracle_round(
                inflight.arrays, inflight.meta, topo, inflight.cost_dev,
                timings, why="uncertified",
            )

        self._warm = state
        # host mirror of the committed warm state (already-fetched
        # arrays riding the round's one sync): the flight recorder's
        # replay seed. Valid until an express batch mutates the warm
        # state on device without a full-state fetch.
        self._warm_seed = (
            np.asarray(asg_np, np.int32),  # noqa: PTA001 -- already-fetched host data
            np.asarray(lvl_np, np.int32),  # noqa: PTA001 -- already-fetched host data
            np.asarray(floor_np, np.int32),  # noqa: PTA001 -- already-fetched host data
        )
        self._warm_mutated = False
        Mp = inflight.Mp
        asg = np.asarray(asg_np[:T], np.int32)  # noqa: PTA001 -- asg_np is already-fetched HOST data (the sanctioned fetch above)
        scale = np.int64(T + 1)
        chosen64 = np.asarray(chosen_np, np.int64)[:T]  # noqa: PTA001 -- already-fetched host data
        alt64 = np.asarray(alt_np, np.int64)[:T]  # noqa: PTA001 -- already-fetched host data
        task_cost = chosen64 // scale
        from poseidon_tpu.graph.deltas import MARGIN_UNKNOWN

        task_margin = np.where(
            alt64 >= int(INF), MARGIN_UNKNOWN,
            alt64 // scale - task_cost,
        )
        plan = inflight.agg_plan
        if plan is not None:
            # scale lane: the solve ran over equivalence-class columns;
            # expand the winning class assignment back to real machines
            # (current placements preserved, so deltas reflect genuine
            # moves — graph/aggregate.py::expand_assignment)
            cols = np.where(
                (asg >= 0) & (asg < plan.n_cols), asg, -1
            ).astype(np.int32)
            asg = expand_assignment(
                plan, inflight.base_slots,
                inflight.meta.task_current, cols,
            )
        else:
            asg = np.where(
                (asg >= 0) & (asg < Mp) & (asg < inflight.n_machines),
                asg, -1,
            ).astype(np.int32)
        if self.express_lane and inflight.dev is not None:
            # keep this round's on-HBM instance warm for the express
            # lane (host maps are built lazily on first express use)
            self._express = _ExpressContext(
                dev=inflight.dev,
                dt=inflight.dt,
                cost_dev=inflight.cost_dev,
                meta=inflight.meta,
                topo=inflight.topo,
                agg_plan=inflight.agg_plan,
                assignment=asg,
                machine_kwargs=inflight.machine_kwargs or {},
                model_fn=inflight.model_fn,
                n_prefs=max(inflight.n_prefs, 1),
                smax=inflight.smax,
                Tp=inflight.Tp,
                Mp=Mp,
                T=T,
                scale=T + 1,
            )
        if self.metrics is not None:
            self.metrics.record_solver_round(
                self.last_round_fetches,
                self._warm is not None,
                self._express is not None,
            )
        return ResidentOutcome(
            assignment=asg,
            channel=np.asarray(ch_np[:T], np.int32),  # noqa: PTA001 -- already-fetched host data
            cost=int(primal_np) // (T + 1),
            backend="dense_auction",
            converged=True,
            rounds=int(rounds),
            phases=int(phases),
            topology=topo,
            timings=timings,
            task_cost=task_cost,
            task_margin=task_margin,
        )


    # ---- the express lane ------------------------------------------------

    def _express_finalize(self, ctx: _ExpressContext) -> None:
        """Build the context's host maps on first express use (off the
        round's critical path; the one O(T) walk is the uid<->row map a
        whole inter-round window of batches then shares)."""
        if ctx.ready:
            return
        ctx.uid_row = {
            u: i for i, u in enumerate(ctx.meta.task_uids)  # noqa: PTA002 -- one-time lazy build per round, amortized over every express batch of the inter-round window (not per-event work)
        }
        ctx.row_uid = {i: u for u, i in ctx.uid_row.items()}
        ctx.free_rows = list(range(ctx.Tp - 1, ctx.T - 1, -1))
        ctx.midx = {
            n: i for i, n in enumerate(ctx.meta.machine_names)  # noqa: PTA002 -- same one-time lazy build as uid_row above
        }
        ctx.rack_idx = {
            n: i for i, n in enumerate(ctx.meta.rack_names)
        }
        plan = ctx.agg_plan
        if plan is not None:
            ctx.col_of = plan.col_of_machine
            order = np.argsort(plan.col_of_machine, kind="stable")
            ctx.col_order = order
            ctx.col_bounds = np.searchsorted(
                plan.col_of_machine[order],
                np.arange(plan.n_cols + 1),
            )
            ctx.members_per_col = np.bincount(
                plan.col_of_machine, minlength=plan.n_cols
            )
            # remaining free seats per REAL machine: the round's base
            # free slots minus its placements (express placements
            # decrement at report time; completions restore)
            left = np.asarray(ctx.topo.slots, np.int64).copy()  # noqa: PTA001 -- TransportTopology.slots is host numpy by construction
            placed = ctx.assignment[ctx.assignment >= 0]
            left -= np.bincount(placed, minlength=len(left))
            ctx.member_slots_left = np.maximum(left, 0)
        # rebalancing mode: running rows are NOT express-movable (rebal
        # deltas stay round-only), so freeze them — deactivate the row,
        # turn the seat into used capacity at the machine the round
        # SEATED it on (its solved assignment; the bridge invalidates
        # the context whenever actuation diverges from that: failed
        # migrations, preemptions, deferred deltas)
        cur = np.asarray(ctx.meta.task_current)  # noqa: PTA001 -- GraphMeta.task_current is host numpy by construction
        run_rows = np.flatnonzero(cur >= 0)
        if len(run_rows):
            tgt = ctx.assignment[run_rows]
            if (tgt < 0).any():
                raise ExpressDegrade(
                    "running task preempted by the round; express "
                    "waits for the next context"
                )
            cols = (
                ctx.col_of[tgt] if ctx.col_of is not None else tgt
            ).astype(np.int32)
            ctx.pending_freeze = (
                run_rows.astype(np.int32), cols,
            )
            # one-time lazy freeze of the running block per round,
            # amortized over the inter-round window (PTA002 cannot
            # see this loop — run_rows is not a declared cluster-sized
            # name — so the former noqa here was audited dead)
            for i in run_rows.tolist():
                u = ctx.row_uid.pop(i, None)
                if u is not None:
                    ctx.uid_row.pop(u, None)
                ctx.free_rows.append(i)
        ctx.ready = True

    def express_maps(self):
        """(machine_idx, rack_idx) of the express context's round —
        what the bridge resolves arrival preference rows against (the
        builder's ``task_arc_rows`` vocabulary). None when no context
        is live. Raises ``ExpressDegrade`` when finalizing the context
        fails (e.g. a running task the round preempted) — the context
        stays set so the caller's invalidate path counts and traces
        the degrade before dropping it."""
        ctx = self._express
        if ctx is None:
            return None
        self._express_finalize(ctx)
        return ctx.midx, ctx.rack_idx

    def _express_col(self, ctx: _ExpressContext, machine_idx: int) -> int:
        return (
            int(ctx.col_of[machine_idx]) if ctx.col_of is not None
            else machine_idx
        )

    def _express_member(self, ctx: _ExpressContext, col: int) -> str:
        """Expand a winning solve column to a real machine name
        (class -> first member with a free seat, canonical order —
        the express analog of ``expand_assignment``'s fill pass)."""
        if ctx.agg_plan is None:
            if col >= len(ctx.meta.machine_names):
                raise ExpressDegrade(f"placement on padding col {col}")
            return ctx.meta.machine_names[col]
        lo, hi = ctx.col_bounds[col], ctx.col_bounds[col + 1]
        members = ctx.col_order[lo:hi]
        avail = ctx.member_slots_left[members] > 0
        if not avail.any():
            raise ExpressDegrade(f"class {col} overfull on expansion")
        m = int(members[int(np.argmax(avail))])
        ctx.member_slots_left[m] -= 1
        return ctx.meta.machine_names[m]

    def _express_mini_inputs(
        self, ctx: _ExpressContext, arrivals: list[ExpressArrival],
        kmax: int, pk: int,
    ):
        """Host CostInputs for the arrivals' task-side arcs: a mini arc
        table (unsched + cluster + pref + unsched->sink per slot) fed
        through ``build_cost_inputs_host`` with the ROUND's machine
        aggregates, so express pricing is the same registry model over
        the same input construction as the full round."""
        E = kmax * (3 + pk)
        kind = np.full(E, -1, np.int8)
        a_task = np.zeros(E, np.int32)
        a_machine = np.full(E, -1, np.int32)
        a_weight = np.zeros(E, np.int32)
        ks = np.arange(kmax, dtype=np.int32)
        kind[:kmax] = int(ArcKind.TASK_TO_UNSCHED)
        kind[kmax: 2 * kmax] = int(ArcKind.TASK_TO_CLUSTER)
        u2s = 2 * kmax + kmax * pk
        kind[u2s: u2s + kmax] = int(ArcKind.UNSCHED_TO_SINK)
        a_task[:kmax] = ks
        a_task[kmax: 2 * kmax] = ks
        a_task[u2s: u2s + kmax] = ks
        wait = np.zeros(kmax, np.int32)
        cpu = np.zeros(kmax, np.int64)
        mem = np.zeros(kmax, np.int64)
        uids = [""] * kmax
        for k, a in enumerate(arrivals):
            uids[k] = a.uid
            wait[k] = a.wait_rounds
            cpu[k] = a.cpu_milli
            mem[k] = a.mem_kb
            for j, (m, _r, wgt) in enumerate(a.prefs):
                i = 2 * kmax + k * pk + j
                kind[i] = int(
                    ArcKind.TASK_TO_MACHINE if m >= 0
                    else ArcKind.TASK_TO_RACK
                )
                a_task[i] = k
                a_machine[i] = m
                a_weight[i] = wgt
        zero = np.zeros(0, np.int32)
        mini_meta = GraphMeta(
            node_role=np.zeros(0, np.int8),
            arc_kind=kind,
            arc_task=a_task,
            arc_machine=a_machine,
            arc_rack=np.full(E, -1, np.int32),
            arc_weight=a_weight,
            arc_discount=np.zeros(E, np.int32),
            task_wait=wait,
            task_current=np.full(kmax, -1, np.int32),
            task_node=zero,
            machine_node=zero,
            node_machine=zero,
            task_uids=uids,
            machine_names=ctx.meta.machine_names,
            rack_names=[],
            job_ids=[],
            n_nodes=0,
            n_arcs=E,
        )
        kw = {
            k: v for k, v in ctx.machine_kwargs.items() if v is not None
        }
        return build_cost_inputs_host(
            E, mini_meta, task_cpu_milli=cpu, task_mem_kb=mem, **kw
        )

    def _express_put(self, tree):
        """One batched upload of host express inputs (replicated over
        the mesh in the sharded lane)."""
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._mesh, PartitionSpec())
            return jax.device_put(
                tree, jax.tree_util.tree_map(lambda _: repl, tree)
            )
        return jax.device_put(tree)

    def express_round(self, batch: ExpressBatch) -> ExpressOutcome:
        """Turn one coalesced watch-event batch into bindings WITHOUT a
        round: patch the warm on-HBM instance (retire bound rows,
        adjust slot capacities, activate+price arrival rows) and run
        the bounded eps=1 repair as ONE fused dispatch with ONE
        sanctioned fetch of only the affected placements.

        Degrades loudly (``ok=False`` + the context invalidated) on
        anything the patch vocabulary cannot represent or the
        certificate cannot prove — the events then simply wait for the
        next full round. Never raises for a representational miss.
        """
        ctx = self._express
        if ctx is None:
            return ExpressOutcome(ok=False, reason="no-context")
        if self._inflight:
            return ExpressOutcome(ok=False, reason="round-in-flight")
        timings: dict[str, float] = {}
        t0 = time.perf_counter()
        try:
            self._express_finalize(ctx)
            kmax = self.express_max_batch
            pk = ctx.n_prefs
            arrivals = batch.arrivals
            if len(arrivals) > kmax:
                raise ExpressDegrade(
                    f"{len(arrivals)} arrivals > --express_max_batch "
                    f"{kmax}"
                )
            # ---- map retires / removals / slot deltas to patches ----
            rows: list[int] = []
            cols: list[int] = []
            deltas: list[int] = []
            if ctx.pending_freeze is not None:
                # first batch of a rebalancing-mode window: freeze the
                # running block out of the express auction
                fr, fc = ctx.pending_freeze
                rows.extend(fr.tolist())
                cols.extend(fc.tolist())
                deltas.extend([-1] * len(fr))
                ctx.pending_freeze = None
            for uid, mname in batch.retires:
                r = ctx.uid_row.pop(uid, None)
                if r is None:
                    raise ExpressDegrade(f"retire of unknown {uid}")
                ctx.row_uid.pop(r, None)
                ctx.free_rows.append(r)
                m = ctx.midx.get(mname)
                if m is None:
                    raise ExpressDegrade(
                        f"retire on unknown machine {mname}"
                    )
                rows.append(r)
                cols.append(self._express_col(ctx, m))
                deltas.append(-1)
            for uid in batch.removals:
                r = ctx.uid_row.pop(uid, None)
                if r is None:
                    raise ExpressDegrade(f"removal of unknown {uid}")
                ctx.row_uid.pop(r, None)
                ctx.free_rows.append(r)
                rows.append(r)
                cols.append(-1)
                deltas.append(0)
            for mname, d in batch.slot_deltas:
                m = ctx.midx.get(mname)
                if m is None:
                    raise ExpressDegrade(
                        f"slot delta on unknown machine {mname}"
                    )
                rows.append(-1)
                cols.append(self._express_col(ctx, m))
                deltas.append(d)
                if ctx.member_slots_left is not None:
                    ctx.member_slots_left[m] = max(
                        ctx.member_slots_left[m] + d, 0
                    )
            # ---- map arrivals to rows + solve-space pref targets ----
            add_row = np.full(kmax, -1, np.int32)
            add_pm = np.full((kmax, pk), -1, np.int32)
            add_pr = np.full((kmax, pk), -1, np.int32)
            for k, a in enumerate(arrivals):
                if a.uid in ctx.uid_row:
                    raise ExpressDegrade(f"duplicate arrival {a.uid}")
                if len(a.prefs) > pk:
                    raise ExpressDegrade(
                        f"{a.uid} has {len(a.prefs)} prefs > the "
                        f"round's pref width {pk}"
                    )
                if not ctx.free_rows:
                    raise ExpressDegrade(
                        "padded task rows exhausted (cluster outgrew "
                        "the round's bucket)"
                    )
                r = ctx.free_rows.pop()
                ctx.uid_row[a.uid] = r
                ctx.row_uid[r] = a.uid
                add_row[k] = r
                for j, (m, rk, _w) in enumerate(a.prefs):
                    if m >= 0:
                        col = self._express_col(ctx, m)
                        if (ctx.members_per_col is not None
                                and ctx.members_per_col[col] != 1):
                            raise ExpressDegrade(
                                f"{a.uid} prefers machine {m} inside "
                                f"a non-singleton class (not pinned "
                                f"at the last round)"
                            )
                        add_pm[k, j] = col
                    else:
                        add_pr[k, j] = rk
            mini_host = self._express_mini_inputs(
                ctx, arrivals, kmax, pk
            )
            timings["prep_ms"] = (time.perf_counter() - t0) * 1000

            # ---- one batched upload + patch chunks + fused repair ----
            warm = self._warm
            if warm is None:
                raise ExpressDegrade("no warm state")
            t0u = time.perf_counter()
            with no_implicit_transfers():
                mini_dev, add_row_d, add_pm_d, add_pr_d, patch_dev = (
                    self._express_put((
                        mini_host, add_row, add_pm, add_pr,
                        _express_patch_chunks(rows, cols, deltas),
                    ))
                )
                timings["upload_ms"] = (
                    time.perf_counter() - t0u
                ) * 1000
                t_dispatch = time.perf_counter()
                dev = ctx.dev
                asg, lvl, floor = warm.asg, warm.lvl, warm.floor
                u_d, w_d, valid_d, s_d = (
                    dev.u, dev.w, dev.task_valid, dev.s
                )
                for rows_d, cols_d, deltas_d in patch_dev:
                    u_d, w_d, valid_d, s_d, asg, lvl = _express_patch(
                        u_d, w_d, valid_d, s_d, asg, lvl,
                        rows_d, cols_d, deltas_d,
                    )
                dev = DenseInstance(
                    c=dev.c, u=u_d, w=w_d, dgen=dev.dgen, s=s_d,
                    task_valid=valid_d, scale=dev.scale, cmax=dev.cmax,
                    smax=dev.smax,
                )
                with enable_x64(True):
                    (dev2, asg_f, lvl_f, floor_f, gap, conv, rounds_d,
                     phases, rows_out, asg_out, n_changes, domain_ok,
                     primal, n_active, report) = _express_chain(
                        dev, ctx.dt, ctx.cost_dev, mini_dev,
                        asg, lvl, floor,
                        add_row_d, add_pm_d, add_pr_d,
                        model_fn=ctx.model_fn, kmax=kmax, pk=pk,
                        alpha=self.alpha, max_rounds=EXPRESS_FUSE,
                        smax=ctx.smax,
                        change_cap=self.express_change_cap,
                    )
            self.express_fetches += 1
            if self.metrics is not None:
                self.metrics.record_express_fetch()
            with sanctioned_transfer():
                (rows_np, asg_np, n_chg, conv_np, dom_np, rnds_np,
                 primal_np) = jax.device_get((  # noqa: PTA001 -- the express batch's ONE sanctioned fetch: only the affected placements + certificate bits
                    rows_out, asg_out, n_changes, conv, domain_ok,
                    rounds_d, primal,
                ))
            timings["solve_ms"] = (
                time.perf_counter() - t_dispatch
            ) * 1000
            if not bool(dom_np):
                raise ExpressDegrade("cost domain exceeded")
            if not bool(conv_np):
                raise ExpressDegrade(
                    f"repair uncertified after {int(rnds_np)} rounds"
                )
            degrade_reason = ""
            if int(n_chg) > self.express_change_cap:
                # the repair is CERTIFIED — only the compacted log is
                # truncated. Killing the batch here (the old behavior)
                # threw away a proven optimum after its fetch already
                # happened; instead degrade LOUDLY to a full sanctioned
                # placement fetch: one extra download of the changed-
                # row mask + assignment, every placement still binds,
                # and the bridge traces EXPRESS_DEGRADE(change_cap)
                # with the context kept warm.
                degrade_reason = (
                    f"change_cap: {int(n_chg)} changed placements > "
                    f"cap {self.express_change_cap} (full placement "
                    f"fetch)"
                )
                self.express_fetches += 1
                if self.metrics is not None:
                    self.metrics.record_express_fetch()
                with sanctioned_transfer():
                    rep_np, asg_full = jax.device_get(  # noqa: PTA001 -- the change-cap degrade's one extra sanctioned fetch: full changed-row mask + assignment (certified state, loudly counted)
                        (report, asg_f)
                    )
                rows_np = np.flatnonzero(rep_np).astype(np.int32)
                asg_np = np.asarray(asg_full)[rows_np]  # noqa: PTA001 -- already-fetched host data
                n_chg = len(rows_np)
            # ---- commit: the patched instance + repaired state ARE
            # the warm state the next round/batch starts from ----
            ctx.dev = dev2
            ctx.batches += 1
            self._warm = DenseState(
                asg=asg_f, lvl=lvl_f, floor=floor_f, gap=gap,
                converged=conv, rounds=rounds_d, phases=phases,
            )
            # the warm state moved on device without a full-state
            # fetch: the host mirror is stale until the next round
            # (replays reproduce this window by re-running the
            # recorded express batches instead)
            self._warm_mutated = True
            placements: list[tuple[str, str]] = []
            for i in range(int(n_chg)):
                r = int(rows_np[i])
                uid = ctx.row_uid.get(r)
                if uid is None:
                    raise ExpressDegrade(
                        f"placement on unmapped row {r}"
                    )
                placements.append(
                    (uid, self._express_member(ctx, int(asg_np[i])))
                )
            return ExpressOutcome(
                ok=True,
                placements=placements,
                cost=int(primal_np) // ctx.scale,
                rounds=int(rnds_np),
                degrade_reason=degrade_reason,
                timings=timings,
            )
        except ExpressDegrade as e:
            self._express = None
            return ExpressOutcome(ok=False, reason=str(e),
                                  timings=timings)

    # ---- the streaming lane: accumulate / flush / finish --------------

    def _stream_apply_freeze(self, ctx: _ExpressContext, warm) -> None:
        """Rebalancing mode's first stream window: the running block's
        freeze is cluster-sized, so apply it eagerly as the synced
        lane's chunked patch dispatches (async, no fetch) instead of
        widening every window's fixed patch slice to cluster size.
        Composition order matches the synced lane exactly: freeze
        patches land before window 0's own patch + repair."""
        fr, fc = ctx.pending_freeze
        ctx.pending_freeze = None
        if not len(fr):
            return
        with no_implicit_transfers():
            chunks = self._express_put(_express_patch_chunks(
                fr.tolist(), fc.tolist(), [-1] * len(fr)
            ))
            u_d, w_d, valid_d, s_d = (
                ctx.dev.u, ctx.dev.w, ctx.dev.task_valid, ctx.dev.s
            )
            asg, lvl = warm.asg, warm.lvl
            for rows_d, cols_d, deltas_d in chunks:
                u_d, w_d, valid_d, s_d, asg, lvl = _express_patch(
                    u_d, w_d, valid_d, s_d, asg, lvl,
                    rows_d, cols_d, deltas_d,
                )
        ctx.dev = DenseInstance(
            c=ctx.dev.c, u=u_d, w=w_d, dgen=ctx.dev.dgen, s=s_d,
            task_valid=valid_d, scale=ctx.dev.scale, cmax=ctx.dev.cmax,
            smax=ctx.dev.smax,
        )
        self._warm = DenseState(
            asg=asg, lvl=lvl, floor=warm.floor, gap=warm.gap,
            converged=warm.converged, rounds=warm.rounds,
            phases=warm.phases,
        )
        self._warm_mutated = True

    def stream_window(self, batch: ExpressBatch) -> ExpressOutcome:
        """Accumulate one coalesced watch-event window into the pending
        stream batch WITHOUT solving it: encode the window into the
        fixed-shape per-window slices ``_stream_chain`` scans (arrival
        rows at kmax x pk, patches padded to the grow-only patch-width
        bucket) and stage its device upload NOW — while the previous
        batch's scan is in flight the upload overlaps it (the double
        buffer). No placements come back until ``stream_flush`` +
        ``stream_finish``; ``ok=True`` means "accumulated".

        Host maps (uid<->row, free rows, member seats) advance at
        accumulate time exactly as the synced lane's, with every
        mutation journaled so the finish-side row resolution can roll
        the map back to each window's in-scan view. Degrades exactly
        like ``express_round`` for anything the patch vocabulary
        cannot represent (ok=False; context + pending windows dropped;
        the events wait for the next full round)."""
        ctx = self._express
        if ctx is None:
            return ExpressOutcome(ok=False, reason="no-context")
        if self._inflight:
            return ExpressOutcome(ok=False, reason="round-in-flight")
        if len(self._stream_pending) >= max(self.stream_windows, 1):
            # driver contract: flush at K windows; refuse loudly
            # rather than silently grow past the compiled scan length
            self._express = None
            self._stream_pending = []
            return ExpressOutcome(
                ok=False, reason="stream buffer full (flush first)"
            )
        timings: dict[str, float] = {}
        t0 = time.perf_counter()
        journal: list[tuple[int, str | None, str | None]] = []
        try:
            self._express_finalize(ctx)
            kmax = self.express_max_batch
            pk = ctx.n_prefs
            arrivals = batch.arrivals
            if len(arrivals) > kmax:
                raise ExpressDegrade(
                    f"{len(arrivals)} arrivals > --express_max_batch "
                    f"{kmax}"
                )
            warm = self._warm
            if warm is None:
                raise ExpressDegrade("no warm state")
            if ctx.pending_freeze is not None:
                self._stream_apply_freeze(ctx, warm)
                warm = self._warm
            # ---- map retires / removals / slot deltas to patches ----
            rows: list[int] = []
            cols: list[int] = []
            deltas: list[int] = []
            for uid, mname in batch.retires:
                if uid in ctx.stream_retired:
                    # the scan already retired this row in-device at
                    # placement time (auto-retire): the confirm-driven
                    # twin must not double-apply the seat decrement
                    ctx.stream_retired.discard(uid)
                    continue
                r = ctx.uid_row.pop(uid, None)
                if r is None:
                    raise ExpressDegrade(f"retire of unknown {uid}")
                ctx.row_uid.pop(r, None)
                ctx.free_rows.append(r)
                journal.append((r, uid, None))
                m = ctx.midx.get(mname)
                if m is None:
                    raise ExpressDegrade(
                        f"retire on unknown machine {mname}"
                    )
                rows.append(r)
                cols.append(self._express_col(ctx, m))
                deltas.append(-1)
            for uid in batch.removals:
                r = ctx.uid_row.pop(uid, None)
                if r is None:
                    raise ExpressDegrade(f"removal of unknown {uid}")
                ctx.row_uid.pop(r, None)
                ctx.free_rows.append(r)
                journal.append((r, uid, None))
                rows.append(r)
                cols.append(-1)
                deltas.append(0)
            for mname, d in batch.slot_deltas:
                m = ctx.midx.get(mname)
                if m is None:
                    raise ExpressDegrade(
                        f"slot delta on unknown machine {mname}"
                    )
                rows.append(-1)
                cols.append(self._express_col(ctx, m))
                deltas.append(d)
                if ctx.member_slots_left is not None:
                    ctx.member_slots_left[m] = max(
                        ctx.member_slots_left[m] + d, 0
                    )
            # ---- map arrivals to rows + solve-space pref targets ----
            add_row = np.full(kmax, -1, np.int32)
            add_pm = np.full((kmax, pk), -1, np.int32)
            add_pr = np.full((kmax, pk), -1, np.int32)
            for k, a in enumerate(arrivals):
                if a.uid in ctx.uid_row:
                    raise ExpressDegrade(f"duplicate arrival {a.uid}")
                if len(a.prefs) > pk:
                    raise ExpressDegrade(
                        f"{a.uid} has {len(a.prefs)} prefs > the "
                        f"round's pref width {pk}"
                    )
                if not ctx.free_rows:
                    raise ExpressDegrade(
                        "padded task rows exhausted (cluster outgrew "
                        "the round's bucket)"
                    )
                r = ctx.free_rows.pop()
                ctx.uid_row[a.uid] = r
                ctx.row_uid[r] = a.uid
                journal.append((r, None, a.uid))
                add_row[k] = r
                for j, (m, rk, _w) in enumerate(a.prefs):
                    if m >= 0:
                        col = self._express_col(ctx, m)
                        if (ctx.members_per_col is not None
                                and ctx.members_per_col[col] != 1):
                            raise ExpressDegrade(
                                f"{a.uid} prefers machine {m} inside "
                                f"a non-singleton class (not pinned "
                                f"at the last round)"
                            )
                        add_pm[k, j] = col
                    else:
                        add_pr[k, j] = rk
            mini_host = self._express_mini_inputs(
                ctx, arrivals, kmax, pk
            )
            # fixed-width patch slice under a grow-only bucket floor:
            # a window with a bigger backlog grows the floor (one
            # recompile); steady state never recompiles
            pw = pad_bucket(
                max(len(rows), 1), minimum=self._stream_pw_floor
            )
            if pw > self._stream_pw_floor:
                self._stream_pw_floor = pw
            prow = np.full(pw, -1, np.int32)
            pcol = np.full(pw, -1, np.int32)
            pdelta = np.zeros(pw, np.int32)
            n = len(rows)
            prow[:n] = rows
            pcol[:n] = cols
            pdelta[:n] = deltas
            timings["prep_ms"] = (time.perf_counter() - t0) * 1000
            host = (mini_host, add_row, add_pm, add_pr,
                    prow, pcol, pdelta)
            t0u = time.perf_counter()
            with no_implicit_transfers():
                devt = self._express_put(host)
            timings["upload_ms"] = (time.perf_counter() - t0u) * 1000
            self._stream_pending.append(_StreamWindow(
                host=host, dev=devt, pw=pw, journal=journal,
                prep_ms=timings["prep_ms"],
                upload_ms=timings["upload_ms"],
            ))
            return ExpressOutcome(ok=True, timings=timings)
        except ExpressDegrade as e:
            self._express = None
            self._stream_pending = []
            return ExpressOutcome(ok=False, reason=str(e),
                                  timings=timings)

    def stream_flush(self) -> None:
        """Dispatch the accumulated windows as ONE ``_stream_chain``
        scan and start the ONE background fetch of the K compacted
        decision logs. No-op when nothing is pending or a batch is
        already in flight (``stream_finish`` first — the certificate
        join serializes scans). Never joins: between flush and finish
        the next batch's windows accumulate and stage their uploads
        against the in-flight scan."""
        if not self._stream_pending:
            return
        if self._stream_inflight is not None:
            return
        ctx = self._express
        warm = self._warm
        if ctx is None or warm is None:
            self._stream_pending = []
            return
        windows = list(self._stream_pending)
        self._stream_pending = []
        K = max(self.stream_windows, 1)
        real = len(windows)
        timings = {
            "prep_ms": sum(w.prep_ms for w in windows),
            "upload_ms": sum(w.upload_ms for w in windows),
        }
        kmax = self.express_max_batch
        pk = ctx.n_prefs
        pw = self._stream_pw_floor
        t0 = time.perf_counter()
        with no_implicit_transfers():
            for wdw in windows:
                if wdw.pw != pw:
                    # the patch-width floor grew mid-batch: re-pad +
                    # re-stage the earlier windows (once per floor
                    # growth; zero in steady state)
                    mini, a_r, a_pm, a_pr, pr0, pc0, pd0 = wdw.host
                    pr1 = np.full(pw, -1, np.int32)
                    pc1 = np.full(pw, -1, np.int32)
                    pd1 = np.zeros(pw, np.int32)
                    pr1[:len(pr0)] = pr0
                    pc1[:len(pc0)] = pc0
                    pd1[:len(pd0)] = pd0
                    wdw.host = (mini, a_r, a_pm, a_pr, pr1, pc1, pd1)
                    wdw.dev = self._express_put(wdw.host)
                    wdw.pw = pw
            if real < K:
                # draining flush: pad to the compiled scan length with
                # no-op windows (no arrivals, no patches) — the same
                # shapes, so the same compiled program
                noop_host = (
                    self._express_mini_inputs(ctx, [], kmax, pk),
                    np.full(kmax, -1, np.int32),
                    np.full((kmax, pk), -1, np.int32),
                    np.full((kmax, pk), -1, np.int32),
                    np.full(pw, -1, np.int32),
                    np.full(pw, -1, np.int32),
                    np.zeros(pw, np.int32),
                )
                noop = _StreamWindow(
                    host=noop_host, dev=self._express_put(noop_host),
                    pw=pw, journal=[],
                )
                windows = windows + [noop] * (K - real)
            # stack the staged per-window device slices into the
            # [K, ...] event-stream buffer (pure device reshuffle:
            # async dispatches, no host sync)
            stacked = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *[w.dev for w in windows]
            )
            (mini_s, add_row_s, add_pm_s, add_pr_s,
             prow_s, pcol_s, pdelta_s) = stacked
            timings["stack_ms"] = (time.perf_counter() - t0) * 1000
            t_dispatch = time.perf_counter()
            with enable_x64(True):
                carry, ys = _stream_chain(
                    ctx.dev, ctx.dt, ctx.cost_dev, mini_s,
                    warm.asg, warm.lvl, warm.floor,
                    add_row_s, add_pm_s, add_pr_s,
                    prow_s, pcol_s, pdelta_s,
                    model_fn=ctx.model_fn, kmax=kmax, pk=pk,
                    alpha=self.alpha, max_rounds=EXPRESS_FUSE,
                    smax=ctx.smax,
                    change_cap=self.express_change_cap,
                )
        self.stream_fetches += 1
        self.last_stream_fetches = 1
        self.last_stream_windows = real
        if self.metrics is not None:
            self.metrics.record_stream_fetch()

        def _fetch():
            with sanctioned_transfer():
                return jax.device_get(ys)  # noqa: PTA001 -- the stream batch's ONE sanctioned fetch: K compacted decision logs + certificate bits

        self._stream_inflight = _InflightStream(
            future=_AsyncFetch(_fetch),
            carry=carry, ctx=ctx, n_windows=real,
            journals=[w.journal for w in windows[:real]],
            # ONE snapshot per K-window flush, amortized across the
            # whole stream batch (finish's row resolution rolls it
            # back through the per-window journals)
            row_uid_end=dict(ctx.row_uid),
            timings=timings, t_dispatch=t_dispatch,
        )

    def stream_finish(self) -> StreamOutcome | None:
        """Join the in-flight stream batch: the ONE fetch carrying K
        windows' compacted decision logs + certificate bits. Commits
        the scan's final carry as the warm on-HBM state (the latch
        guarantees it is the last GOOD window's state even when a
        later window failed), resolves each window's compacted rows to
        uids through the journal rollback, and expands aggregation
        columns to members exactly as the synced lane does. Returns
        None when nothing is in flight; never raises."""
        inf = self._stream_inflight
        if inf is None:
            return None
        self._stream_inflight = None
        ctx = inf.ctx
        real = inf.n_windows
        try:
            fetched = inf.future.result(self._fetch_deadline_s())
        except FetchTimeout:
            self.fetch_timeouts += 1
            # the device link is suspect: drop everything warm (the
            # same abandon the round path makes) — never a silent wait
            self._express = None
            self._stream_pending = []
            self._warm = None
            self._warm_mutated = True
            return StreamOutcome(
                ok=False, reason="stream fetch deadline missed",
                windows=real, fetches=1, timings=inf.timings,
            )
        (rows_np, asg_np, nchg_np, live_np, conv_np, dom_np, rnds_np,
         primal_np) = fetched
        timings = dict(inf.timings)
        timings["solve_ms"] = (
            time.perf_counter() - inf.t_dispatch
        ) * 1000
        if self._express is not ctx:
            # a degrade invalidated the context between flush and
            # finish: nothing to commit against, and the events are
            # already waiting for the round path
            return StreamOutcome(
                ok=False, reason="context invalidated mid-flight",
                windows=real, fetches=1, timings=timings,
            )
        # ---- first failed window (if any) + its reason ----
        failed = -1
        reason = ""
        for wdx in range(real):
            if bool(live_np[wdx]):
                continue
            failed = wdx
            if not bool(dom_np[wdx]):
                reason = f"window {wdx}: cost domain exceeded"
            elif not bool(conv_np[wdx]):
                reason = (
                    f"window {wdx}: repair uncertified after "
                    f"{int(rnds_np[wdx])} rounds"
                )
            elif int(nchg_np[wdx]) > self.express_change_cap:
                reason = (
                    f"window {wdx}: change_cap: {int(nchg_np[wdx])} "
                    f"changed placements > cap "
                    f"{self.express_change_cap}"
                )
            else:
                reason = f"window {wdx}: certificate failed"
            break
        good = real if failed < 0 else failed
        # ---- commit the final carry as the warm on-HBM state (the
        # last good window's state: valid even mid-stream-failure) ----
        (c_d, u_d, w_d, s_d, valid_d, asg_d, lvl_d, floor_d,
         _live) = inf.carry
        ctx.dev = DenseInstance(
            c=c_d, u=u_d, w=w_d, dgen=ctx.dev.dgen, s=s_d,
            task_valid=valid_d, scale=ctx.dev.scale, cmax=ctx.dev.cmax,
            smax=ctx.dev.smax,
        )
        ctx.batches += good
        self._warm = DenseState(
            asg=asg_d, lvl=lvl_d, floor=floor_d,
            gap=jnp.int32(0), converged=jnp.asarray(True),
            rounds=jnp.int32(0), phases=jnp.int32(0),
        )
        self._warm_mutated = True
        # ---- resolve per-window compacted rows to uids: roll the
        # row<->uid map back through the journals, last window first
        # (each window resolves against the exact map state its scan
        # step saw) ----
        Tp = ctx.Tp
        cap = self.express_change_cap
        by_win: dict[int, list[tuple[str, int]]] = {}
        cur = inf.row_uid_end
        bad = ""
        for wdx in range(real - 1, -1, -1):
            if wdx < good and not bad:
                out: list[tuple[str, int]] = []
                for i in range(min(int(nchg_np[wdx]), cap)):
                    r = int(rows_np[wdx, i])
                    if r >= Tp:
                        break
                    uid = cur.get(r)
                    if uid is None:
                        bad = (
                            f"window {wdx}: placement on unmapped "
                            f"row {r}"
                        )
                        break
                    out.append((uid, int(asg_np[wdx, i])))
                by_win[wdx] = out
            for row, old, _new in reversed(inf.journals[wdx]):
                if old is None:
                    cur.pop(row, None)
                else:
                    cur[row] = old
        if bad:
            self._express = None
            self._stream_pending = []
            return StreamOutcome(
                ok=False, reason=bad, windows=real, fetches=1,
                timings=timings,
            )
        # ---- expand columns to members in forward window order (the
        # synced lane's per-batch report order, so seat accounting
        # matches bit-for-bit) ----
        placements: list[tuple[str, str, int]] = []
        try:
            for wdx in range(good):
                for uid, col in by_win.get(wdx, ()):
                    placements.append(
                        (uid, self._express_member(ctx, col), wdx)
                    )
        except ExpressDegrade as e:
            self._express = None
            self._stream_pending = []
            return StreamOutcome(
                ok=False, reason=str(e), windows=real, fetches=1,
                timings=timings,
            )
        # host twin of the scan's auto-retire: free the placed rows
        # and mark the uids so the confirm-driven retire is a no-op
        for uid, _m, _w in placements:
            r = ctx.uid_row.pop(uid, None)
            if r is not None:
                ctx.row_uid.pop(r, None)
                ctx.free_rows.append(r)
            ctx.stream_retired.add(uid)
        window_costs = [
            int(primal_np[w]) // ctx.scale for w in range(good)
        ]
        window_rounds = [int(rnds_np[w]) for w in range(good)]
        if failed >= 0:
            self._express = None
            self._stream_pending = []
            return StreamOutcome(
                ok=False, placements=placements,
                window_costs=window_costs,
                window_rounds=window_rounds, windows=real,
                failed_window=failed, reason=reason, fetches=1,
                timings=timings,
            )
        return StreamOutcome(
            ok=True, placements=placements,
            window_costs=window_costs, window_rounds=window_rounds,
            windows=real, fetches=1, timings=timings,
        )

    # margin on the oracle degrade path needs the full [T, M] route
    # table on host; above this many cells it is skipped (cost still
    # computed — margins report MARGIN_UNKNOWN). Degraded rounds are
    # the rare path, and a memory-envelope degrade is by definition a
    # table too big to materialize anywhere.
    ORACLE_MARGIN_CELLS = 1 << 22

    @staticmethod
    def _host_decision_stats(topo, cost_host, asg):
        """Host twin of ``_decision_stats`` for oracle-solved rounds:
        per-task chosen route cost + runner-up alternative from the
        priced arc table (vectorized numpy; the chosen-route part is
        O(T·P), the runner-up part O(T·M) and skipped over the cell
        budget)."""
        from poseidon_tpu.graph.deltas import MARGIN_UNKNOWN
        from poseidon_tpu.ops.transport import (
            INF as TINF,
            instance_from_topology,
        )

        inst = instance_from_topology(topo, cost_host)
        T, M = inst.n_tasks, inst.n_machines
        if T == 0:
            z = np.zeros(0, np.int64)
            return z, z
        asg = np.asarray(asg, np.int64)  # noqa: PTA001 -- oracle-path input is host data (the degrade path already downloaded everything)
        on = asg >= 0
        m = np.clip(asg, 0, max(M - 1, 0))
        best = np.where(on, inst.w + inst.d[m], TINF)
        hit_m = inst.pref_machine == asg[:, None]
        pc = np.where(hit_m, inst.pref_cost, TINF)
        hit_r = (inst.pref_rack >= 0) & (
            inst.pref_rack == inst.rack_of[m][:, None]
        )
        pc = np.minimum(
            pc, np.where(hit_r, inst.pref_cost + inst.ra[m][:, None],
                         TINF)
        )
        best = np.minimum(best, pc.min(axis=1, initial=TINF))
        chosen = np.where(on, best, inst.u).astype(np.int64)
        if T * M > ResidentSolver.ORACLE_MARGIN_CELLS:
            return chosen, np.full(T, MARGIN_UNKNOWN, np.int64)
        # full route table [T, M]: cluster channel + pref channels
        row = inst.w[:, None] + inst.d[None, :]
        for k in range(inst.max_prefs):
            pm = inst.pref_machine[:, k: k + 1]
            pr = inst.pref_rack[:, k: k + 1]
            pck = inst.pref_cost[:, k: k + 1]
            mids = np.arange(M)[None, :]
            row = np.minimum(
                row, np.where((pm == mids) & (pm >= 0), pck, TINF)
            )
            hit = (pr >= 0) & (pr == inst.rack_of[None, :])
            row = np.minimum(
                row, np.where(hit, pck + inst.ra[None, :], TINF)
            )
        masked = np.where(
            (np.arange(M)[None, :] == asg[:, None]) & on[:, None],
            TINF, row,
        )
        alt_m = masked.min(axis=1, initial=TINF)
        alt = np.where(on, np.minimum(alt_m, inst.u), alt_m)
        margin = np.where(
            alt >= TINF, MARGIN_UNKNOWN, alt - chosen
        ).astype(np.int64)
        return chosen, margin

    def _oracle_round(
        self, arrays, meta, topo, cost_dev, timings, *, why: str
    ) -> ResidentOutcome:
        """Degrade one round to the C++ oracle (downloads the arc table).

        ``topo`` is None on a non-taxonomy graph — the outcome then
        carries no topology and cannot be flow-decomposed via
        ``flows_from_assignment`` (its channel codes are -1).
        """
        if not self.oracle_fallback:
            raise RuntimeError(
                f"resident solve failed ({why}) and oracle fallback is "
                f"disabled"
            )
        from poseidon_tpu.graph.decompose import extract_placements
        from poseidon_tpu.oracle import solve_oracle

        t0 = time.perf_counter()
        self.last_round_fetches += 1
        with sanctioned_transfer():
            fetched = jax.device_get(cost_dev)  # noqa: PTA001 -- sanctioned degrade-path download of the priced arc table for the oracle
        cost_host = np.asarray(fetched, np.int32)[: meta.n_arcs]  # noqa: PTA001 -- already-fetched host data
        net = FlowNetwork.from_arrays(
            arrays["src"], arrays["dst"], arrays["cap"], cost_host,
            arrays["supply"],
        )
        o = solve_oracle(
            net, algorithm="cost_scaling", timeout_s=self.oracle_timeout_s
        )
        placements = extract_placements(
            np.asarray(o.flows, np.int64), meta,  # noqa: PTA001 -- oracle output is host data
            arrays["src"], arrays["dst"],
        )
        T = len(meta.task_uids)
        midx = {name: i for i, name in enumerate(meta.machine_names)}
        asg = np.full(T, -1, np.int32)
        for i, uid in enumerate(meta.task_uids):
            m = placements.get(uid)
            if m is not None:
                asg[i] = midx[m]
        task_cost = task_margin = None
        if topo is not None:
            # real channel codes, so the outcome remains
            # flow-decomposable just like a dense one
            from poseidon_tpu.ops.dense_auction import _channels_for

            channel = _channels_for(
                instance_from_topology(topo, cost_host), asg
            )
            task_cost, task_margin = self._host_decision_stats(
                topo, cost_host, asg
            )
        else:
            channel = np.full(T, -1, np.int32)
        timings["oracle_ms"] = (time.perf_counter() - t0) * 1000
        if self.metrics is not None:
            self.metrics.record_solver_round(
                self.last_round_fetches,
                self._warm is not None,
                self._express is not None,
            )
        return ResidentOutcome(
            assignment=asg,
            channel=channel,
            cost=int(o.cost),
            backend=f"oracle:{why}",
            converged=True,
            rounds=0,
            phases=0,
            topology=topo,
            timings=timings,
            task_cost=task_cost,
            task_margin=task_margin,
        )
