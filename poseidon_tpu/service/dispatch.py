"""Shape-bucket router + batch dispatcher: N tenants, one device.

The solver seam of the multi-tenant service (see ``service/service.py``
for the front door). Each tenant's ``SchedulerBridge`` talks to a
``TenantSolver`` — the same ``begin_round``/``finish_round`` surface as
``ResidentSolver`` — but begin registers the tenant's priced instance
with the shared ``BatchDispatcher`` instead of dispatching it alone.
At launch, queued instances group into shape buckets keyed by their
padded (Tp, Mp, P) dims (each tenant pads to its OWN grow-only floors
— ``ops/resident.TenantWarmPool`` — so a tenant's in-bucket solve is
the same function as its solo solve, and steady-state dispatches hit
zero recompiles), and each bucket solves as one batched device
program: ONE ``device_put`` of the stacked channel tables, per-member
pipelined dispatches of the unchanged ``ops/batch._solve_member``
kernel (NOT a vmapped lockstep — see ops/batch.py's measured
economics), and ONE batched ``device_get`` running on a background
thread from the moment of dispatch.

Pricing runs on the host CPU backend (the same rule as the resident
lane's small-instance degrade path): the registry cost models are
O(arcs) elementwise jnp, so a per-tenant pricing fetch on the CPU
backend never crosses the device link — the solve's batched fetch is
the dispatch's one sanctioned download.

Per-tenant exactness: a member's bucketed solve is bit-identical to
its solo ``solve_transport_dense`` (tests/test_service.py pins this
across cost models, preemption modes, and mixed shape buckets); an
uncertified warm solve retries cold, and anything past that degrades
LOUDLY to the C++ oracle for that tenant alone — never a silent wrong
placement, and never a stall for the rest of the batch.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from poseidon_tpu.graph.builder import GraphMeta
from poseidon_tpu.graph.network import FlowNetwork, pad_bucket
from poseidon_tpu.guards import (
    CompileCounter,
    FetchTimeout,
    no_implicit_transfers,
    sanctioned_transfer,
)
from poseidon_tpu.models.costs import build_cost_inputs_host
from poseidon_tpu.ops.batch import (
    MEMBER_KEYS,
    _solve_member,
    build_member_tables,
    member_bucket_dims,
    stack_members,
)
from poseidon_tpu.ops.dense_auction import (
    I32,
    CostDomainTooLarge,
    DenseMemoryTooLarge,
    DenseState,
    _channels_for,
    check_table_budget,
    default_fuse,
    max_variants_for,
    member_side_ints,
)
from poseidon_tpu.ops.resident import (
    ResidentOutcome,
    TenantWarmPool,
    _AsyncFetch,
    _jitted_model,
)
from poseidon_tpu.ops.transport import (
    NotSchedulingShaped,
    TransportTopology,
    extract_topology,
    instance_from_topology,
)

log = logging.getLogger(__name__)

# Budget accounting: each bucket member is charged its dense table
# plus TWICE its channel side tables (ops/dense_auction
# .member_side_ints). The 2x covers the batch-axis padding — stacked
# uploads pad the member count to a grow-only pad_bucket width, which
# is at most 2x the live member count, and padding slots carry channel
# tables only (their dense tables are never materialized: padding
# members are never dispatched).
def _budget_side_ints(Tp: int, Mp: int, P: int) -> int:
    return 2 * member_side_ints(Tp, Mp, P)


@dataclasses.dataclass
class PendingSolve:
    """One tenant's registered-but-not-yet-dispatched round solve.

    The service-lane analog of ``ops/resident.InflightSolve``: returned
    by ``TenantSolver.begin_round``, consumed by ``finish_round``.
    Degrade paths (non-taxonomy graph, cost domain, empty bucket)
    resolve synchronously and carry ``outcome`` directly.
    """

    tenant: str = ""
    outcome: ResidentOutcome | None = None
    inst: object = None              # TransportInstance
    meta: GraphMeta | None = None
    topo: TransportTopology | None = None
    arrays: dict | None = None
    cost_host: np.ndarray | None = None
    tables: dict | None = None       # padded host channel tables
    T: int = 0
    n_machines: int = 0
    Tp: int = 0
    Mp: int = 0
    P: int = 0
    smax: int = 1
    warm: DenseState | None = None
    warm_used: bool = False
    chunk: object = None             # _Chunk, set at launch
    slot: int = -1
    timings: dict = dataclasses.field(default_factory=dict)
    consumed: bool = False


@dataclasses.dataclass
class _Chunk:
    """One launched bucket chunk: stacked device tables + the in-flight
    batched fetch + per-member device state refs."""

    key: tuple
    members: list
    stacked: object = None           # device tree of stacked tables
    future: _AsyncFetch | None = None
    states: list = dataclasses.field(default_factory=list)
    smax: int = 1
    t_dispatch: float = 0.0
    # set when the chunk's batched fetch missed its deadline: later
    # members fail FAST instead of each re-waiting the full timeout on
    # the same dead future (an 8-member chunk would otherwise stall
    # the wave for 8 x the deadline)
    failed: bool = False


class TenantSolver:
    """The ResidentSolver-shaped seam one tenant's bridge drives.

    ``begin_round`` prices the tenant's graph (host CPU backend),
    compacts it to transportation form, and registers it with the
    shared dispatcher; ``finish_round`` joins the bucket's batched
    fetch and completes this tenant's round (certificate check, cold
    retry, warm-context commit, oracle degrade). The debug handles
    (``last_instance`` / ``last_assignment`` / ``last_cost_host``)
    feed the tests' bit-identity proofs.
    """

    def __init__(self, tenant_id: str, dispatcher: "BatchDispatcher"):
        self.tenant_id = tenant_id
        self.dispatcher = dispatcher
        # bridge-compat surface (the bridge reads/sets these)
        self.fetch_timeouts = 0
        self.oracle_timeout_s = dispatcher.oracle_timeout_s
        self.express_fetches = 0
        # bit-identity hooks: the last round's exact solver inputs and
        # output, host-side (tests re-solve them solo)
        self.last_instance = None
        self.last_assignment = None
        self.last_cost_host = None
        self.last_arrays = None
        self.last_meta = None
        self.last_backend = ""

    # ---- bridge-compat stubs (no express lane in the service yet) ----

    @property
    def express_ready(self) -> bool:
        return False

    def invalidate_express(self) -> None:
        pass

    @property
    def warm(self):
        ctx = self.dispatcher.pool.context(self.tenant_id)
        return ctx.state

    def reset(self) -> None:
        self.dispatcher.pool.invalidate(self.tenant_id)

    # ---- the round ----------------------------------------------------

    def begin_round(
        self,
        arrays: dict[str, np.ndarray],
        meta: GraphMeta,
        *,
        cost_model: str,
        cost_input_kwargs: dict | None = None,
        topology: TransportTopology | None = None,
    ) -> PendingSolve:
        """Price + compact + register one tenant round with the shared
        dispatcher. Returns a ``PendingSolve``; the batched dispatch
        happens at the service's next ``launch()`` (or lazily on this
        tenant's ``finish_round`` — the serial one-tenant case)."""
        timings: dict[str, float] = {}
        t0 = time.perf_counter()
        ctx = self.dispatcher.pool.context(self.tenant_id)
        topo = topology
        if topo is None:
            try:
                topo = extract_topology(
                    meta, arrays["src"], arrays["dst"], arrays["cap"]
                )
            except NotSchedulingShaped:
                topo = None
        # ---- price on the host CPU backend (O(arcs) elementwise) ----
        ctx.e_floor = pad_bucket(max(meta.n_arcs, 1), minimum=ctx.e_floor)
        ctx.ti_floor = pad_bucket(
            max(len(meta.task_uids), 1), minimum=ctx.ti_floor
        )
        ctx.mi_floor = pad_bucket(
            max(len(meta.machine_names), 1), minimum=ctx.mi_floor
        )
        inputs_host = build_cost_inputs_host(
            ctx.e_floor, meta, t_min=ctx.ti_floor, m_min=ctx.mi_floor,
            **(cost_input_kwargs or {}),
        )
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # no CPU backend registered: default dev
            cpu = None
        inputs_dev = (
            jax.device_put(inputs_host, cpu)
            if cpu is not None else jax.device_put(inputs_host)
        )
        cost = _jitted_model(cost_model)(inputs_dev)
        fetched = jax.device_get(cost)  # noqa: PTA001 -- host-CPU pricing fetch: the model ran on the CPU backend (no device-link crossing); the solve's batched fetch is the dispatch's one sanctioned download
        cost_host = np.asarray(fetched, np.int32)[: meta.n_arcs]  # noqa: PTA001 -- already-fetched host data
        timings["prep_ms"] = (time.perf_counter() - t0) * 1000
        self.last_cost_host = cost_host
        self.last_arrays = arrays
        self.last_meta = meta
        if topo is None:
            # non-taxonomy graph: the batched transportation form does
            # not apply — solve this tenant on the oracle now (the same
            # deliberate routing the resident lane makes)
            return PendingSolve(
                tenant=self.tenant_id,
                outcome=self._oracle_outcome(
                    arrays, meta, None, cost_host, timings,
                    why="not-scheduling-shaped",
                ),
            )
        inst = instance_from_topology(topo, cost_host)
        self.last_instance = inst
        return self.dispatcher.register(
            self, inst, arrays, meta, topo, cost_host, timings
        )

    def finish_round(self, pending: PendingSolve) -> ResidentOutcome:
        """Join this tenant's slice of the batched fetch and complete
        the round. Launches the dispatcher first if the wave was never
        launched (the serial path)."""
        if pending.outcome is not None:
            pending.consumed = True
            self.last_backend = pending.outcome.backend
            return pending.outcome
        self.dispatcher.ensure_launched(pending)
        out = self.dispatcher.finish(self, pending)
        self.last_backend = out.backend
        return out

    def discard_round(self, pending: PendingSolve) -> None:
        """Join and drop (driver error path) — drains the chunk fetch
        so the worker thread idles; warm state is left as it was."""
        if pending.outcome is not None or pending.consumed:
            return
        pending.consumed = True
        chunk = pending.chunk
        if chunk is None or chunk.future is None:
            return
        if chunk.failed:
            return  # deadline already paid by a bucket-mate
        try:
            chunk.future.result(
                timeout_s=self.dispatcher.oracle_timeout_s
            )
        except FetchTimeout:
            chunk.failed = True
            self.fetch_timeouts += 1
            log.error(
                "discard_round(%s): abandoning a batched fetch still "
                "pending", self.tenant_id,
            )
        except Exception:
            log.exception(
                "discard_round(%s): in-flight fetch failed",
                self.tenant_id,
            )

    def _oracle_outcome(
        self, arrays, meta, topo, cost_host, timings, *, why: str
    ) -> ResidentOutcome:
        """Degrade ONE tenant's round to the C++ oracle (host costs are
        already in hand — no device download needed here, unlike the
        resident degrade path)."""
        from poseidon_tpu.graph.decompose import extract_placements
        from poseidon_tpu.oracle import solve_oracle

        t0 = time.perf_counter()
        net = FlowNetwork.from_arrays(
            arrays["src"], arrays["dst"], arrays["cap"], cost_host,
            arrays["supply"],
        )
        o = solve_oracle(
            net, algorithm="cost_scaling",
            timeout_s=self.oracle_timeout_s,
        )
        placements = extract_placements(
            np.asarray(o.flows, np.int64), meta,
            arrays["src"], arrays["dst"],
        )
        T = len(meta.task_uids)
        midx = {name: i for i, name in enumerate(meta.machine_names)}
        asg = np.full(T, -1, np.int32)
        for i, uid in enumerate(meta.task_uids):
            m = placements.get(uid)
            if m is not None:
                asg[i] = midx[m]
        if topo is not None:
            channel = _channels_for(
                instance_from_topology(topo, cost_host), asg
            )
        else:
            channel = np.full(T, -1, np.int32)
        timings["oracle_ms"] = (time.perf_counter() - t0) * 1000
        self.last_assignment = asg
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
        )


class BatchDispatcher:
    """Groups registered tenant solves into shape buckets and solves
    each bucket as one batched device program with one batched fetch.

    Single-threaded by contract (the service pump thread owns it, like
    ``SchedulerBridge``); the only cross-thread structure is the
    ``_AsyncFetch`` handle each launched chunk carries. ``max_batch``
    bounds instances per chunk on top of the HBM budget's own fit
    (``max_variants_for``) — an oversize wave splits into several
    fitting dispatches, each with its own sanctioned fetch.
    """

    def __init__(
        self,
        *,
        alpha: int = 1024,
        max_rounds: int | None = None,
        oracle_fallback: bool = True,
        oracle_timeout_s: float = 1000.0,
        max_batch: int = 64,
        metrics=None,
    ):
        self.alpha = alpha
        self.max_rounds = (
            max_rounds if max_rounds is not None else default_fuse()
        )
        self.oracle_fallback = oracle_fallback
        self.oracle_timeout_s = oracle_timeout_s
        self.max_batch = max(max_batch, 1)
        self.metrics = metrics
        self.pool = TenantWarmPool()
        self._queue: list[PendingSolve] = []
        # grow-only per-bucket floors: batch-axis width and smax are
        # STATIC kernel knobs, so a churning tenant count / free-slot
        # high-water must not recompile the member kernel (satellite:
        # bucket dims ride grow-only floors too)
        self._b_floor: dict[tuple, int] = {}
        self._smax_floor: dict[tuple, int] = {}
        # observability: lifetime dispatches and the last launch's
        # compile count (0 in steady state, as
        # tests/test_service.py::TestZeroRecompile asserts)
        self.dispatches = 0
        self.last_launch_compiles = 0

    # ---- registration --------------------------------------------------

    def register(
        self, solver: TenantSolver, inst, arrays, meta, topo,
        cost_host, timings,
    ) -> PendingSolve:
        ctx = self.pool.context(solver.tenant_id)
        Tp, Mp, P = member_bucket_dims(
            inst, t_min=ctx.t_floor, m_min=ctx.m_floor,
            p_min=ctx.p_floor,
        )
        try:
            check_table_budget(
                Tp, Mp, 1,
                side_ints_per_variant=_budget_side_ints(Tp, Mp, P),
            )
            tables = build_member_tables(inst, Tp, Mp, P)
        except DenseMemoryTooLarge as e:
            # this tenant alone blows the budget: degrade it (and reset
            # its floors — a floor raised by a past larger cluster must
            # not re-pad a fitting instance over budget forever)
            self.pool.reset_floors(solver.tenant_id)
            if not self.oracle_fallback:
                raise
            log.warning(
                "tenant %s exceeds the dense HBM budget (%s); "
                "degrading to oracle", solver.tenant_id, e,
            )
            return PendingSolve(
                tenant=solver.tenant_id,
                outcome=solver._oracle_outcome(
                    arrays, meta, topo, cost_host, timings,
                    why="memory-envelope",
                ),
            )
        except (CostDomainTooLarge, ValueError) as e:
            if not self.oracle_fallback:
                raise
            log.warning(
                "tenant %s rejected by the dense kernel (%s); "
                "degrading to oracle", solver.tenant_id, e,
            )
            return PendingSolve(
                tenant=solver.tenant_id,
                outcome=solver._oracle_outcome(
                    arrays, meta, topo, cost_host, timings,
                    why="cost-domain",
                ),
            )
        ctx.t_floor, ctx.m_floor, ctx.p_floor = Tp, Mp, P
        ctx.s_floor = pad_bucket(
            max(int(inst.slots.max(initial=1)), 1), minimum=ctx.s_floor
        )
        pending = PendingSolve(
            tenant=solver.tenant_id,
            inst=inst,
            meta=meta,
            topo=topo,
            arrays=arrays,
            cost_host=cost_host,
            tables=tables,
            T=inst.n_tasks,
            n_machines=inst.n_machines,
            Tp=Tp,
            Mp=Mp,
            P=P,
            smax=min(ctx.s_floor, Tp),
            warm=self.pool.warm(solver.tenant_id, Tp, Mp),
            timings=timings,
        )
        pending.warm_used = pending.warm is not None
        self._queue.append(pending)
        return pending

    def ensure_launched(self, pending: PendingSolve) -> None:
        if pending.chunk is None and pending.outcome is None:
            self.launch()

    # ---- launch: bucket, stack, upload, dispatch, async fetch ----------

    def launch(self) -> int:
        """Dispatch every registered solve: group by (Tp, Mp, P) shape
        bucket, chunk against the HBM budget + ``max_batch``, and for
        each chunk do ONE upload, per-member kernel dispatches, and ONE
        batched background fetch. Returns the number of chunks."""
        queue, self._queue = self._queue, []
        if not queue:
            return 0
        buckets: dict[tuple, list[PendingSolve]] = {}
        for p in queue:
            buckets.setdefault((p.Tp, p.Mp, p.P), []).append(p)
        n_chunks = 0
        counter = CompileCounter()
        with counter:
            waves: list[tuple[tuple, list]] = []
            for key, members in sorted(buckets.items()):
                Tp, Mp, P = key
                fit = max_variants_for(
                    Tp, Mp,
                    side_ints_per_variant=_budget_side_ints(Tp, Mp, P),
                )
                width = max(min(self.max_batch, fit), 1)
                for i in range(0, len(members), width):
                    waves.append((key, members[i: i + width]))
            # wave streaming: stage wave 0, then dispatch wave k and
            # stage wave k+1 back-to-back — the next wave's member-
            # table upload overlaps the in-flight wave's (async) member
            # dispatches, so N waves pay ONE batched fetch each with
            # zero idle gap between them (the service-lane twin of the
            # resident stream lane's double buffer)
            staged = self._stage_chunk(*waves[0]) if waves else None
            for j in range(len(waves)):
                self._dispatch_chunk(staged)
                n_chunks += 1
                staged = (
                    self._stage_chunk(*waves[j + 1])
                    if j + 1 < len(waves) else None
                )
        self.last_launch_compiles = counter.count if counter.supported \
            else -1
        if self.metrics is not None and counter.supported:
            self.metrics.record_service_compiles(counter.count)
        return n_chunks

    def _stage_chunk(self, key: tuple, members: list):
        """Stage one wave's member tables on device WITHOUT
        dispatching: host-side stack + ONE batched upload. ``launch``
        calls this one wave ahead of ``_dispatch_chunk`` so the upload
        overlaps the previous wave's in-flight member dispatches (the
        double buffer)."""
        Tp, Mp, P = key
        # grow-only batch-axis bucket: one compiled member-kernel shape
        # per (Tp, Mp, P) even as the tenant count churns
        Bp = pad_bucket(len(members), minimum=self._b_floor.get(key, 1))
        self._b_floor[key] = Bp
        smax = max(
            self._smax_floor.get(key, 1),
            max(m.smax for m in members),
        )
        self._smax_floor[key] = smax
        t0 = time.perf_counter()
        stacked_host = stack_members([m.tables for m in members], Bp)
        # zeros + member-index scalars OUTSIDE the transfer guard:
        # their fill/scalar uploads are implicit h2d the guard would
        # reject (same rule as resident's arg prep)
        zeros_t = jnp.zeros(Tp, I32)
        zeros_m = jnp.zeros(Mp, I32)
        idxs = [jnp.int32(i) for i in range(len(members))]
        chunk = _Chunk(key=key, members=members, smax=smax)
        with no_implicit_transfers():
            stacked = jax.device_put(stacked_host)
        up_ms = (time.perf_counter() - t0) * 1000
        return (chunk, stacked, zeros_t, zeros_m, idxs, up_ms)

    def _dispatch_chunk(self, staged) -> None:
        """Dispatch a staged wave's member kernels and start its ONE
        batched background fetch."""
        chunk, stacked, zeros_t, zeros_m, idxs, up_ms = staged
        Tp, Mp, P = chunk.key
        members = chunk.members
        smax = chunk.smax
        with no_implicit_transfers():
            chunk.t_dispatch = time.perf_counter()
            with enable_x64(True):
                for i, m in enumerate(members):
                    warm = m.warm
                    out = _solve_member(
                        *(stacked[k] for k in MEMBER_KEYS),
                        idxs[i],
                        warm.asg if warm is not None else zeros_t,
                        warm.lvl if warm is not None else zeros_t,
                        warm.floor if warm is not None else zeros_m,
                        n_prefs=P, smax=smax, alpha=self.alpha,
                        max_rounds=self.max_rounds,
                        warm_start=warm is not None,
                    )
                    chunk.states.append(out)
                    m.chunk = chunk
                    m.slot = i
                    m.timings["upload_ms"] = up_ms / len(members)

        fetch_refs = [
            (cost, conv, asg, rounds)
            for cost, conv, asg, rounds, *_ in chunk.states
        ]

        def _fetch():
            with sanctioned_transfer():
                vals = jax.device_get(fetch_refs)  # noqa: PTA001 -- THE chunk's one sanctioned batched fetch: every member's placements in one download
            return vals, time.perf_counter()

        chunk.stacked = stacked
        chunk.future = _AsyncFetch(_fetch)
        self.dispatches += 1
        if self.metrics is not None:
            self.metrics.record_service_dispatch(
                f"{Tp}x{Mp}x{P}", len(members)
            )

    # ---- finish: join, certify, commit, degrade ------------------------

    def finish(
        self, solver: TenantSolver, pending: PendingSolve
    ) -> ResidentOutcome:
        pending.consumed = True
        chunk: _Chunk = pending.chunk
        timings = pending.timings
        t0 = time.perf_counter()
        if chunk.failed:
            # a bucket-mate already paid the deadline on this chunk's
            # fetch: fail fast rather than re-waiting on a dead future
            solver.fetch_timeouts += 1
            self.pool.invalidate(pending.tenant)
            raise FetchTimeout(
                f"batched fetch for tenant {pending.tenant}'s chunk "
                f"already missed its deadline"
            )
        try:
            vals, t_done = chunk.future.result(
                timeout_s=self.oracle_timeout_s
            )
        except FetchTimeout:
            chunk.failed = True
            solver.fetch_timeouts += 1
            self.pool.invalidate(pending.tenant)
            log.error(
                "batched placement fetch missed its deadline; "
                "abandoning tenant %s's round", pending.tenant,
            )
            raise
        timings["fetch_wait_ms"] = (time.perf_counter() - t0) * 1000
        timings["solve_ms"] = (t_done - chunk.t_dispatch) * 1000
        timings["fetch_ms"] = 0.0
        cost_np, conv, asg_np, rounds = vals[pending.slot]
        state_refs = chunk.states[pending.slot]
        if not bool(conv) and pending.warm_used:
            # a stale warm start stranded the eps=1 settle: retry cold
            # against the chunk's still-resident stacked tables (one
            # extra dispatch + one extra sanctioned fetch, this member
            # only — the rest of the batch is untouched)
            self.pool.invalidate(pending.tenant)
            t0 = time.perf_counter()
            zeros_t = jnp.zeros(pending.Tp, I32)
            zeros_m = jnp.zeros(pending.Mp, I32)
            slot_idx = jnp.int32(pending.slot)
            with no_implicit_transfers():
                with enable_x64(True):
                    state_refs = _solve_member(
                        *(chunk.stacked[k] for k in MEMBER_KEYS),
                        slot_idx,
                        zeros_t, zeros_t, zeros_m,
                        n_prefs=pending.P, smax=chunk.smax,
                        alpha=self.alpha, max_rounds=self.max_rounds,
                        warm_start=False,
                    )
            with sanctioned_transfer():
                cost_np, conv, asg_np, rounds = jax.device_get((  # noqa: PTA001 -- sanctioned second fetch of the cold retry (this member really does pay twice)
                    state_refs[0], state_refs[1], state_refs[2],
                    state_refs[3],
                ))
            timings["solve_ms"] += (time.perf_counter() - t0) * 1000
        if not bool(conv):
            self.pool.invalidate(pending.tenant)
            if not self.oracle_fallback:
                raise RuntimeError(
                    f"service solve for tenant {pending.tenant} did "
                    f"not certify and oracle fallback is disabled"
                )
            return solver._oracle_outcome(
                pending.arrays, pending.meta, pending.topo,
                pending.cost_host, timings, why="uncertified",
            )
        # commit the member's device state as the tenant's warm context
        _c, conv_d, asg_d, rounds_d, lvl_d, floor_d, gap_d, phases_d = \
            state_refs
        self.pool.commit(
            pending.tenant,
            DenseState(
                asg=asg_d, lvl=lvl_d, floor=floor_d, gap=gap_d,
                converged=conv_d, rounds=rounds_d, phases=phases_d,
            ),
            pending.Tp, pending.Mp,
        )
        T = pending.T
        asg = np.asarray(asg_np, np.int32)[:T]  # noqa: PTA001 -- already-fetched host data (the chunk's sanctioned batched fetch)
        asg = np.where(
            (asg >= 0) & (asg < pending.n_machines), asg, -1
        ).astype(np.int32)
        channel = _channels_for(pending.inst, asg)
        solver.last_assignment = asg
        return ResidentOutcome(
            assignment=asg,
            channel=channel,
            cost=int(cost_np) // (T + 1),
            backend="dense_service",
            converged=True,
            rounds=int(rounds),
            phases=0,
            topology=pending.topo,
            timings=timings,
        )
