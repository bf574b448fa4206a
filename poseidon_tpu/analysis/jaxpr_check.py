"""The compiled-program auditor: trace the production kernels, walk
their closed jaxprs, and pin what the AST layer can only approximate.

PTA001 can prove no *syntactic* host sync sits in a hot scope; it
cannot see what the compiled program actually does. This module can:
it drives one tiny scheduling round through the REAL construction path
(synthetic cluster → FlowGraphBuilder → topology padding → the
resident solver's own argument prep), traces every production kernel
with ``jax.make_jaxpr`` on those tiny shapes, and asserts the compiled
contracts directly on the jaxprs:

- **zero host callbacks** — no ``*callback*`` / ``debug_print`` /
  infeed/outfeed primitives anywhere in the program (a stray
  ``jax.debug.print`` left from a debugging session silently syncs
  every dispatch);
- **zero smuggled transfers** — no ``device_put`` primitives inside
  the fused programs, and a bounded closure-constant census: a host
  array smuggled into a kernel (``jnp.asarray(host_val)`` where
  ``host_val`` is module state) becomes a tracing CONSTANT, so it
  shows up here as an oversized const or a const-census diff;
- **no f64 leaks** — the kernels run under ``enable_x64`` for the
  int64 domain arithmetic; no float64 aval may appear anywhere (a
  float64 table would double the HBM story AND desync from the TPU's
  f32-native layout);
- **a pinned per-kernel primitive-count fingerprint**
  (``analysis/kernel_fingerprints.json``): an accidental fusion break,
  a new transfer, or a silently changed reduction shows up as a CI
  diff at review time, not as a perf regression three PRs later.
  ``--update-fingerprints`` re-traces and rewrites the file; the diff
  then documents the intentional change in the PR.

Audited kernels (the production set): ``_solve`` (the eps-ladder
auction), ``_resident_chain`` (the whole fused round),
``_express_patch`` + ``_express_chain`` (the express lane),
``_stream_chain`` (the K-window streaming scan), and
``_solve_member`` (the service lane's bucket-member solve). The
fingerprint is a property of the TRACE, not the backend: the 8-device
CI lane re-runs the audit to prove the SPMD path sees the same
program (sharding changes layout, never primitives).

Violations carry code PTA008 so they ride the same reporting/CI
surface as the AST rules.
"""

from __future__ import annotations

import json
import pathlib
from collections import Counter as _Counter

import numpy as np

from poseidon_tpu.analysis.core import Violation

FINGERPRINT_FILE = "poseidon_tpu/analysis/kernel_fingerprints.json"

# a closure constant larger than this (bytes) inside a production
# kernel is a smuggled host array, full stop: the kernels take every
# table through explicit arguments, so legitimate consts are scalars
# and tiny index vectors
_CONST_BYTES_LIMIT = 256

_BANNED_PRIMITIVE_SUBSTRINGS = ("callback", "infeed", "outfeed")
_BANNED_PRIMITIVES = {"debug_print", "device_put", "copy"}


# ---------------------------------------------------------------------------
# jaxpr walking (duck-typed: ClosedJaxpr has .jaxpr/.consts, Jaxpr has
# .eqns — isinstance against jax internals churns across versions)
# ---------------------------------------------------------------------------


def _inner_jaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr, list(x.consts)
            elif hasattr(x, "eqns"):
                yield x, []


def _walk(closed):
    """Yield (jaxpr, consts) for the closed jaxpr and every nested
    sub-jaxpr (pjit bodies, scan/while/cond branches)."""
    stack = [(closed.jaxpr, list(closed.consts))]
    while stack:
        jaxpr, consts = stack.pop()
        yield jaxpr, consts
        for eqn in jaxpr.eqns:
            stack.extend(_inner_jaxprs(eqn.params))


def primitive_counts(closed) -> dict[str, int]:
    counts: _Counter = _Counter()
    for jaxpr, _consts in _walk(closed):
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] += 1
    return dict(sorted(counts.items()))


def const_census(closed) -> tuple[int, int]:
    """(count, total bytes) of array constants across every level."""
    count = 0
    total = 0
    for _jaxpr, consts in _walk(closed):
        for c in consts:
            count += 1
            total += int(np.asarray(c).nbytes)
    return count, total


def _all_avals(closed):
    for jaxpr, _consts in _walk(closed):
        for v in jaxpr.invars + jaxpr.constvars + jaxpr.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield aval


def structural_problems(name: str, closed) -> list[str]:
    """Contract violations independent of the committed fingerprint."""
    problems: list[str] = []
    counts = primitive_counts(closed)
    for prim, n in counts.items():
        if prim in _BANNED_PRIMITIVES or any(
            s in prim for s in _BANNED_PRIMITIVE_SUBSTRINGS
        ):
            problems.append(
                f"{name}: banned primitive '{prim}' x{n} in the "
                "compiled program (host callback / smuggled transfer "
                "— the fused chain must stay device-pure)"
            )
    for _jaxpr, consts in _walk(closed):
        for c in consts:
            arr = np.asarray(c)
            if arr.nbytes > _CONST_BYTES_LIMIT:
                problems.append(
                    f"{name}: {arr.nbytes}-byte closure constant "
                    f"(shape {arr.shape}, {arr.dtype}) baked into the "
                    "trace — a smuggled host array; every table must "
                    "enter through an explicit argument"
                )
    f64 = sorted({
        str(getattr(a, "shape", "?"))
        for a in _all_avals(closed)
        if getattr(a, "dtype", None) is not None
        and np.dtype(a.dtype) == np.float64
    })
    if f64:
        problems.append(
            f"{name}: float64 avals leak into the program (shapes "
            f"{', '.join(f64[:4])}) — the kernels are integer/f32 "
            "under x64 hygiene"
        )
    return problems


def fingerprint(closed) -> dict:
    count, nbytes = const_census(closed)
    return {
        "primitives": primitive_counts(closed),
        "const_count": count,
        "const_bytes": nbytes,
    }


def diff_fingerprint(name: str, got: dict, want: dict) -> list[str]:
    problems: list[str] = []
    gp, wp = got["primitives"], want.get("primitives", {})
    for prim in sorted(set(gp) | set(wp)):
        g, w = gp.get(prim, 0), wp.get(prim, 0)
        if g != w:
            problems.append(
                f"{name}: primitive '{prim}' count {g} != pinned {w} "
                "(fusion break / new op — if intentional, re-pin with "
                "--update-fingerprints and let the diff document it)"
            )
    for key in ("const_count", "const_bytes"):
        if got[key] != want.get(key, 0):
            problems.append(
                f"{name}: {key} {got[key]} != pinned {want.get(key, 0)}"
                " (a closure constant appeared or vanished)"
            )
    return problems


# ---------------------------------------------------------------------------
# tracing the production kernels on tiny shapes
# ---------------------------------------------------------------------------


def trace_production_kernels() -> dict[str, object]:
    """Drive one tiny round through the real construction path and
    return {kernel name: closed jaxpr} for the production set.

    The tiny round EXECUTES once (CPU-cheap at 8 machines / 12 tasks)
    because the express kernels take the solver's own warm context —
    tracing against hand-rolled lookalike arrays would audit a
    different program than production dispatches.
    """
    import jax

    from jax import enable_x64
    from poseidon_tpu.graph.builder import FlowGraphBuilder
    from poseidon_tpu.models.costs import build_cost_inputs_host
    from poseidon_tpu.ops import resident as res
    from poseidon_tpu.ops.batch import (
        MEMBER_KEYS,
        _solve_member,
        member_bucket_dims,
        stack_members,
    )
    from poseidon_tpu.ops.dense_auction import _solve, build_member_tables
    from poseidon_tpu.ops.transport import (
        extract_topology,
        instance_from_topology,
    )
    from poseidon_tpu.synth import make_synthetic_cluster

    cluster = make_synthetic_cluster(
        8, 12, seed=11, machines_per_rack=4, max_tasks_per_machine=4,
        prefs_per_task=2, tasks_per_job=4,
    )
    arrays, meta = FlowGraphBuilder().build_arrays(cluster)
    solver = res.ResidentSolver(
        oracle_fallback=False, small_to_oracle=False,
        express_lane=True, express_max_batch=4,
    )
    outcome = solver.run_round(arrays, meta, cost_model="quincy")
    if not outcome.converged:
        raise RuntimeError(
            "jaxpr audit: the tiny bootstrap round did not certify"
        )
    ctx = solver._express
    if ctx is None:
        raise RuntimeError(
            "jaxpr audit: no express context after a certified round"
        )
    warm = solver._warm
    model_fn = ctx.model_fn
    kmax = solver.express_max_batch
    pk = ctx.n_prefs
    Tp = ctx.dev.c.shape[0]

    arrival = res.ExpressArrival(
        uid="jaxpr-audit-pod", wait_rounds=0, cpu_milli=100,
        mem_kb=1 << 16, prefs=((0, -1, 3), (-1, 0, 5)),
    )
    solver._express_finalize(ctx)
    mini_host = solver._express_mini_inputs(ctx, [arrival], kmax, pk)
    add_row = np.full(kmax, -1, np.int32)
    add_row[0] = Tp - 1
    add_pm = np.full((kmax, pk), -1, np.int32)
    add_pr = np.full((kmax, pk), -1, np.int32)

    # the stream lane's [K, ...] event buffers: the same per-window
    # slices the synced lane takes, stacked along the batch axis (K=2
    # is enough — scan length never changes the traced program)
    skw = 2
    mini_stack = jax.tree_util.tree_map(
        lambda leaf: np.stack([np.asarray(leaf)] * skw), mini_host
    )
    add_row_s = np.stack([add_row] * skw)
    add_pm_s = np.stack([add_pm] * skw)
    add_pr_s = np.stack([add_pr] * skw)
    spw = solver._stream_pw_floor
    prow_s = np.full((skw, spw), -1, np.int32)
    pcol_s = np.full((skw, spw), -1, np.int32)
    pdelta_s = np.zeros((skw, spw), np.int32)

    # the service lane's stacked member tables (2 heterogeneous
    # members through the same scale-and-pad source production uses)
    topo = extract_topology(
        meta, arrays["src"], arrays["dst"], arrays["cap"]
    )
    cost_host = np.asarray(
        jax.device_get(ctx.cost_dev), np.int64
    )[: meta.n_arcs]
    inst = instance_from_topology(topo, cost_host)
    bTp, bMp, bP = member_bucket_dims(inst)
    members = [
        build_member_tables(inst, bTp, bMp, bP) for _ in range(2)
    ]
    stacked = stack_members(members, 2)
    bsmax = max(min(int(np.max(members[0]["slots"], initial=0)), bTp), 1)

    zeros_bt = np.zeros(bTp, np.int32)
    zeros_bm = np.zeros(bMp, np.int32)
    patch_w = res._EXPRESS_PATCH_CHUNK
    with enable_x64(True):
        traces = {  # noqa: PTA007 -- one-shot audit bootstrap: each kernel is traced exactly once per run on pinned tiny shapes; there is no steady state to protect
            "solve": jax.make_jaxpr(
                lambda dev, a, l, f, e: _solve(
                    dev, a, l, f, e, alpha=solver.alpha,
                    max_rounds=64, smax=ctx.smax,
                    analytic_init=False,
                )
            )(ctx.dev, warm.asg, warm.lvl, warm.floor, np.int32(1)),
            "resident_chain": jax.make_jaxpr(
                lambda dt, inp: res._resident_chain(
                    dt, inp, model_fn=model_fn,
                    n_prefs=pk, smax=ctx.smax, alpha=solver.alpha,
                    max_rounds=64,
                )
            )(
                ctx.dt,
                # the round's pricing inputs, rebuilt exactly as
                # begin_round padded them (its floors are still live
                # on the solver)
                build_cost_inputs_host(
                    solver._e_floor, meta,
                    t_min=solver._ti_floor, m_min=solver._mi_floor,
                ),
            ),
            "express_patch": jax.make_jaxpr(
                lambda u, w, tv, s, a, l, r, c, d: res._express_patch(
                    u, w, tv, s, a, l, r, c, d
                )
            )(
                ctx.dev.u, ctx.dev.w, ctx.dev.task_valid, ctx.dev.s,
                warm.asg, warm.lvl,
                np.full(patch_w, -1, np.int32),
                np.full(patch_w, -1, np.int32),
                np.zeros(patch_w, np.int32),
            ),
            "express_chain": jax.make_jaxpr(
                lambda dev, dt, cost, mini, a, l, f, ar, pm, pr:
                res._express_chain(
                    dev, dt, cost, mini, a, l, f, ar, pm, pr,
                    model_fn=model_fn, kmax=kmax, pk=pk,
                    alpha=solver.alpha, max_rounds=res.EXPRESS_FUSE,
                    smax=ctx.smax,
                    change_cap=solver.express_change_cap,
                )
            )(
                ctx.dev, ctx.dt, ctx.cost_dev, mini_host,
                warm.asg, warm.lvl, warm.floor,
                add_row, add_pm, add_pr,
            ),
            "stream_chain": jax.make_jaxpr(
                lambda dev, dt, cost, mini, a, l, f, ar, pm, pr,
                prw, pcl, pdl:
                res._stream_chain(
                    dev, dt, cost, mini, a, l, f, ar, pm, pr,
                    prw, pcl, pdl,
                    model_fn=model_fn, kmax=kmax, pk=pk,
                    alpha=solver.alpha, max_rounds=res.EXPRESS_FUSE,
                    smax=ctx.smax,
                    change_cap=solver.express_change_cap,
                )
            )(
                ctx.dev, ctx.dt, ctx.cost_dev, mini_stack,
                warm.asg, warm.lvl, warm.floor,
                add_row_s, add_pm_s, add_pr_s,
                prow_s, pcol_s, pdelta_s,
            ),
            "solve_member": jax.make_jaxpr(
                lambda *args: _solve_member(
                    *args, n_prefs=bP, smax=bsmax, alpha=solver.alpha,
                    max_rounds=64, warm_start=False,
                )
            )(
                *(stacked[k] for k in MEMBER_KEYS), np.int32(0),
                zeros_bt, zeros_bt, zeros_bm,
            ),
        }
    return traces


# ---------------------------------------------------------------------------
# the audit entry point
# ---------------------------------------------------------------------------


def run_jaxpr_audit(
    root: pathlib.Path, *, update: bool = False, traces=None
) -> tuple[list[Violation], int]:
    """Trace, check structure, and diff against the committed
    fingerprints. Returns (violations, kernels audited). ``update``
    rewrites ``kernel_fingerprints.json`` instead of diffing.
    ``traces`` reuses an already-traced kernel set (the tests trace
    once and drive every audit path from it)."""
    fp_path = root / FINGERPRINT_FILE
    if traces is None:
        traces = trace_production_kernels()
    violations: list[Violation] = []

    def flag(msg: str):
        violations.append(Violation(
            code="PTA008", rule="jaxpr-audit",
            path=FINGERPRINT_FILE, line=1, col=0, message=msg,
        ))

    got = {name: fingerprint(t) for name, t in traces.items()}
    for name, t in traces.items():
        for p in structural_problems(name, t):
            flag(p)

    if update:
        fp_path.write_text(json.dumps(
            {
                "_comment": (
                    "Pinned per-kernel primitive-count fingerprints "
                    "(python -m poseidon_tpu.analysis "
                    "--update-fingerprints). A diff here means the "
                    "compiled programs changed: say why in the PR."
                ),
                "kernels": got,
            },
            indent=2, sort_keys=True,
        ) + "\n")
        return violations, len(traces)

    if not fp_path.is_file():
        flag(
            f"{FINGERPRINT_FILE} is missing: run python -m "
            "poseidon_tpu.analysis --update-fingerprints and commit it"
        )
        return violations, len(traces)
    want = json.loads(fp_path.read_text()).get("kernels", {})
    for name in sorted(set(got) | set(want)):
        if name not in got:
            flag(
                f"{name}: pinned in {FINGERPRINT_FILE} but no longer "
                "traced — remove the stale entry with "
                "--update-fingerprints"
            )
            continue
        if name not in want:
            flag(
                f"{name}: traced but not pinned in {FINGERPRINT_FILE} "
                "— add it with --update-fingerprints"
            )
            continue
        for p in diff_fingerprint(name, got[name], want[name]):
            flag(p)
    return violations, len(traces)
