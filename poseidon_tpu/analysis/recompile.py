"""PTA007: static-arg / pad-shape provenance — the recompile-hazard pass.

A draining pending pool once exposed three recompile sources at
RUNTIME (``tests/test_obs.py::TestZeroRecompileUnderDrain`` is the
regression), all one bug class: a value derived from a data-dependent
quantity (a ``max`` over live state, a ``len`` of the pending pool, a
topology's ``max_prefs``) flowing into a position that pins a compiled
shape — a ``static_argnames`` argument of a jitted kernel, or a
``t_min`` / ``m_min`` / ``p_min`` padding floor of the host padding
helpers — WITHOUT riding a grow-only floor. The steady-state symptom
is brutal and silent: a pending pool draining across a bucket boundary
shrinks the derived value, the static arg changes, and every
post-drain round pays a multi-second recompile that profiles as "the
TPU got slow".

This pass catches the pattern at review time, as dataflow any reviewer
can replay:

1. **Jit registry (repo-wide).** Every ``@jax.jit`` /
   ``@partial(jax.jit, static_argnames=...)`` def in the tree is
   indexed with its static parameter names (``static_argnums`` map
   through the positional parameter list), so call sites anywhere know
   which argument positions pin compiled variants.

2. **Taint (function-local, flow-ordered).** A local is tainted when
   it derives from a hazard source — a ``max(...)`` / ``len(...)``
   call, a ``.max()`` reduction, or a declared hazard attribute
   (``.max_prefs``) — via assignments replayed in source order, so a
   later clean re-binding (``P = self._p_floor``) clears the name.

3. **Floors sanctify.** An expression that references a grow-only
   floor (``Contracts.floor_markers``: anything carrying ``floor`` in
   its name, or the ``t_min``/``m_min``/``p_min``/``minimum`` pad
   vocabulary) is clean: ``pad_bucket(max(n, 1),
   minimum=self._e_floor)`` rides the floor, ``pad_bucket(max(n, 1))``
   does not. The grow-only-ness of the floor attribute itself is the
   storing site's obligation (the same expression both reads and
   re-stores it), which the marker check covers by construction.

4. **Sinks.** A tainted, un-floored expression arriving at a static
   parameter of a registered jitted callable, or at a declared pad
   floor of the padding helpers (``Contracts.pad_sinks``), is the
   violation.

One-shot lanes (a cold ``solve_transport_dense`` call in a test or a
script) recompile per call BY DESIGN — such sites carry a reasoned
``# noqa: PTA007`` so the design decision is written down where the
reviewer reads it.
"""

from __future__ import annotations

import ast

from poseidon_tpu.analysis.contracts import Contracts
from poseidon_tpu.analysis.core import (
    RepoContext,
    Violation,
    files_enforcing,
    repo_rule,
)
from poseidon_tpu.analysis.rules import (
    _bound_names,
    _dotted,
    _jit_decorator,
    iter_functions,
    iter_own_nodes,
)


def _static_params(fn: ast.AST, dec: ast.Call) -> tuple[list[str], set[str]]:
    """(positional param names, static param names) of a jitted def."""
    params = [
        a.arg for a in fn.args.posonlyargs + fn.args.args
    ]
    kwonly = [a.arg for a in fn.args.kwonlyargs]
    static: set[str] = set()
    for kw in dec.keywords:
        if kw.arg == "static_argnames" and \
                isinstance(kw.value, (ast.Tuple, ast.List)):
            for elt in kw.value.elts:
                if isinstance(elt, ast.Constant) and \
                        isinstance(elt.value, str):
                    static.add(elt.value)
        elif kw.arg == "static_argnames" and \
                isinstance(kw.value, ast.Constant) and \
                isinstance(kw.value.value, str):
            static.add(kw.value.value)
        elif kw.arg == "static_argnums":
            nums = []
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                nums = [
                    e.value for e in kw.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int)
                ]
            elif isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int):
                nums = [kw.value.value]
            for i in nums:
                if 0 <= i < len(params):
                    static.add(params[i])
    del kwonly
    return params, static


def build_jit_registry(
    files,
) -> dict[str, tuple[list[str], set[str]]]:
    """Terminal callable name -> (positional params, static param
    names) for every jitted def in the ENFORCING files (only defs with
    at least one static parameter matter to this pass; a tests/ def
    must not shadow a production kernel's signature). A name defined
    twice with DIFFERENT signatures is ambiguous and dropped — checking
    call sites against the wrong kernel's statics would both miss real
    hazards and invent false ones."""
    registry: dict[str, tuple[list[str], set[str]]] = {}
    ambiguous: set[str] = set()
    for fctx in files.values():
        for fn, _qual, _depth in iter_functions(fctx.tree):
            dec = _jit_decorator(fn)
            if dec is None:
                continue
            params, static = _static_params(fn, dec)
            if not static or fn.name in ambiguous:
                continue
            existing = registry.get(fn.name)
            if existing is not None and existing != (params, static):
                del registry[fn.name]
                ambiguous.add(fn.name)
                continue
            registry[fn.name] = (params, static)
    return registry


def _has_floor_marker(expr: ast.AST, c: Contracts) -> bool:
    exact = {m for m in c.floor_markers if m != "floor"}
    for n in ast.walk(expr):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        elif isinstance(n, ast.keyword):
            name = n.arg
        if name is None:
            continue
        if "floor" in name.lower() or name in exact:
            return True
    return False


def _has_hazard_source(expr: ast.AST, c: Contracts) -> str | None:
    """The hazard in ``expr``, or None. max()/len() calls, ``.max()``
    reductions, and declared hazard attributes are data-dependent."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            if isinstance(n.func, ast.Name) and \
                    n.func.id in ("max", "len") and n.args and not all(
                        isinstance(a, ast.Constant) for a in n.args
                    ):
                return f"{n.func.id}(...)"
            if isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("max", "argmax"):
                return f".{n.func.attr}() reduction"
            d = _dotted(n.func)
            if d in ("np.max", "numpy.max", "np.amax"):
                return d
        if isinstance(n, ast.Attribute) and n.attr in c.hazard_attrs:
            return f".{n.attr}"
    return None


def _ordered_assigns(
    fn: ast.AST,
) -> list[tuple[int, set[str], ast.AST, bool]]:
    """(lineno, bound names, value expr, is_augmented) in source order
    — the taint replay is flow-ORDERED: a later clean re-binding of a
    name (``P = self._p_floor`` after ``P = topo.max_prefs``) clears
    its taint, which a flow-insensitive union would keep forever."""
    items: list[tuple[int, set[str], ast.AST, bool]] = []
    for node in iter_own_nodes(fn):
        if isinstance(node, ast.Assign):
            targets: set[str] = set()
            for t in node.targets:
                targets |= _bound_names(t)
            items.append((node.lineno, targets, node.value, False))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) and \
                isinstance(node.target, ast.Name) and \
                node.value is not None:
            items.append((
                node.lineno, {node.target.id}, node.value,
                isinstance(node, ast.AugAssign),
            ))
    items.sort(key=lambda it: it[0])
    return items


def _taint_at(
    assigns: list[tuple[int, set[str], ast.AST, bool]],
    line: int,
    c: Contracts,
) -> dict[str, str]:
    """Name -> hazard description as of (just before) ``line``,
    replaying assignments in source order. Loops that assign below
    their use are out of scope (grow-only floors do not live in
    loops)."""
    tainted: dict[str, str] = {}
    for ln, targets, value, augmented in assigns:
        if ln >= line:
            break
        if _has_floor_marker(value, c):
            for t in targets:
                tainted.pop(t, None)  # rides a floor: sanctified
            continue
        hazard = _has_hazard_source(value, c)
        if hazard is None:
            carried = [
                n.id for n in ast.walk(value)
                if isinstance(n, ast.Name) and n.id in tainted
            ]
            if not carried:
                if not augmented:
                    for t in targets:
                        tainted.pop(t, None)  # clean re-binding
                continue
            hazard = tainted[carried[0]]
        for t in targets:
            tainted[t] = hazard
    return tainted


def _expr_hazard(
    expr: ast.AST, tainted: dict[str, str], c: Contracts
) -> str | None:
    if _has_floor_marker(expr, c):
        return None
    hazard = _has_hazard_source(expr, c)
    if hazard is not None:
        return hazard
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in tainted:
            return tainted[n.id]
    return None


@repo_rule("PTA007", "recompile-hazard")
def recompile_hazard(repo: RepoContext) -> list[Violation]:
    c = repo.contracts
    files = files_enforcing(repo, "PTA007")
    registry = build_jit_registry(files)
    out: list[Violation] = []
    for rel, fctx in files.items():
        for fn, qual, _depth in iter_functions(fctx.tree):
            assigns = _ordered_assigns(fn)
            for node in iter_own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = None
                if isinstance(node.func, ast.Name):
                    callee = node.func.id
                elif isinstance(node.func, ast.Attribute):
                    callee = node.func.attr
                if callee is None:
                    continue
                sinks: list[tuple[str, ast.AST]] = []
                if callee in registry:
                    params, static = registry[callee]
                    for i, a in enumerate(node.args):
                        if i < len(params) and params[i] in static:
                            sinks.append((params[i], a))
                    for kw in node.keywords:
                        if kw.arg in static:
                            sinks.append((kw.arg, kw.value))
                if callee in c.pad_sinks:
                    floors = c.pad_sinks[callee]
                    for kw in node.keywords:
                        if kw.arg in floors:
                            sinks.append((kw.arg, kw.value))
                if not sinks:
                    continue
                tainted = _taint_at(assigns, node.lineno, c)
                for pname, value in sinks:
                    hazard = _expr_hazard(value, tainted, c)
                    if hazard is None:
                        continue
                    out.append(Violation(
                        code="PTA007", rule="recompile-hazard",
                        path=rel, line=node.lineno,
                        col=node.col_offset,
                        message=(
                            f"shape-pinning argument '{pname}' of "
                            f"{callee}() in {qual} derives from "
                            f"data-dependent {hazard} without riding "
                            "a grow-only floor: when the live value "
                            "shrinks across a bucket boundary this "
                            "recompiles the compiled chain mid-"
                            "steady-state (the PR 8 bug class); "
                            "route it through a grow-only *_floor / "
                            "pad_bucket(minimum=...) or suppress "
                            "with the one-shot-lane reason"
                        ),
                    ))
    return out
