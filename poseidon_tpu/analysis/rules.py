"""The repo-specific rule set (PTA001-PTA005).

Each rule documents itself with a minimal bad/good pair. Rules are
scoped by ``contracts.py`` — the hot-path files/functions, the
cluster-sized collection names, the thread classes — so a generic
pattern (a ``for`` loop, an ``int()`` call) is only a violation where
the repo's stated invariants forbid it.

PTA001 no-host-sync
    BAD  (inside a hot-path scope)::

        val = cost.item()              # device sync mid-round
        host = np.asarray(asg_dev)     # host materialization
    GOOD::

        # defer to the round's single sanctioned fetch, or:
        host = np.asarray(asg_np)  # noqa: PTA001 -- already host data

PTA002 no-cluster-loops
    BAD  (inside an O(churn) scope)::

        for t in cluster.tasks: ...    # O(cluster) every round
    GOOD::

        for d in dset.place: ...       # O(churn): only this round's deltas

PTA003 jit-hygiene
    BAD::

        def price(x):
            return jax.jit(model)(x)   # fresh wrapper -> retrace per call

        @partial(jax.jit, static_argnames=("opts",))
        def f(x, opts=[]): ...         # non-hashable static default
    GOOD::

        _model_jit = jax.jit(model)    # module level, traced once

PTA004 lock-discipline
    BAD::

        def run(self):  # pta: background-thread
            self.rounds += 1           # unlocked cross-thread mutation
    GOOD::

        def run(self):  # pta: background-thread
            with self._lock:
                self.rounds += 1
    (or declare the attribute as a documented handoff in contracts.py)

PTA005 surface-consistency
    BAD::

        self.trace.emit("REBALANCE")   # not in trace.EVENT_TYPES
        p.add_argument("--new_flag")   # absent from README / deploy cfg
    GOOD::

        self.trace.emit("MIGRATE")     # declared vocabulary
"""

from __future__ import annotations

import ast
import builtins
import re

from poseidon_tpu.analysis.contracts import Contracts
from poseidon_tpu.analysis.core import (
    FileContext,
    RepoContext,
    Violation,
    file_rule,
    repo_rule,
)

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)
_BUILTINS = frozenset(dir(builtins))


# ---- shared AST helpers ------------------------------------------------


def _dotted(node: ast.AST) -> str | None:
    """'jax.device_get' for Attribute chains rooted at a Name."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def iter_functions(tree: ast.AST):
    """Yield (node, qualname, depth) for every def, depth-first.
    Qualnames join class and function names with '.'; depth counts
    enclosing FUNCTIONS only (a method of a top-level class is depth 0).
    """
    def walk(node, prefix, depth):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC_NODES):
                qual = f"{prefix}{child.name}"
                yield child, qual, depth
                yield from walk(child, qual + ".", depth + 1)
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.", depth)
            else:
                yield from walk(child, prefix, depth)
    yield from walk(tree, "", 0)


def iter_own_nodes(fn: ast.AST):
    """Walk a function's own body, NOT descending into nested defs or
    classes (they are analyzed as their own scopes)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, _FUNC_NODES + (ast.ClassDef,)):
            continue  # nested scope: analyzed as its own function
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _names_in(node: ast.AST) -> set[str]:
    return {
        n.id for n in ast.walk(node) if isinstance(n, ast.Name)
    }


def _bound_names(target: ast.AST) -> set[str]:
    """Names a target expression BINDS. ``obj.attr = x`` / ``d[k] = x``
    mutate an object without binding any name, so they contribute
    nothing (unlike ``_names_in``, which would claim ``obj``)."""
    if isinstance(target, ast.Name):
        return {target.id}
    if isinstance(target, (ast.Tuple, ast.List)):
        out: set[str] = set()
        for e in target.elts:
            out |= _bound_names(e)
        return out
    if isinstance(target, ast.Starred):
        return _bound_names(target.value)
    return set()


# ---- PTA001: no host syncs in hot-path scopes --------------------------

_SYNC_ATTRS = {"item", "block_until_ready"}
_HOST_MATERIALIZERS = {"np.asarray", "numpy.asarray"}


def _is_device_producer(call: ast.Call, contracts: Contracts) -> bool:
    d = _dotted(call.func)
    if d is not None:
        if d in contracts.device_producer_exceptions:
            return False
        for p in contracts.device_producers:
            if p.endswith("."):
                if d.startswith(p):
                    return True
            elif d == p or d.endswith("." + p):
                return True
    if isinstance(call.func, ast.Call):  # e.g. _jitted_model(name)(x)
        return _is_device_producer(call.func, contracts)
    return False


def _device_tainted_names(fn, contracts: Contracts) -> set[str]:
    """Names assigned (directly or transitively) from device-array
    producers within this function."""
    assigns: list[tuple[set[str], ast.AST]] = []
    for node in iter_own_nodes(fn):
        if isinstance(node, ast.Assign):
            targets: set[str] = set()
            for t in node.targets:
                targets |= _bound_names(t)
            assigns.append((targets, node.value))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) and \
                isinstance(node.target, ast.Name) and node.value is not None:
            assigns.append(({node.target.id}, node.value))
    def _is_host_barrier(value: ast.AST) -> bool:
        # an explicit download's RESULT is host data: int()/float() on
        # it cannot sync again, so the assignment untaints its targets
        # even though the downloaded operands were device arrays
        return (
            isinstance(value, ast.Call)
            and _dotted(value.func) in contracts.device_producer_exceptions
        )

    tainted: set[str] = set()
    for _ in range(2):  # two passes: one hop of name->name propagation
        for targets, value in assigns:
            if _is_host_barrier(value):
                continue
            if any(
                isinstance(n, ast.Call)
                and _is_device_producer(n, contracts)
                for n in ast.walk(value)
            ) or (_names_in(value) & tainted):
                tainted |= targets
    return tainted


@file_rule("PTA001", "no-host-sync")
def no_host_sync(ctx: FileContext) -> list[Violation]:
    c = ctx.contracts
    whole_file = any(ctx.path.endswith(s) for s in c.hot_path_files)
    out: list[Violation] = []

    def flag(node, msg):
        out.append(Violation(
            code="PTA001", rule="no-host-sync", path=ctx.path,
            line=node.lineno, col=node.col_offset, message=msg,
        ))

    for fn, qual, _depth in iter_functions(ctx.tree):
        if not (whole_file or ctx.in_scope(c.hot_path_functions, qual)):
            continue
        tainted = _device_tainted_names(fn, c)
        for node in iter_own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _SYNC_ATTRS:
                flag(node, f".{f.attr}() forces a device sync inside "
                           f"hot-path scope {qual}")
                continue
            d = _dotted(f)
            if d == "jax.device_get" or (
                isinstance(f, ast.Name) and f.id == "device_get"
            ):
                flag(node, "jax.device_get is a host sync; only the "
                           "round's sanctioned fetch may download "
                           f"(hot-path scope {qual})")
                continue
            if d in _HOST_MATERIALIZERS:
                flag(node, f"{d} materializes on host inside hot-path "
                           f"scope {qual} (syncs if the operand is a "
                           "device array)")
                continue
            if isinstance(f, ast.Name) and f.id in ("int", "float") \
                    and node.args:
                if _names_in(node.args[0]) & tainted:
                    flag(node, f"{f.id}() on a device array blocks on "
                               f"the device (hot-path scope {qual})")
    return out


# ---- PTA002: no cluster-sized loops in O(churn) scopes -----------------


def _cluster_sized_ref(node: ast.AST, c: Contracts) -> str | None:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in c.cluster_sized_names:
            return n.id
        if isinstance(n, ast.Attribute) and n.attr in c.cluster_sized_names:
            return n.attr
    return None


@file_rule("PTA002", "no-cluster-loops")
def no_cluster_loops(ctx: FileContext) -> list[Violation]:
    c = ctx.contracts
    out: list[Violation] = []
    for fn, qual, _depth in iter_functions(ctx.tree):
        if not ctx.in_scope(c.ochurn_functions, qual):
            continue
        for node in iter_own_nodes(fn):
            if isinstance(node, ast.For):
                iters = [node.iter]
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.DictComp, ast.GeneratorExp)):
                iters = [g.iter for g in node.generators]
            else:
                continue
            for it in iters:
                name = _cluster_sized_ref(it, c)
                if name:
                    out.append(Violation(
                        code="PTA002", rule="no-cluster-loops",
                        path=ctx.path, line=node.lineno,
                        col=node.col_offset,
                        message=(
                            f"Python loop over cluster-sized '{name}' "
                            f"in O(churn) scope {qual}: iterate this "
                            "round's deltas, or maintain a counter"
                        ),
                    ))
                    break
    return out


# ---- PTA003: jit boundary hygiene --------------------------------------


def _is_jit_expr(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("jax.jit", "jit")


def _jit_decorator(fn) -> ast.Call | None:
    """The decorator Call if ``fn`` is jitted (plain @jax.jit returns a
    synthetic marker too — None vs Call distinction only matters for
    static_argnames extraction)."""
    for dec in fn.decorator_list:
        if _is_jit_expr(dec):
            return ast.Call(func=dec, args=[], keywords=[])
        if isinstance(dec, ast.Call):
            if _is_jit_expr(dec.func):
                return dec
            d = _dotted(dec.func)
            if d in ("partial", "functools.partial") and dec.args \
                    and _is_jit_expr(dec.args[0]):
                return dec
    return None


_MUTABLE_DEFAULTS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)


def _module_bindings(tree: ast.Module) -> set[str]:
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                names.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, _FUNC_NODES + (ast.ClassDef,)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                names |= _names_in(t)
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            names.add(node.target.id)
        elif isinstance(node, (ast.If, ast.Try)):
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    for a in sub.names:
                        names.add((a.asname or a.name).split(".")[0])
                elif isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        names |= _names_in(t)
    return names


def _locally_bound(fn) -> set[str]:
    bound = {a.arg for a in fn.args.args + fn.args.kwonlyargs
             + fn.args.posonlyargs}
    if fn.args.vararg:
        bound.add(fn.args.vararg.arg)
    if fn.args.kwarg:
        bound.add(fn.args.kwarg.arg)
    for node in iter_own_nodes(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                bound |= _bound_names(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            bound |= _bound_names(node.target)
        elif isinstance(node, ast.comprehension):
            bound |= _bound_names(node.target)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                bound.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            bound |= _bound_names(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            bound |= _names_in(node.optional_vars)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
        elif isinstance(node, _FUNC_NODES + (ast.ClassDef,)):
            bound.add(node.name)
    return bound


def _all_import_bindings(tree: ast.AST) -> set[str]:
    """Every name bound by an import anywhere in the file. Closing over
    a locally-imported MODULE is harmless (modules don't retrace), so
    PTA003's capture check exempts them."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                names.add((a.asname or a.name).split(".")[0])
    return names


@file_rule("PTA003", "jit-hygiene")
def jit_hygiene(ctx: FileContext) -> list[Violation]:
    out: list[Violation] = []
    mod_names = _module_bindings(ctx.tree) | _all_import_bindings(ctx.tree)

    def flag(node, msg):
        out.append(Violation(
            code="PTA003", rule="jit-hygiene", path=ctx.path,
            line=node.lineno, col=node.col_offset, message=msg,
        ))

    for fn, qual, depth in iter_functions(ctx.tree):
        # (a) inline jax.jit(...) calls: fresh wrapper per call
        for node in iter_own_nodes(fn):
            if isinstance(node, ast.Call) and _is_jit_expr(node.func):
                flag(node, f"jax.jit(...) inside {qual} creates a fresh "
                           "traced wrapper per call (retrace + "
                           "recompile every round); hoist to module "
                           "level or cache the jitted callable")
        dec = _jit_decorator(fn)
        if dec is None:
            continue
        # (b) non-hashable defaults on a jitted function
        for default in list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None
        ]:
            if isinstance(default, _MUTABLE_DEFAULTS) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set")
            ):
                flag(default, f"mutable default on jitted {qual}: "
                              "unhashable as a static argument and a "
                              "retrace trap")
        # (d) static_argnames naming unknown parameters
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs
                  + fn.args.posonlyargs}
        for kw in dec.keywords:
            if kw.arg in ("static_argnames", "static_argnums") and \
                    isinstance(kw.value, (ast.Tuple, ast.List)):
                for elt in kw.value.elts:
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, str) and \
                            elt.value not in params:
                        flag(elt, f"static_argnames entry "
                                  f"'{elt.value}' is not a parameter "
                                  f"of {qual}")
        # (c) nested jitted defs: closure capture bakes enclosing-scope
        # values into the trace (silent retrace when they change)
        if depth > 0:
            flag(fn, f"jitted function {qual} is defined inside a "
                     "function: it is re-jitted per enclosing call and "
                     "its closure is baked into the trace; hoist it")
            loads = {
                n.id for n in ast.walk(fn)
                if isinstance(n, ast.Name)
                and isinstance(n.ctx, ast.Load)
            }
            free = loads - _locally_bound(fn) - mod_names - _BUILTINS
            for name in sorted(free):
                flag(fn, f"jitted {qual} closes over '{name}' from an "
                         "enclosing scope; pass it as an argument "
                         "(static or traced) instead")
    return out


# ---- PTA004: lock discipline for cross-thread state --------------------


@file_rule("PTA004", "lock-discipline")
def lock_discipline(ctx: FileContext) -> list[Violation]:
    c = ctx.contracts
    out: list[Violation] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        tc = c.thread_classes.get(node.name)
        if tc is None:
            continue
        # (attr -> list of (line, col, is_write, domain, locked))
        accesses: dict[str, list[tuple[int, int, bool, str, bool]]] = {}

        def visit_fn(fn, self_name, domain):
            lock_expr = f"{self_name}.{tc.lock_attr}"

            def rec(n, locked):
                if isinstance(n, _FUNC_NODES):
                    nested_domain = (
                        "background"
                        if n.lineno in ctx.background_lines
                        else domain
                    )
                    # nested functions capture self from the method;
                    # a lock held at DEFINITION time is not held when
                    # the closure later runs, so locked resets
                    for stmt in n.body:
                        rec_nested(stmt, False, nested_domain)
                    return
                if isinstance(n, ast.ClassDef):
                    return
                now_locked = locked
                if isinstance(n, ast.With):
                    if any(
                        _dotted(item.context_expr) == lock_expr
                        for item in n.items
                    ):
                        now_locked = True
                if isinstance(n, ast.Attribute) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id == self_name:
                    is_write = isinstance(n.ctx, (ast.Store, ast.Del))
                    accesses.setdefault(n.attr, []).append(
                        (n.lineno, n.col_offset, is_write, domain,
                         now_locked)
                    )
                for child in ast.iter_child_nodes(n):
                    rec(child, now_locked)

            def rec_nested(n, locked, nested_domain):
                nonlocal domain
                saved, domain = domain, nested_domain
                rec(n, locked)
                domain = saved

            for stmt in fn.body:
                rec(stmt, False)

        for fn in node.body:
            if not isinstance(fn, _FUNC_NODES):
                continue
            if fn.name == "__init__":
                # construction happens-before any thread start: the
                # documented handoff for initial state
                continue
            args = fn.args.posonlyargs + fn.args.args
            if not args:
                continue
            self_name = args[0].arg
            domain = (
                "background" if fn.lineno in ctx.background_lines
                else "main"
            )
            visit_fn(fn, self_name, domain)

        for attr, sites in accesses.items():
            domains_writing = {d for (_, _, w, d, _) in sites if w}
            domains_all = {d for (_, _, _, d, _) in sites}
            if not domains_writing or len(domains_all) < 2:
                continue
            if attr in tc.handoffs:
                continue
            for line, col, is_write, domain, locked in sites:
                if locked:
                    continue
                out.append(Violation(
                    code="PTA004", rule="lock-discipline",
                    path=ctx.path, line=line, col=col,
                    message=(
                        f"{node.name}.{attr} is written cross-thread "
                        f"({'write' if is_write else 'read'} from the "
                        f"{domain} thread without holding "
                        f"self.{tc.lock_attr}); lock it or declare a "
                        "documented handoff in analysis/contracts.py"
                    ),
                ))
    return out


# ---- PTA005: trace vocabulary + flag surface consistency ---------------


def _trace_vocab(ctx: FileContext, vocab_name: str) -> set[str] | None:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == vocab_name
            for t in node.targets
        ):
            consts = {
                n.value for n in ast.walk(node.value)
                if isinstance(n, ast.Constant)
                and isinstance(n.value, str)
            }
            if consts:
                return consts
    return None


@repo_rule("PTA005", "surface-consistency")
def surface_consistency(repo: RepoContext) -> list[Violation]:
    c = repo.contracts
    out: list[Violation] = []

    # -- trace event vocabulary --
    trace_ctx = next(
        (f for rel, f in repo.files.items()
         if rel.endswith(c.trace_module)),
        None,
    )
    vocab: set[str] | None = None
    if trace_ctx is not None:
        vocab = _trace_vocab(trace_ctx, c.trace_vocab_name)
        if vocab is None:
            out.append(Violation(
                code="PTA005", rule="surface-consistency",
                path=trace_ctx.path, line=1, col=0,
                message=(
                    f"{c.trace_vocab_name} vocabulary declaration not "
                    f"found in {c.trace_module}"
                ),
            ))
    if vocab is not None:
        for rel, fctx in repo.files.items():
            for node in ast.walk(fctx.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("emit", "emit_many")):
                    continue
                base = node.func.value
                base_name = (
                    base.attr if isinstance(base, ast.Attribute)
                    else base.id if isinstance(base, ast.Name) else None
                )
                if base_name != "trace":
                    continue
                if not node.args:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    if arg.value not in vocab:
                        out.append(Violation(
                            code="PTA005", rule="surface-consistency",
                            path=rel, line=node.lineno,
                            col=node.col_offset,
                            message=(
                                f"trace event '{arg.value}' is not in "
                                f"the declared {c.trace_vocab_name} "
                                f"vocabulary ({c.trace_module})"
                            ),
                        ))
                else:
                    out.append(Violation(
                        code="PTA005", rule="surface-consistency",
                        path=rel, line=node.lineno, col=node.col_offset,
                        message=(
                            "dynamic trace event name: emit a literal "
                            "from the declared vocabulary (or suppress "
                            "with a reason)"
                        ),
                    ))

    # -- cli flag surface --
    cli_ctx = next(
        (f for rel, f in repo.files.items()
         if rel.endswith(c.flag_module)),
        None,
    )
    if cli_ctx is not None:
        flags: list[tuple[str, int, int]] = []
        for node in ast.walk(cli_ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"
                    and node.args):
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.startswith("--")):
                continue
            hidden = any(
                kw.arg == "help"
                and isinstance(kw.value, ast.Attribute)
                and kw.value.attr == "SUPPRESS"
                for kw in node.keywords
            )
            if not hidden:
                flags.append((arg.value, node.lineno, node.col_offset))
        doc_texts = {
            doc: repo.read_text(doc) for doc in c.flag_doc_files
        }
        for doc, text in doc_texts.items():
            if text is None:
                out.append(Violation(
                    code="PTA005", rule="surface-consistency",
                    path=cli_ctx.path, line=1, col=0,
                    message=f"flag doc file '{doc}' not found",
                ))
        for flag, line, col in flags:
            pattern = re.compile(re.escape(flag) + r"(?![\w-])")
            for doc, text in doc_texts.items():
                if text is not None and not pattern.search(text):
                    out.append(Violation(
                        code="PTA005", rule="surface-consistency",
                        path=cli_ctx.path, line=line, col=col,
                        message=(
                            f"flag {flag} is not documented in {doc}"
                        ),
                    ))

    # -- metric family surface --
    # every poseidon_* family registered in the metrics module must
    # appear in the README's observability reference and vice versa:
    # an operator alerting on a renamed family pages on silence, and a
    # documented-but-unregistered family is a dashboard query that
    # matches nothing. Same drift-proofing shape as the trace
    # vocabulary above: the code side is the AST (literal first args
    # to .counter/.gauge/.histogram), the doc side is a token scan.
    metrics_ctx = next(
        (f for rel, f in repo.files.items()
         if rel.endswith(c.metrics_module)),
        None,
    )
    if metrics_ctx is not None:
        registered: dict[str, tuple[int, int]] = {}
        for node in ast.walk(metrics_ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in (
                        "counter", "gauge", "histogram")
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str) and \
                    arg.value.startswith("poseidon_"):
                registered.setdefault(
                    arg.value, (node.lineno, node.col_offset)
                )
        doc_text = repo.read_text(c.metrics_doc_file)
        if doc_text is None:
            out.append(Violation(
                code="PTA005", rule="surface-consistency",
                path=metrics_ctx.path, line=1, col=0,
                message=(
                    f"metric doc file '{c.metrics_doc_file}' not found"
                ),
            ))
        elif registered:
            documented = {
                m for m in re.findall(
                    r"\bposeidon_[a-z0-9_]+", doc_text
                )
                if not m.startswith("poseidon_tpu")
            }
            for name in sorted(registered):
                if name not in documented:
                    line, col = registered[name]
                    out.append(Violation(
                        code="PTA005", rule="surface-consistency",
                        path=metrics_ctx.path, line=line, col=col,
                        message=(
                            f"metric family '{name}' is registered "
                            f"but not documented in "
                            f"{c.metrics_doc_file}'s observability "
                            "reference"
                        ),
                    ))
            # reverse direction: histogram exports add per-series
            # _bucket/_sum/_count suffixes, so strip those before
            # deciding a documented token names a missing family; a
            # token ending in '_' is a prose prefix reference
            # ("the poseidon_outbox_* family") — fine as long as some
            # registered family matches, but it does NOT satisfy the
            # forward per-family requirement above
            def _family(tok: str) -> str:
                for suf in ("_bucket", "_sum", "_count"):
                    if tok.endswith(suf) and \
                            tok[: -len(suf)] in registered:
                        return tok[: -len(suf)]
                return tok
            for tok in sorted(documented):
                if tok.endswith("_") and any(
                    name.startswith(tok) for name in registered
                ):
                    continue
                if _family(tok) not in registered:
                    out.append(Violation(
                        code="PTA005", rule="surface-consistency",
                        path=metrics_ctx.path, line=1, col=0,
                        message=(
                            f"{c.metrics_doc_file} documents metric "
                            f"family '{tok}' that is not registered "
                            f"in {c.metrics_module} — delete the "
                            "stale reference or register the family"
                        ),
                    ))
    return out
