"""CLI entry: ``python -m poseidon_tpu.analysis [options] [paths]``.

Exit codes: 0 clean, 1 violations found, 2 usage error. CI runs
``python -m poseidon_tpu.analysis --format=json --audit-suppressions``
as a blocking step (after ruff, before the test suite) and the jaxpr
soundness audits (``--jaxpr``) on both the plain and 8-virtual-device
lanes.

Passes:

- the AST rules (always): PTA001-PTA005 file/repo rules plus the
  whole-program passes — PTA006 (lockset race detection over the
  thread model), PTA007 (recompile-hazard static-arg provenance) and
  PTA010 (lock-order deadlock + no-blocking-under-lock);
- ``--audit-suppressions``: additionally report DEAD ``# noqa:
  PTA0xx`` comments (rule no longer fires on that statement);
- ``--jaxpr``: additionally trace the production kernels and audit
  their closed jaxprs — the fingerprint/structure audit (PTA008,
  ``analysis/kernel_fingerprints.json``) and the padding-taint
  dataflow audit (PTA009, ``analysis/padding_taint.py``) share one
  trace. ``--jaxpr-only`` runs just those audits (the CI audit step —
  its lint step already ran the AST rules). ``--update-fingerprints``
  re-pins the fingerprint file instead of diffing (structural
  contract problems still report);
- ``--rule PTA0NN[,PTA0MM]``: run only the named rule(s) — CI lanes
  and local iteration isolate one pass without paying for the rest
  (an unknown code exits 2: a typo'd rule id must not ride a green
  stamp, exactly like a typo'd path). Selecting no jaxpr-backed rule
  skips tracing; selecting ONLY jaxpr-backed rules skips the AST
  walk.

A path argument that exists but contains no Python targets is a usage
error (exit 2), not a clean run: a typo'd CI path must fail loudly.

The JSON document's schema is load-bearing for CI and downstream
tooling and is locked by tests/test_analysis.py::TestJsonSchema:
``violations`` (objects with exactly code/rule/path/line/col/message,
sorted by (path, line, col, code)), ``count``, ``files_scanned``, and
— only when ``--jaxpr`` ran — ``kernels_audited``.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

from poseidon_tpu.analysis.core import (
    FILE_RULES,
    REPO_RULES,
    _ensure_rules_loaded,
    analyze_and_audit,
    analyze_tree,
    format_human,
    format_json,
)

# codes not produced by a registered AST rule: PTA000 comes from the
# parser/suppression layer, PTA008/PTA009 from the jaxpr audits
_EXTRA_CODES = ("PTA000", "PTA008", "PTA009")
_JAXPR_CODES = frozenset(("PTA008", "PTA009"))


def _known_codes() -> set[str]:
    _ensure_rules_loaded()
    codes = {code for code, _name, _fn in FILE_RULES}
    codes.update(code for code, _name, _fn in REPO_RULES)
    codes.update(_EXTRA_CODES)
    return codes


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m poseidon_tpu.analysis",
        description=(
            "Contract linter: enforce the repo's hot-path, O(churn), "
            "jit-hygiene, thread-discipline, surface-consistency, "
            "lockset-race, recompile-hazard and lock-order invariants "
            "(rules PTA001-PTA007, PTA010; see analysis/rules.py, "
            "analysis/threads.py, analysis/recompile.py, analysis/"
            "locks.py), plus the compiled-kernel jaxpr audits "
            "(PTA008 fingerprints, analysis/jaxpr_check.py; PTA009 "
            "padding-taint dataflow, analysis/padding_taint.py)"
        ),
    )
    p.add_argument(
        "paths", nargs="*",
        help="files to scan (default: the shipped tree — "
             "poseidon_tpu/, scripts/, tests/)",
    )
    p.add_argument(
        "--format", choices=("human", "json"), default="human",
        help="output format (json for CI)",
    )
    p.add_argument(
        "--root", default=".",
        help="repo root (scopes and doc files resolve against it)",
    )
    p.add_argument(
        "--rule", default=None, metavar="PTA0NN[,PTA0MM]",
        help="run only the named rule(s); unknown codes exit 2",
    )
    p.add_argument(
        "--audit-suppressions", action="store_true",
        help="also report dead '# noqa: PTA0xx' suppressions "
             "(reasoned noqas whose rule no longer fires there)",
    )
    p.add_argument(
        "--jaxpr", action="store_true",
        help="also trace the production kernels and audit their "
             "closed jaxprs (callbacks/transfers/f64/fingerprints "
             "via PTA008, padding-taint dataflow via PTA009)",
    )
    p.add_argument(
        "--jaxpr-only", action="store_true",
        help="run ONLY the kernel jaxpr audits, skipping the AST "
             "rules (the CI audit step: the lint step already ran "
             "them)",
    )
    p.add_argument(
        "--update-fingerprints", action="store_true",
        help="re-trace the kernels and rewrite analysis/"
             "kernel_fingerprints.json (implies --jaxpr)",
    )
    args = p.parse_args(argv)

    selection: set[str] | None = None
    if args.rule is not None:
        selection = {c.strip() for c in args.rule.split(",") if c.strip()}
        unknown = selection - _known_codes()
        if not selection or unknown:
            bad = ", ".join(sorted(unknown)) or "(empty)"
            print(
                f"unknown rule id(s): {bad} — known: "
                + ", ".join(sorted(_known_codes())),
                file=sys.stderr,
            )
            return 2

    root = pathlib.Path(args.root).resolve()
    paths = None
    if args.paths:
        paths = []
        for raw in args.paths:
            path = pathlib.Path(raw).resolve()
            if not path.exists():
                print(f"no such file: {raw}", file=sys.stderr)
                return 2
            if not path.is_relative_to(root):
                print(
                    f"{raw} is outside --root {root} (scopes are "
                    "declared root-relative)", file=sys.stderr,
                )
                return 2
            if path.is_dir():
                paths.extend(sorted(path.rglob("*.py")))
            else:
                paths.append(path)
        if not paths:
            # a target that exists but holds no Python files is a
            # typo'd CI path, not a clean tree: refuse the green stamp
            print(
                "no Python targets under: "
                + " ".join(args.paths)
                + " — pass files or directories containing .py "
                "files (usage error, exit 2)",
                file=sys.stderr,
            )
            return 2

    jaxpr_requested = (
        args.jaxpr or args.jaxpr_only or args.update_fingerprints
    )
    run_ast = not args.jaxpr_only and (
        selection is None or bool(selection - _JAXPR_CODES)
    )
    run_pta008 = args.update_fingerprints or (
        jaxpr_requested and (selection is None or "PTA008" in selection)
    )
    run_pta009 = jaxpr_requested and (
        selection is None or "PTA009" in selection
    )

    if run_ast:
        run = (
            analyze_and_audit if args.audit_suppressions
            else analyze_tree
        )
        violations, files_scanned = run(root, paths)
    else:
        violations, files_scanned = [], 0
    kernels_audited = None
    if run_pta008 or run_pta009:
        from poseidon_tpu.analysis.jaxpr_check import (
            trace_production_kernels,
        )

        # both jaxpr audits read the same traces; trace once
        traces = trace_production_kernels()
        if run_pta008:
            from poseidon_tpu.analysis.jaxpr_check import run_jaxpr_audit

            jaxpr_violations, kernels_audited = run_jaxpr_audit(
                root, update=args.update_fingerprints, traces=traces
            )
            violations = violations + jaxpr_violations
        if run_pta009:
            from poseidon_tpu.analysis.padding_taint import (
                run_padding_audit,
            )

            taint_violations, kernels_audited = run_padding_audit(
                root, traces=traces
            )
            violations = violations + taint_violations
    if selection is not None:
        violations = [v for v in violations if v.code in selection]
    # the merged document keeps the locked (path, line, col, code)
    # ordering whichever passes contributed
    violations = sorted(
        violations, key=lambda v: (v.path, v.line, v.col, v.code)
    )

    if args.format == "json":
        print(format_json(violations, files_scanned, kernels_audited))
    else:
        out = format_human(violations, files_scanned)
        if kernels_audited is not None:
            out += f"\n{kernels_audited} kernel jaxpr(s) audited"
        print(out)
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
