"""Contract linter: whole-program analysis enforcing this repo's
performance and concurrency invariants.

The codebase *states* its contracts — rounds are O(churn), the resident
round is one fused program with exactly one host sync, the bridge is
single-threaded with documented cross-thread handoffs — but a contract
nobody checks is a comment. This package makes them machine-checked:

- ``python -m poseidon_tpu.analysis`` runs every registered rule over
  the shipped tree (``poseidon_tpu/``, ``scripts/``, and
  ``tests/`` under a narrowed per-rule scope) and exits non-zero
  on any violation; CI runs it as a blocking step (with
  ``--audit-suppressions``, so DEAD ``# noqa`` comments fail too).
- Rules are repo-specific, declared against ``contracts.py``. The
  file-local set (``rules.py``, PTA001-PTA005) covers host syncs,
  cluster loops, jit hygiene, marker-based lock discipline, and the
  trace/flag surface. The whole-program set goes further: PTA006
  (``threads.py``) builds a repo-wide thread model from the markers
  PLUS spawn-site inference and runs an Eraser-style lockset race
  check that VERIFIES the PTA004 handoff allowlist (stale entries are
  violations); PTA007 (``recompile.py``) is dataflow over static-arg
  and pad-shape provenance, catching the grow-only-floor recompile
  bug class PR 8 had to flush out at runtime.
- ``--jaxpr`` (``jaxpr_check.py``, PTA008) traces the production
  kernels on tiny shapes and audits their closed jaxprs: zero host
  callbacks, zero smuggled transfers/constants, no f64 leaks, and a
  pinned per-kernel primitive-count fingerprint
  (``kernel_fingerprints.json``) so a fusion break is a CI diff, not
  a perf regression three PRs later.
- Violations are suppressed inline with ``# noqa: PTA001 -- reason``;
  the reason is REQUIRED (a bare suppression is itself a violation,
  PTA000), the suppression covers its whole statement span, and the
  suppression audit reports entries whose rule no longer fires.

The static passes pair with runtime teeth in ``poseidon_tpu/guards.py``
(``jax.transfer_guard`` around the resident round, a compile counter
for the recompile budget, the fetch deadline) — the linter catches the
pattern at review time, the guards catch whatever slips through at run
time.
"""

from poseidon_tpu.analysis.contracts import Contracts, DEFAULT_CONTRACTS
from poseidon_tpu.analysis.core import (
    Violation,
    analyze_and_audit,
    analyze_file,
    analyze_tree,
    audit_suppressions,
    default_targets,
    format_human,
    format_json,
)

__all__ = [
    "Contracts",
    "DEFAULT_CONTRACTS",
    "Violation",
    "analyze_and_audit",
    "analyze_file",
    "analyze_tree",
    "audit_suppressions",
    "default_targets",
    "format_human",
    "format_json",
]
