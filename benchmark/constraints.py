"""Placement constraints: the part of Kubernetes' v1 vocabulary the
benchmark generates, serves and checks. Standard library only: the
apiserver child and the plain reference both import it, and neither
imports the program.

A node carries labels and taints (``{key, value, effect}``). A pod's
constraint is a dict with any of three keys, each in its Kubernetes
shape:

- ``node_selector`` (``spec.nodeSelector``): every label given must be
  on the node with that value;
- ``affinity`` (``spec.affinity.nodeAffinity``): only
  ``requiredDuringSchedulingIgnoredDuringExecution.nodeSelectorTerms``.
  Terms are ORed, each term's ``matchExpressions`` ANDed; operators
  ``In``, ``NotIn``, ``Exists``, ``DoesNotExist``. A term with no
  expressions matches no node, and so does an empty list of terms;
- ``tolerations`` (``spec.tolerations``): ``{key, operator, value,
  effect}``, operator ``Equal`` (the default) or ``Exists``. An empty key
  with ``Exists`` tolerates every taint; an empty effect matches every
  effect.

A node admits a pod when the selector and the affinity hold and every
taint of effect ``NoSchedule`` or ``NoExecute`` is tolerated. The soft
forms (``PreferNoSchedule``, preferred affinity) never filter, and the
generator refuses to emit them (``validate``).
"""

from __future__ import annotations

import json

KEYS = ("node_selector", "affinity", "tolerations")
FILTERING = ("NoSchedule", "NoExecute")
OPERATORS = ("In", "NotIn", "Exists", "DoesNotExist")
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def tolerates(tol: dict, taint: dict) -> bool:
    """Kubernetes' ``Toleration.ToleratesTaint``."""
    if tol.get("effect") and tol["effect"] != taint.get("effect"):
        return False
    if tol.get("key") and tol["key"] != taint.get("key"):
        return False
    if tol.get("operator", "Equal") == "Exists":
        return True
    return tol.get("value", "") == taint.get("value", "")


def _holds(expr: dict, labels: dict) -> bool:
    op, key = expr["operator"], expr["key"]
    if op == "Exists":
        return key in labels
    if op == "DoesNotExist":
        return key not in labels
    inside = key in labels and labels[key] in expr.get("values", [])
    return inside if op == "In" else not inside


def _affinity_holds(affinity: dict, labels: dict) -> bool:
    terms = affinity.get(REQUIRED, {}).get("nodeSelectorTerms", [])
    return any(t.get("matchExpressions")
               and all(_holds(e, labels) for e in t["matchExpressions"])
               for t in terms)


def feasible(constraint: dict | None, labels: dict, taints: list) -> bool:
    """Whether a node with ``labels`` and ``taints`` admits a pod with
    ``constraint`` (None: no constraint)."""
    c = constraint or {}
    if any(labels.get(k) != v for k, v in c.get("node_selector", {}).items()):
        return False
    if "affinity" in c and not _affinity_holds(c["affinity"], labels):
        return False
    tols = c.get("tolerations", [])
    return all(any(tolerates(t, taint) for t in tols)
               for taint in taints if taint.get("effect") in FILTERING)


def signature(constraint: dict | None) -> str:
    """The canonical form of a constraint: pods with one signature are
    admitted by the same nodes."""
    return json.dumps({k: v for k, v in (constraint or {}).items() if v},
                      sort_keys=True, separators=(",", ":"))


# ---- what a configuration may state ---------------------------------------

def _refuse(where: str, what: str) -> None:
    raise ValueError(f"{where}: {what}")


def _check_taint(where: str, taint: dict) -> None:
    if set(taint) - {"key", "value", "effect"} or not taint.get("key"):
        _refuse(where, f"a taint is {{key, value, effect}}: {taint}")
    if taint.get("effect") not in FILTERING:
        _refuse(where, f"taint effect {taint.get('effect')!r}: only "
                       f"{FILTERING} (PreferNoSchedule is soft, out of scope)")


def _check_constraint(where: str, c: dict) -> None:
    extra = set(c) - set(KEYS) - {"share"}
    if extra:
        _refuse(where, f"unknown keys {sorted(extra)}")
    for k, v in c.get("node_selector", {}).items():
        if not isinstance(v, str):
            _refuse(where, f"nodeSelector {k!r} must be a string")
    if "affinity" in c:
        a = c["affinity"]
        if set(a) != {REQUIRED} or set(a[REQUIRED]) != {"nodeSelectorTerms"}:
            _refuse(where, f"affinity is {REQUIRED}.nodeSelectorTerms only "
                           f"(preferred affinity is soft, out of scope)")
        for term in a[REQUIRED]["nodeSelectorTerms"]:
            if set(term) != {"matchExpressions"}:
                _refuse(where, "a term holds matchExpressions only")
            for e in term["matchExpressions"]:
                if e.get("operator") not in OPERATORS or not e.get("key"):
                    _refuse(where, f"expression {e}: operators {OPERATORS}")
    for t in c.get("tolerations", []):
        if set(t) - {"key", "operator", "value", "effect"}:
            _refuse(where, f"a toleration is {{key, operator, value, "
                           f"effect}}: {t}")
        if t.get("operator", "Equal") not in ("Equal", "Exists"):
            _refuse(where, f"toleration operator {t['operator']!r}")
        if not t.get("key") and t.get("operator") != "Exists":
            _refuse(where, "an empty toleration key needs operator Exists")
        if t.get("effect") and t["effect"] not in FILTERING:
            _refuse(where, f"toleration effect {t['effect']!r}")


def _check_shares(where: str, items: list) -> None:
    shares = [float(i.get("share", -1)) for i in items]
    if not items or min(shares) <= 0 or sum(shares) > 1 + 1e-9:
        _refuse(where, "each share is positive and they sum to at most 1")


def validate(cfg: dict) -> None:
    """Refuse a constraint vocabulary the harness cannot honour."""
    c, p = cfg["cluster"], cfg["pods"]
    if "zones" in c and int(c["zones"]) < 1:
        _refuse("cluster.zones", "a positive count")
    if "node_pools" in c:
        _check_shares("cluster.node_pools", c["node_pools"])
        for pool in c["node_pools"]:
            if set(pool) - {"share", "labels", "taints"}:
                _refuse("cluster.node_pools", f"unknown keys in {pool}")
            for taint in pool.get("taints", []):
                _check_taint("cluster.node_pools", taint)
    if "constraints" in p:
        _check_shares("pods.constraints", p["constraints"])
        for con in p["constraints"]:
            _check_constraint("pods.constraints", con)
