"""Deterministic chaos harness: seeded fault schedules + invariants.

The failure-domain survival story (node-failure storms, apiserver
outages, overload bursts) is only real if it is *machine-checked*:
``scenarios.py`` drives the REAL daemon loop (cli.run_loop, fake
apiserver, journal/outbox/guard all live) through seeded,
round-scheduled fault injections and asserts the invariants that
define "survived" — exactly-once actuation, zero lost pods, guard
release within the grace bound, and bounded time back to a certified
round after the fault clears. ``tests/test_chaos.py`` runs each
scenario, reruns it to check that recovery compiles nothing, and (slow)
fuzzes more seeds.
"""

from poseidon_tpu.chaos.scenarios import (
    ChaosOrchestrator,
    ChaosScenario,
    FaultAction,
    InvariantReport,
    check_invariants,
    read_stats,
    rounds_to_recover,
    run_daemon_scenario,
    scenario_apiserver_outage,
    scenario_composite,
    scenario_node_storm,
    scenario_overload_burst,
    seed_cluster,
)

__all__ = [
    "ChaosOrchestrator",
    "ChaosScenario",
    "FaultAction",
    "InvariantReport",
    "check_invariants",
    "read_stats",
    "rounds_to_recover",
    "run_daemon_scenario",
    "scenario_apiserver_outage",
    "scenario_composite",
    "scenario_node_storm",
    "scenario_overload_burst",
    "seed_cluster",
]
