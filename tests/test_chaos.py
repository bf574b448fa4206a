"""Failure-domain survival: the degradation ladder + chaos scenarios.

Four layers, bottom up:

- unit: the client's distinct timeout retry class (a hung apiserver is
  not a 5xx), the mass-eviction guard's grace exit + trace/metric
  surface, the staged displaced-pod re-queue, the actuation outbox's
  park/replay/dead-letter ladder, and the watch subsystem's bounded
  memory under a long outage;
- driver: the run_loop watchdog (round-deadline misses -> declared
  overload) and the express shed-to-tick path;
- scenario: the seeded chaos harness drives the REAL daemon loop
  through the three acceptance scenarios (mass node loss, apiserver
  outage window, overload burst) and machine-checks the survival
  invariants (exactly-once actuation, zero lost pods, guard release
  within the bound, bounded recovery, zero degrades);
- fuzz (slow): the same scenarios across multiple seeds.
"""

from __future__ import annotations

import time

import pytest

from poseidon_tpu.apiclient import FakeApiServer, K8sApiClient
from poseidon_tpu.apiclient.client import backoff_delay
from poseidon_tpu.apiclient.watch import ClusterWatcher
from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.chaos import (
    check_invariants,
    run_daemon_scenario,
    scenario_apiserver_outage,
    scenario_node_storm,
    scenario_overload_burst,
)
from poseidon_tpu.cluster import Machine, Task, TaskPhase
from poseidon_tpu.ha import ActuationOutbox, OutageDetector
from poseidon_tpu.obs import MetricsRegistry, SchedulerMetrics


def _machines(n: int, prefix: str = "n") -> list[Machine]:
    return [
        Machine(name=f"{prefix}{i}", cpu_capacity=8.0,
                cpu_allocatable=8.0, max_tasks=10)
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# client: the hung apiserver is its own retry class
# ---------------------------------------------------------------------------


class TestClientRetryStats:
    def test_timeout_counted_distinctly_from_5xx(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            client = K8sApiClient(
                port=server.port, timeout_s=0.15, retries=1,
                backoff_base_s=0.01, backoff_cap_s=0.02,
            )
            # a slow (hung) response: the client's socket timeout
            # fires while the server sleeps
            server.delay_next(2, seconds=1.0)
            with pytest.raises(Exception):
                client.all_nodes()
            assert client.retry_stats["timeout"] >= 1
            assert client.retry_stats["5xx"] == 0
            # an erroring apiserver lands in the 5xx bucket instead
            server.delay_next(0, 0)
            server.fail_next(2)
            with pytest.raises(Exception):
                client.all_nodes()
            assert client.retry_stats["5xx"] >= 1

    def test_429_and_transport_classes(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            client = K8sApiClient(
                port=server.port, timeout_s=1.0, retries=1,
                backoff_base_s=0.01, backoff_cap_s=0.02,
            )
            server.rate_limit_next(1, retry_after_s=0.01)
            client.all_nodes()  # retried past the 429
            assert client.retry_stats["429"] == 1
            server.disconnect_next(1)
            client.all_nodes()  # retried past the mid-body cut
            assert client.retry_stats["transport"] >= 1

    def test_backoff_delay_bounded_with_jitter(self):
        # the reconnect/retry delay never exceeds cap * 1.5 (the
        # jitter factor's upper bound), even at absurd attempt counts
        for attempt in (0, 3, 10, 60):
            for _ in range(50):
                d = backoff_delay(attempt, base_s=0.05, cap_s=2.0)
                assert d <= 2.0 * 1.5 + 1e-9
                assert d >= 0


# ---------------------------------------------------------------------------
# the mass-eviction guard: grace exit + observability
# ---------------------------------------------------------------------------


class TestGuardGraceExit:
    def test_grace_window_accepts_before_strikes(self):
        metrics = SchedulerMetrics(MetricsRegistry())
        bridge = SchedulerBridge(
            cost_model="trivial", shrink_grace_s=0.05, metrics=metrics,
        )
        bridge.observe_nodes(_machines(10))
        assert len(bridge.machines) == 10
        survivors = _machines(10)[:3]
        bridge.observe_nodes(survivors)        # strike 1: held
        assert len(bridge.machines) == 10
        time.sleep(0.06)
        bridge.observe_nodes(survivors)        # grace elapsed: accept
        assert len(bridge.machines) == 3
        events = [e.event for e in bridge.trace.events]
        assert "EVICTION_GUARD_HOLD" in events
        rel = [e for e in bridge.trace.events
               if e.event == "EVICTION_GUARD_RELEASE"]
        assert rel and rel[-1].detail["outcome"] == "accepted"
        assert rel[-1].detail["kind"] == "node"
        text = metrics.registry.render()
        assert 'poseidon_eviction_guard_holds_total{kind="node"} 1' \
            in text
        assert ('poseidon_eviction_guard_releases_total'
                '{kind="node",outcome="accepted"} 1') in text
        assert 'poseidon_eviction_guard_active{kind="node"} 0' in text

    def test_recovered_release_when_snapshot_heals(self):
        metrics = SchedulerMetrics(MetricsRegistry())
        bridge = SchedulerBridge(
            cost_model="trivial", shrink_grace_s=60.0, metrics=metrics,
        )
        full = _machines(10)
        bridge.observe_nodes(full)
        bridge.observe_nodes(full[:3])         # strike 1: held
        assert bridge._node_shrink_strikes == 1
        bridge.observe_nodes(full)             # healed
        assert bridge._node_shrink_strikes == 0
        rel = [e for e in bridge.trace.events
               if e.event == "EVICTION_GUARD_RELEASE"]
        assert rel and rel[-1].detail["outcome"] == "recovered"
        assert len(bridge.machines) == 10
        text = metrics.registry.render()
        assert ('poseidon_eviction_guard_releases_total'
                '{kind="node",outcome="recovered"} 1') in text

    def test_strikes_exit_still_works(self):
        # the poll-counted exit is unchanged (grace only ADDS an exit)
        bridge = SchedulerBridge(
            cost_model="trivial", shrink_grace_s=3600.0,
        )
        full = _machines(10)
        bridge.observe_nodes(full)
        survivors = full[:3]
        bridge.observe_nodes(survivors)
        bridge.observe_nodes(survivors)
        assert len(bridge.machines) == 10      # still held
        bridge.observe_nodes(survivors)        # strike 3: accepted
        assert len(bridge.machines) == 3


# ---------------------------------------------------------------------------
# staged displaced-pod re-queue
# ---------------------------------------------------------------------------


class TestStagedRequeue:
    def _bridge_with_running(self, n_nodes=4, per_node=3, budget=4):
        bridge = SchedulerBridge(
            cost_model="trivial", max_migrations_per_round=budget,
        )
        bridge.observe_nodes(_machines(n_nodes))
        pods = []
        for i in range(n_nodes):
            for j in range(per_node):
                pods.append(Task(
                    uid=f"p{i}-{j}", phase=TaskPhase.RUNNING,
                    machine=f"n{i}", cpu_request=0.1,
                ))
        bridge.observe_pods(pods)
        return bridge

    def test_rack_loss_drains_in_budget_waves(self):
        # 9 pods displaced, budget 4 -> waves of 4/4/1
        bridge = self._bridge_with_running(
            n_nodes=4, per_node=3, budget=4,
        )
        for name in ("n1", "n2", "n3"):
            bridge.observe_node_event(
                "DELETED", Machine(name=name),
            )
        # displacement parks; state truth is immediate
        assert all(
            bridge.tasks[f"p{i}-{j}"].phase == TaskPhase.PENDING
            for i in (1, 2, 3) for j in range(3)
        )
        admitted = []
        for _ in range(4):
            r = bridge.run_scheduler()
            admitted.append(r.stats.requeue_admitted)
            for uid, m in r.bindings.items():
                bridge.confirm_binding(uid, m)
        assert admitted[:3] == [4, 4, 1]
        assert bridge._displaced_parked == {}
        # every round's NEW schedulable displacement respected the
        # budget (placements may lag when capacity is tight, but
        # admission never exceeded 4)
        evict_events = [
            e for e in bridge.trace.events if e.event == "EVICT"
        ]
        assert len(evict_events) == 9
        assert all(e.detail["parked"] for e in evict_events)

    def test_small_removal_admitted_same_tick(self):
        # below the budget, behavior matches the old immediate flip:
        # observe precedes begin in the tick, so the pods are
        # schedulable in the very next round
        bridge = self._bridge_with_running(
            n_nodes=4, per_node=3, budget=64,
        )
        bridge.observe_node_event("DELETED", Machine(name="n3"))
        r = bridge.run_scheduler()
        assert r.stats.requeue_admitted == 3
        assert r.stats.displaced_parked == 0

    def test_parked_pod_deleted_while_waiting(self):
        bridge = self._bridge_with_running(
            n_nodes=2, per_node=4, budget=2,
        )
        bridge.observe_node_event("DELETED", Machine(name="n1"))
        assert len(bridge._displaced_parked) == 4
        # two of the parked pods leave the cluster before admission
        parked = list(bridge._displaced_parked)
        bridge.observe_pod_event(
            "DELETED", bridge.tasks[parked[0]]
        )
        bridge.observe_pod_event(
            "DELETED", bridge.tasks[parked[1]]
        )
        assert len(bridge._displaced_parked) == 2
        r = bridge.run_scheduler()
        assert r.stats.requeue_admitted == 2
        assert bridge._displaced_parked == {}

    def test_parked_pods_excluded_from_cluster_view(self):
        bridge = self._bridge_with_running(
            n_nodes=2, per_node=4, budget=1,
        )
        bridge.observe_node_event("DELETED", Machine(name="n1"))
        cluster = bridge.cluster_state()
        assert len(cluster.tasks) == 4  # 4 still running on n0
        assert len(bridge.tasks) == 8   # state truth keeps all 8


# ---------------------------------------------------------------------------
# the actuation outbox
# ---------------------------------------------------------------------------


class TestOutbox:
    def test_park_and_replay_exactly_once(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            server.add_pod("p0")
            client = K8sApiClient(
                port=server.port, timeout_s=1.0, retries=0,
                backoff_base_s=0.01, backoff_cap_s=0.02,
            )
            settled = []
            outbox = ActuationOutbox(
                client, base_backoff_s=0.01, cap_backoff_s=0.05,
                on_settled=lambda e, o: settled.append((e.uid, o)),
            )
            server.set_outage(True)
            assert client.bind_outcome("default/p0", "n0") \
                == "unreachable"
            outbox.enqueue("bind", "default/p0", machine="n0")
            time.sleep(0.08)
            counts = outbox.pump()
            assert counts["waiting"] == 1       # probe failed
            assert outbox.pending == 1
            server.set_outage(False)
            time.sleep(0.12)
            counts = outbox.pump()
            assert counts["replayed"] == 1
            assert settled == [("default/p0", "replayed")]
            assert outbox.pending == 0
            server.apply_pending()
            assert server.bindings == [("default/p0", "n0")]
            # replaying again is a no-op (idempotent, exactly-once)
            outbox.enqueue("bind", "default/p0", machine="n0")
            time.sleep(0.03)
            counts = outbox.pump()
            assert counts["already-applied"] == 1
            assert server.bindings == [("default/p0", "n0")]

    def test_recovery_drains_whole_backlog_in_one_pump(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            for i in range(6):
                server.add_pod(f"p{i}")
            client = K8sApiClient(
                port=server.port, timeout_s=1.0, retries=0,
            )
            # entries enqueued with fresh backoff stamps; the first
            # settle must drain ALL of them now, not per-stamp
            outbox = ActuationOutbox(
                client, base_backoff_s=5.0, cap_backoff_s=10.0,
            )
            for i in range(6):
                outbox.enqueue("bind", f"default/p{i}", machine="n0")
            counts = outbox.pump(force=True)
            assert counts["replayed"] == 6
            assert outbox.pending == 0

    def test_empty_pump_never_touches_the_wire(self):
        # the pump runs every tick: with nothing parked it must cost
        # no apiserver request at all
        class _DeadClient:
            def __getattr__(self, name):
                raise AssertionError(f"empty pump called client.{name}")

        counts = ActuationOutbox(_DeadClient()).pump()
        assert not any(counts.values())

    def test_dead_letter_on_rejection(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            server.add_pod("p0", node="n1-gone", phase="Running")
            client = K8sApiClient(port=server.port, retries=0)
            dead = []
            outbox = ActuationOutbox(
                client, base_backoff_s=0.01,
                on_dead_letter=lambda e: dead.append(e.uid),
            )
            # the pod is bound elsewhere: the parked bind can never
            # land -> dead-letter, not eternal retry
            outbox.enqueue("bind", "default/p0", machine="n0")
            time.sleep(0.03)
            outbox.pump()
            assert dead == ["default/p0"]
            assert outbox.pending == 0
            assert outbox.dead_letters_total == 1

    def test_outage_detector_one_episode(self):
        flips = []
        det = OutageDetector(3, on_change=flips.append)
        for _ in range(2):
            det.note_failure()
        assert not det.active
        det.note_failure()
        assert det.active and flips == [True]
        for _ in range(5):
            det.note_failure()     # still ONE episode
        assert det.episodes == 1
        det.note_success()
        assert not det.active and flips == [True, False]


# ---------------------------------------------------------------------------
# watch subsystem under a long outage: bounded memory
# ---------------------------------------------------------------------------


class TestWatchOutageBounded:
    def test_reconnect_queue_stays_bounded(self):
        with FakeApiServer() as server:
            server.add_node("n0")
            server.add_pod("p0")
            client = K8sApiClient(port=server.port, timeout_s=0.5)
            watcher = ClusterWatcher(
                client, max_lag_s=30.0,
                backoff_base_s=0.005, backoff_cap_s=0.01,
            )
            try:
                watcher.tick()  # seed
                server.set_outage(True)
                # dozens of failed reconnect attempts accumulate...
                time.sleep(0.6)
                for stream in watcher._streams.values():
                    # ...but at most ONE queued RECONNECT per
                    # consecutive-failure run (+ a possible stream
                    # close); the rest coalesce into the counter
                    assert stream.queue.qsize() <= 3, (
                        stream.resource, stream.queue.qsize(),
                    )
                total_coalesced = sum(
                    s.coalesced_reconnects
                    for s in watcher._streams.values()
                )
                assert total_coalesced >= 5
                server.set_outage(False)
                delta = watcher.tick()
                # the folded counts are exact, not dropped
                assert delta.reconnects >= total_coalesced
            finally:
                watcher.stop()

    def test_no_resync_storm_from_quiet_outage(self):
        # a long outage with no staleness bound hit must not resync
        # in a loop (the storm gauge's input stays quiet)
        with FakeApiServer() as server:
            server.add_node("n0")
            client = K8sApiClient(port=server.port, timeout_s=0.5)
            watcher = ClusterWatcher(
                client, max_lag_s=30.0,
                backoff_base_s=0.005, backoff_cap_s=0.01,
            )
            try:
                watcher.tick()
                server.set_outage(True)
                for _ in range(5):
                    time.sleep(0.02)
                    watcher.tick()
                assert watcher.resyncs_total == 0
            finally:
                watcher.stop()


# ---------------------------------------------------------------------------
# driver: watchdog + express shed
# ---------------------------------------------------------------------------


class TestWatchdogAndShed:
    def test_round_deadline_watchdog_traces_misses(self, tmp_path):
        from poseidon_tpu.cli import parse_args, run_loop
        from poseidon_tpu.trace import read_trace

        with FakeApiServer() as server:
            for i in range(3):
                server.add_node(f"n{i}")
            for i in range(6):
                server.add_pod(f"p{i}")
            trace_path = str(tmp_path / "trace.jsonl")
            args = parse_args([
                f"--k8s_apiserver_port={server.port}",
                "--polling_frequency=20000",
                "--max_rounds=6",
                "--round_deadline_ms=0.0001",   # every round misses
                f"--trace_log={trace_path}",
            ])
            assert run_loop(args) == 0
        misses = [
            e for e in read_trace(trace_path)
            if e.event == "ROUND_DEADLINE_MISS"
        ]
        assert len(misses) >= 2
        assert misses[-1].detail["consecutive"] >= 2

    def test_express_shed_on_deep_queue(self):
        with FakeApiServer() as server:
            server.add_node("n0", pods=200)
            client = K8sApiClient(port=server.port, timeout_s=1.0)
            watcher = ClusterWatcher(client, max_lag_s=30.0)
            try:
                watcher.tick()  # seed
                for i in range(40):
                    server.add_pod(f"burst-{i:03d}")
                assert watcher.wait_caught_up(server.current_rv())
                ev = watcher.express_poll(
                    0.2, max_events=16, shed_queue=8,
                )
                assert ev.shed and ev.needs_tick
                assert ev.pod_events == []
                # nothing lost: the tick path drains the whole burst
                delta = watcher.tick()
                assert len(delta.pod_events) == 40
            finally:
                watcher.stop()


# ---------------------------------------------------------------------------
# the seeded scenarios (the acceptance ladder, single-seed fast pass)
# ---------------------------------------------------------------------------


class TestScenarios:
    def test_node_storm_survival(self, tmp_path):
        sc = scenario_node_storm()
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        rep = check_invariants(
            run, expect_guard=True, guard_release_rounds=5,
        )
        rep.assert_ok()
        # the drain was STAGED: no round admitted more than the budget
        admits = [
            r.get("requeue_admitted", 0) for r in run.stats
        ]
        assert max(admits) <= 12
        assert sum(admits) >= 12  # a real multi-wave drain happened

    def test_apiserver_outage_survival(self, tmp_path):
        sc = scenario_apiserver_outage()
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        rep = check_invariants(run)
        rep.assert_ok()
        phases = [
            (e.detail or {}).get("phase")
            for e in run.trace_events if e.event == "OUTAGE"
        ]
        assert phases == ["begin", "end"]
        # the outage did NOT inflate bind_failures round after round
        # (the aging-distortion satellite): unreachable POSTs parked
        assert sum(r.get("bind_failures", 0) for r in run.stats) == 0
        # ...and the outbox really was exercised
        assert any(
            r.get("outbox_pending", 0) > 0 for r in run.stats
        )

    def test_writes_down_outage_does_not_flap(self, tmp_path):
        # the reads-OK/writes-down shape (etcd write-quorum loss):
        # polls succeed the whole time, only POSTs fail. A successful
        # READ must not clear the declared outage while actuations
        # are still parked — regression for the episode-per-round
        # flapping a naive read-success clear would produce
        from poseidon_tpu.chaos.scenarios import (
            ChaosScenario,
            FaultAction,
        )

        sc = ChaosScenario(
            name="writes_down", seed=7,
            actions=(
                FaultAction(1, "outage_begin", {"writes_only": True}),
                FaultAction(12, "outage_end"),
            ),
            rounds=60, fault_clear_round=12, recover_within=47,
            nodes=8, pods=24,
        )
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        check_invariants(run).assert_ok()
        phases = [
            (e.detail or {}).get("phase")
            for e in run.trace_events if e.event == "OUTAGE"
        ]
        assert phases == ["begin", "end"], (
            f"outage flapped despite healthy reads: {phases}"
        )

    def test_overload_burst_survival(self, tmp_path):
        sc = scenario_overload_burst()
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        rep = check_invariants(run)
        rep.assert_ok()
        # the burst was absorbed by the tick path in ONE solve round
        placed = max(r.get("pods_placed", 0) for r in run.stats)
        assert placed >= 150

    @pytest.mark.parametrize("name", [
        "node_storm", "apiserver_outage", "overload_burst",
    ])
    def test_rerun_compiles_nothing(self, tmp_path, name):
        """A second run of a seeded scenario reproduces the first's
        shapes exactly, so recovery rides the warm compiled chain: the
        rerun holds the invariants again with zero compiles."""
        from poseidon_tpu.guards import CompileCounter

        make, checks = {
            "node_storm": (
                scenario_node_storm,
                dict(expect_guard=True, guard_release_rounds=5),
            ),
            "apiserver_outage": (scenario_apiserver_outage, {}),
            "overload_burst": (scenario_overload_burst, {}),
        }[name]
        check_invariants(
            run_daemon_scenario(make(), str(tmp_path), polling_ms=25.0),
            **checks,
        ).assert_ok()
        counter = CompileCounter()
        with counter:
            run = run_daemon_scenario(
                make(), str(tmp_path), polling_ms=25.0
            )
        check_invariants(run, **checks).assert_ok()
        if not counter.supported:
            pytest.skip("jax.monitoring not available")
        assert counter.count == 0, (
            f"{counter.count} recompile(s) rerunning {name}"
        )


@pytest.mark.slow
class TestScenarioFuzz:
    """The same invariants across seeds — a failed seed reproduces
    exactly (the orchestrator is schedule+seed deterministic)."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_node_storm_seeds(self, tmp_path, seed):
        sc = scenario_node_storm(seed=seed)
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        check_invariants(
            run, expect_guard=True, guard_release_rounds=5,
        ).assert_ok()

    @pytest.mark.parametrize("seed", [4, 5])
    def test_outage_seeds(self, tmp_path, seed):
        sc = scenario_apiserver_outage(seed=seed)
        run = run_daemon_scenario(sc, str(tmp_path), polling_ms=25.0)
        check_invariants(run).assert_ok()
