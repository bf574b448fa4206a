"""The observability subsystem: metrics registry + exposition format,
health endpoints, scheduler instrument recording, the trace analysis
backend, and the daemon e2e (``--metrics_port`` against the fake
apiserver). Program spans are tests/test_spans.py."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from poseidon_tpu.bridge import SchedulerBridge, SchedulerStats
from poseidon_tpu.cluster import Task
from poseidon_tpu.obs import (
    FlightRecorder,
    HealthState,
    LifecycleTracker,
    MetricsRegistry,
    ObsServer,
    SchedulerMetrics,
    ShadowAuditor,
    SloEngine,
)
from poseidon_tpu.obs.metrics import (
    STORM_RESYNCS,
    _bounded_why,
    resync_reason_label,
)
from poseidon_tpu.obs.report import analyze_trace, render_report
from poseidon_tpu.synth import make_synthetic_cluster
from poseidon_tpu.trace import TraceGenerator

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _get(port, path):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5.0
        )
        return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class TestRegistry:
    def test_counter_gauge_render(self):
        reg = MetricsRegistry()
        c = reg.counter("jobs_total", "help text")
        g = reg.gauge("depth")
        c.inc()
        c.inc(2, queue="fast")
        g.set(7.5)
        text = reg.render()
        assert "# HELP jobs_total help text" in text
        assert "# TYPE jobs_total counter" in text
        assert "jobs_total 1" in text
        assert 'jobs_total{queue="fast"} 2' in text
        assert "# TYPE depth gauge" in text
        assert "depth 7.5" in text

    def test_histogram_cumulative_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0, 50.0, 5000.0):
            h.observe(v)
        text = reg.render()
        assert 'lat_ms_bucket{le="1"} 1' in text
        assert 'lat_ms_bucket{le="10"} 3' in text
        assert 'lat_ms_bucket{le="100"} 4' in text
        assert 'lat_ms_bucket{le="+Inf"} 5' in text
        assert "lat_ms_sum 5060.5" in text
        assert "lat_ms_count 5" in text

    def test_registration_idempotent_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        assert reg.counter("x_total") is c
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_concurrent_recording_is_consistent(self):
        reg = MetricsRegistry()
        c = reg.counter("n_total")

        def work():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert "n_total 4000" in reg.render()


class TestSchedulerMetrics:
    def _stats(self, **kw):
        s = SchedulerStats(round_num=1)
        s.backend = kw.pop("backend", "dense_auction")
        s.lane = kw.pop("lane", "watch")
        s.build_mode = kw.pop("build_mode", "delta")
        s.total_ms = kw.pop("total_ms", 5.0)
        for k, v in kw.items():
            setattr(s, k, v)
        return s

    def test_record_round_families(self):
        m = SchedulerMetrics(MetricsRegistry())
        m.record_round(self._stats(
            pods_total=10, pods_pending=3, deltas_place=3,
            deltas_migrate=1, bind_failures=2,
        ))
        text = m.registry.render()
        assert ('poseidon_rounds_total{backend="dense",lane="watch"} 1'
                in text)
        assert ('poseidon_round_latency_ms_bucket{build_mode="delta",'
                'lane="watch",le="5"} 1' in text)
        assert 'poseidon_deltas_total{kind="migrate"} 1' in text
        assert "poseidon_bind_failures_total 2" in text
        assert 'poseidon_pods{state="total"} 10' in text

    def test_degraded_gauge_sets_and_clears(self):
        m = SchedulerMetrics(MetricsRegistry())
        m.record_round(self._stats(backend="oracle:memory-envelope"))
        assert ('poseidon_degraded{why="memory-envelope"} 1'
                in m.registry.render())
        # an empty (no-solve) round carries no evidence either way
        m.record_round(self._stats(backend="", build_mode=""))
        assert ('poseidon_degraded{why="memory-envelope"} 1'
                in m.registry.render())
        # ANY non-degraded solve clears the flag — deliberate oracle
        # routing included (it is dispatch, not degradation)
        m.record_round(self._stats(backend="oracle:small-instance"))
        assert ('poseidon_degraded{why="memory-envelope"} 0'
                in m.registry.render())
        m.record_round(self._stats(backend="oracle:cost-domain"))
        m.record_round(self._stats(backend="dense_auction"))
        assert ('poseidon_degraded{why="cost-domain"} 0'
                in m.registry.render())

    def test_resync_storm_gauge(self):
        m = SchedulerMetrics(MetricsRegistry())
        m.record_round(self._stats(watch_resyncs=0))
        assert "poseidon_watch_resync_storm 0" in m.registry.render()
        m.record_round(self._stats(watch_resyncs=STORM_RESYNCS))
        assert "poseidon_watch_resync_storm 1" in m.registry.render()

    def test_reason_labels_are_bounded(self):
        assert resync_reason_label("rv 7 expired (HTTP 410)") == "gone"
        assert resync_reason_label(
            "pods: no stream activity for 30s (--watch_max_lag)"
        ) == "stale"
        assert resync_reason_label(
            "pods: unparseable ADDED event: KeyError('uid')"
        ) == "decode"
        assert _bounded_why("4 arrivals > --express_max_batch 2") \
            == "batch-size"
        assert _bounded_why("unconfirmed placements") == "unconfirmed"

    def test_empty_round_keeps_counters_out_of_latency(self):
        """An idle cluster's empty rounds flush window counters but
        must not feed the latency histogram or clobber the last real
        round's cost/phase gauges."""
        m = SchedulerMetrics(MetricsRegistry())
        m.record_round(self._stats(cost=42, solve_ms=3.0))
        m.record_round(self._stats(
            backend="", build_mode="", total_ms=0.001, cost=0,
            solve_ms=0.0, bind_failures=1,
        ))
        text = m.registry.render()
        assert "poseidon_round_latency_ms_count" in text
        assert ('poseidon_round_latency_ms_count{build_mode="delta",'
                'lane="watch"} 1' in text)
        assert 'build_mode=""' not in text  # no empty-round sample
        assert "poseidon_round_cost 42" in text
        assert 'poseidon_round_phase_ms{phase="solve"} 3' in text
        assert "poseidon_bind_failures_total 1" in text  # counters flow
        assert ('poseidon_rounds_total{backend="empty",lane="watch"} 1'
                in text)

    def test_express_batch_recording(self):
        m = SchedulerMetrics(MetricsRegistry())
        m.record_express_batch([2.5, 0.7, 1.1])
        m.record_express_batch([])  # retire-only batch: no placements
        text = m.registry.render()
        assert "poseidon_express_batches_total 2" in text
        assert "poseidon_express_places_total 3" in text
        assert "poseidon_express_e2b_ms_count 3" in text


class TestServer:
    def test_endpoints_and_readyz_latch(self):
        reg = MetricsRegistry()
        reg.counter("poseidon_rounds_total").inc()
        health = HealthState()
        with ObsServer(reg, health, port=0, host="127.0.0.1") as srv:
            assert _get(srv.port, "/healthz")[0] == 200
            code, body = _get(srv.port, "/readyz")
            assert code == 503
            assert "seed LIST" in body and "scheduling round" in body
            # a proven-empty round counts (an idle cluster is the
            # steady state of an operational scheduler) — but only
            # once seeded
            health.mark_round("")
            assert _get(srv.port, "/readyz")[0] == 503
            health.mark_seeded()
            health.mark_round("")
            assert _get(srv.port, "/readyz")[0] == 200
            code, body = _get(srv.port, "/metrics")
            assert code == 200
            assert "poseidon_rounds_total 1" in body
            assert _get(srv.port, "/nope")[0] == 404

    def test_ready_gauge_flips_with_the_latch(self):
        """HealthState owns the poseidon_ready gauge: both flip under
        one lock, so a scraper that saw /readyz 200 can never read the
        gauge at 0."""
        reg = MetricsRegistry()
        metrics = SchedulerMetrics(reg)
        health = HealthState(ready_gauge=metrics.ready)
        assert "poseidon_ready 0" in reg.render()
        health.mark_seeded()
        assert "poseidon_ready 0" in reg.render()
        # a proven-empty round after seeding flips both together
        health.mark_round("")
        assert health.ready
        assert "poseidon_ready 1" in reg.render()

    def test_build_info_gauge_and_healthz_echo(self):
        """poseidon_build_info scrapes with the deploy-identity labels
        and /healthz echoes the same dict as JSON."""
        import jax

        from poseidon_tpu.obs import build_info

        import poseidon_tpu

        reg = MetricsRegistry()
        metrics = SchedulerMetrics(reg)
        info = build_info(mesh_width=4)
        assert info["version"] == poseidon_tpu.__version__
        assert info["jax"] == jax.__version__
        metrics.set_build_info(info)
        text = reg.render()
        assert "# TYPE poseidon_build_info gauge" in text
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("poseidon_build_info{")
        )
        assert f'version="{poseidon_tpu.__version__}"' in line
        assert 'mesh_width="4"' in line
        assert line.endswith(" 1")
        with ObsServer(reg, HealthState(), port=0, host="127.0.0.1",
                       build=info) as srv:
            code, body = _get(srv.port, "/healthz")
            assert code == 200
            doc = json.loads(body)
            assert doc["status"] == "ok"
            assert doc["build"]["jax"] == jax.__version__
            assert doc["build"]["backend"] == info["backend"]
            # /metrics carries the family alongside
            assert "poseidon_build_info" in _get(
                srv.port, "/metrics"
            )[1]

    def test_scrape_concurrent_with_recording(self):
        reg = MetricsRegistry()
        c = reg.counter("poseidon_rounds_total")
        with ObsServer(reg, HealthState(), port=0,
                       host="127.0.0.1") as srv:
            stop = threading.Event()

            def record():
                while not stop.is_set():
                    c.inc()

            t = threading.Thread(target=record, daemon=True)
            t.start()
            try:
                for _ in range(20):
                    code, body = _get(srv.port, "/metrics")
                    assert code == 200
                    assert "poseidon_rounds_total" in body
            finally:
                stop.set()
                t.join(timeout=2.0)


class TestBridgeIntegration:
    def _run_rounds(self, *, metrics=None, rounds=2):
        cluster = make_synthetic_cluster(
            20, 60, seed=5, prefs_per_task=2
        )
        trace = TraceGenerator()
        bridge = SchedulerBridge(
            cost_model="quincy", small_to_oracle=False, trace=trace,
            metrics=metrics,
        )
        bridge.lane = "poll"
        bridge.observe_nodes(list(cluster.machines))
        bridge.observe_pods(list(cluster.tasks))
        for _ in range(rounds):
            res = bridge.run_scheduler()
            for uid, m in res.bindings.items():
                bridge.confirm_binding(uid, m)
        return bridge, trace, res

    def test_round_metrics_from_live_bridge(self):
        m = SchedulerMetrics(MetricsRegistry())
        bridge, _trace, res = self._run_rounds(metrics=m)
        assert res.stats.lane == "poll"
        text = m.registry.render()
        assert ('poseidon_rounds_total{backend="dense",lane="poll"} 2'
                in text)
        assert "poseidon_round_latency_ms_count" in text
        assert 'poseidon_solver_fetches_total{lane="round"} 2' in text
        assert "poseidon_solver_warm 1" in text

    def test_express_place_carries_e2b_detail(self):
        m = SchedulerMetrics(MetricsRegistry())
        cluster = make_synthetic_cluster(
            20, 90, seed=3, prefs_per_task=2
        )
        trace = TraceGenerator()
        bridge = SchedulerBridge(
            cost_model="quincy", small_to_oracle=False,
            express_lane=True, trace=trace, metrics=m,
        )
        bridge.observe_nodes(list(cluster.machines))
        bridge.observe_pods(list(cluster.tasks))
        res = bridge.run_scheduler()
        for uid, mach in res.bindings.items():
            bridge.confirm_binding(uid, mach)
        pod = Task(uid="xp-0", cpu_request=0.1, memory_request_kb=64,
                   data_prefs={cluster.machines[0].name: 400})
        r = bridge.express_batch([("ADDED", pod)])
        assert r is not None and r.bindings
        places = [e for e in trace.events if e.event == "EXPRESS_PLACE"]
        assert places and places[0].detail["e2b_ms"] > 0
        # the batch's own phase timings ride the result
        assert set(r.timings) >= {"prep_ms", "upload_ms", "solve_ms"}
        # no receipt stamp given: the placement's e2b is the batch's
        assert places[0].detail["e2b_ms"] == pytest.approx(
            r.latency_ms, abs=1e-3
        )
        text = m.registry.render()
        assert "poseidon_express_batches_total 1" in text
        assert "poseidon_express_e2b_ms_count 1" in text
        assert 'poseidon_solver_fetches_total{lane="express"} 1' in text


class TestReportAndChrome:
    def _trace_file(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        cluster = make_synthetic_cluster(
            20, 60, seed=5, prefs_per_task=2
        )
        with open(path, "w") as fh:
            trace = TraceGenerator(sink=fh)
            bridge = SchedulerBridge(
                cost_model="quincy", small_to_oracle=False,
                trace=trace,
            )
            bridge.lane = "watch+pipelined"
            bridge.observe_nodes(list(cluster.machines))
            bridge.observe_pods(list(cluster.tasks))
            for _ in range(2):
                res = bridge.run_scheduler()
                for uid, m in res.bindings.items():
                    bridge.confirm_binding(uid, m)
            trace.flush()
        return str(path)

    def test_analyze_trace(self, tmp_path):
        data = analyze_trace(self._trace_file(tmp_path))
        assert data["rounds"] == 2
        key = "watch+pipelined/full"
        assert key in data["round_latency_ms"]
        assert data["round_latency_ms"][key]["n"] >= 1
        assert data["backend_latency_ms"]["dense"]["p50"] > 0
        assert data["churn"]["totals"]["SCHEDULE"] > 0
        assert "span_phase_p50_ms" not in data
        text = render_report(data)
        assert "round latency" in text and "placement churn" in text

    def test_cli_report_and_chrome(self, tmp_path, capsys):
        from poseidon_tpu.trace import main as trace_main

        path = self._trace_file(tmp_path)
        assert trace_main(["report", path]) == 0
        out = capsys.readouterr().out
        assert "poseidon-tpu trace report" in out
        assert trace_main(["report", path, "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["rounds"] == 2
        # the Chrome-trace export is gone: the profiler's own trace
        # (GET /debug/profile) is the timeline
        with pytest.raises(SystemExit):
            trace_main(["chrome", path])

    def test_empty_rounds_still_carry_window_counters(self, tmp_path):
        """The bridge flushes the window's express/bind-failure
        counters into empty rounds (an express window that bound
        everything ends in one) — the report must count them, not
        skip them with the latency grouping."""
        from poseidon_tpu.trace import TraceEvent

        path = tmp_path / "empty.jsonl"
        with open(path, "w") as fh:
            for ev in (
                TraceEvent(
                    timestamp_us=1, event="ROUND", round_num=1,
                    detail={"backend": "dense_auction", "lane": "express",
                            "build_mode": "delta", "total_ms": 5.0},
                ),
                TraceEvent(
                    timestamp_us=2, event="ROUND", round_num=2,
                    detail={"backend": "", "express_batches": 3,
                            "express_places": 4, "bind_failures": 1,
                            "deltas_deferred": 2},
                ),
            ):
                fh.write(json.dumps(ev.__dict__) + "\n")
        data = analyze_trace(str(path))
        assert data["express"]["batches"] == 3
        assert data["express"]["places"] == 4
        assert data["churn"]["bind_failures"] == 1
        assert data["churn"]["deltas_deferred"] == 2
        # the empty round still does not contribute a latency sample
        assert data["nonempty_rounds"] == 1


class TestTenantReport:
    def _two_tenant_trace(self, tmp_path):
        """A fake-serve-shaped trace: two tenant sessions writing into
        ONE sink, each generator stamped with its tenant id (exactly
        what service.add_tenant does)."""
        path = tmp_path / "serve.jsonl"
        with open(path, "w") as fh:
            for tid, n_rounds, total in (
                ("tenant-0", 3, 5.0), ("tenant-1", 2, 50.0),
            ):
                gen = TraceGenerator(sink=fh, tenant=tid)
                for r in range(1, n_rounds + 1):
                    gen.emit(
                        "SCHEDULE", task=f"{tid}-pod-{r}",
                        machine=f"{tid}-n0", round_num=r,
                    )
                    gen.emit("ROUND", round_num=r, detail={
                        "backend": "dense_auction",
                        "lane": "service", "build_mode": "delta",
                        "total_ms": total,
                    })
                gen.flush()
        return str(path)

    def test_service_sessions_stamp_tenant(self):
        from poseidon_tpu.service.service import SchedulingService

        svc = SchedulingService()
        s = svc.add_tenant("acme")
        assert s.trace.tenant == "acme"
        s.bridge.trace.emit("ROUND", round_num=1)
        assert s.trace.events[-1].tenant == "acme"

    def test_tenant_filter_isolates_sessions(self, tmp_path):
        path = self._two_tenant_trace(tmp_path)
        whole = analyze_trace(path)
        t0 = analyze_trace(path, tenant="tenant-0")
        t1 = analyze_trace(path, tenant="tenant-1")
        assert whole["rounds"] == 5
        assert t0["rounds"] == 3 and t1["rounds"] == 2
        assert t0["churn"]["totals"]["SCHEDULE"] == 3
        assert t1["churn"]["totals"]["SCHEDULE"] == 2
        # latency percentiles come from ONLY the tenant's own rounds
        assert t0["round_latency_ms"]["service/delta"]["p50"] == 5.0
        assert t1["round_latency_ms"]["service/delta"]["p50"] == 50.0
        assert analyze_trace(path, tenant="ghost")["rounds"] == 0

    def test_report_cli_tenant_flag(self, tmp_path, capsys):
        from poseidon_tpu.trace import main as trace_main

        path = self._two_tenant_trace(tmp_path)
        rc = trace_main(["report", path, "--tenant", "tenant-1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "tenant: tenant-1" in out
        assert "rounds: 2" in out


class TestZeroRecompileUnderDrain:
    @pytest.mark.parametrize("surface", [
        "none", "metrics", "flight_recorder", "checkpoint", "observatory",
    ])
    def test_draining_pool_stays_zero_recompile(self, surface, tmp_path):
        """Regression for the three recompile sources a draining pool
        once exposed: a pending pool that DRAINS across padding-bucket
        boundaries (cost-input shapes), packs its free seats (the
        ``smax`` static), and narrows its pref width (the ``n_prefs``
        static) must stay at zero steady-state recompiles — the
        solver's grow-only floors now cover all three axes, not just
        the topology padding.

        Every host-side surface the daemon can attach to a round reads
        host values only, so the budget holds with it on: the metrics
        + trace ring, the flight recorder capturing every round, a
        checkpoint captured every round (and the warm restore's first
        round: a delta build whose restored floors reproduce the
        compiled shapes), and the observatory (per-pod lifecycle, the
        shadow audit sampled every other round, the SLO engine)."""
        from poseidon_tpu.guards import CompileCounter
        from poseidon_tpu.ha import restore_bridge
        from poseidon_tpu.ha.checkpoint import capture_snapshot

        kw: dict = {}
        slo = auditor = None
        if surface == "metrics":
            kw = dict(metrics=SchedulerMetrics(MetricsRegistry()),
                      trace=TraceGenerator())
        elif surface == "flight_recorder":
            kw = dict(flightrec=FlightRecorder(str(tmp_path), rounds=4))
        elif surface == "observatory":
            metrics = SchedulerMetrics(MetricsRegistry())
            trace = TraceGenerator()
            auditor = ShadowAuditor(
                metrics=metrics, sample_every=2, background=False
            )
            auditor.prewarm(tasks=224, machines=16)
            kw = dict(metrics=metrics, trace=trace, auditor=auditor,
                      lifecycle=LifecycleTracker(metrics))
            slo = SloEngine(
                ["e2c_p99_ms < 60000 by lane=tick", "regret == 0"],
                metrics=metrics, trace=trace,
            )

        # oversubscribed on purpose: 160 seats, 224 pods — a standing
        # unscheduled pool of ~64 that the churn below drains a few
        # pods per round, so the pending count crosses padding-bucket
        # boundaries INSIDE the counted window (pre-fix, each crossing
        # recompiled the fused chain)
        cluster = make_synthetic_cluster(
            16, 224, seed=7, prefs_per_task=2
        )
        bridge = SchedulerBridge(
            cost_model="quincy", small_to_oracle=False, **kw
        )
        bridge.observe_nodes(list(cluster.machines))
        bridge.observe_pods(list(cluster.tasks))
        res = bridge.run_scheduler()
        for uid, m in res.bindings.items():
            bridge.confirm_binding(uid, m)
        running = list(res.bindings)
        snaps = []
        seq = 0

        def churn_round():
            # complete 6 running pods, arrive 2 single-pref pods: the
            # standing pool drains ~4/round (shrinking cost-input
            # shapes), freed seats churn (the smax static), and the
            # arrival mix narrows the pref width — the three pre-fix
            # recompile triggers
            nonlocal seq
            freed = ""
            for _ in range(6):
                done = running.pop(0)
                freed = bridge.pod_to_machine[done]
                bridge.observe_pod_event(
                    "DELETED", bridge.tasks[done]
                )
            for _ in range(2):
                bridge.observe_pod_event("ADDED", Task(
                    uid=f"dr-{seq}", cpu_request=0.1,
                    memory_request_kb=64, data_prefs={freed: 400},
                ))
                seq += 1
            r = bridge.run_scheduler()
            for uid, m in r.bindings.items():
                bridge.confirm_binding(uid, m)
                running.append(uid)
            if surface == "checkpoint":
                snaps.append(capture_snapshot(bridge))
            if auditor is not None:
                auditor.run_pending()
            if slo is not None:
                slo.evaluate(r.stats.round_num)

        for _ in range(2):  # warm both chain variants (and the audit)
            churn_round()
        counter = CompileCounter()
        with counter:
            for _ in range(10):
                churn_round()
        if surface == "checkpoint":
            # the restore rebuilds host state outside the count; the
            # restored bridge's first round is what must reuse the
            # compiled shapes
            warm = SchedulerBridge(
                cost_model="quincy", small_to_oracle=False
            )
            restore_bridge(warm, snaps[-1])
            with counter:
                warm.observe_pod_event("ADDED", Task(
                    uid="restored-arrival", cpu_request=0.1,
                    memory_request_kb=64,
                ))
                r = warm.run_scheduler()
            assert r.stats.build_mode == "delta"
            assert r.stats.backend == "dense_auction"
        if not counter.supported:
            pytest.skip("jax.monitoring not available")
        if auditor is not None:
            assert auditor.completed >= 5 and not auditor.failures
        assert counter.count == 0, (
            f"{counter.count} recompile(s) during a draining-pool "
            f"steady state with the {surface} surface on"
        )


class TestDaemonE2E:
    def test_metrics_endpoint_live_daemon(self, tmp_path):
        """The acceptance scrape: a live fake-apiserver run exposes the
        required metric families, /readyz flips only after the first
        certified round, and the trace carries the ROUND records."""
        import socket

        from poseidon_tpu.apiclient import FakeApiServer
        from poseidon_tpu.cli import parse_args, run_loop

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        trace_path = tmp_path / "daemon-trace.jsonl"
        seen = {}

        def scrape():
            # poll /readyz until it flips, then scrape /metrics while
            # the daemon is still serving
            import time as _time

            deadline = _time.monotonic() + 30.0
            while _time.monotonic() < deadline:
                try:
                    code, _ = _get(port, "/readyz")
                except OSError:
                    _time.sleep(0.05)
                    continue
                seen.setdefault("first_readyz", code)
                if code == 200:
                    seen["ready"] = True
                    seen["healthz"] = _get(port, "/healthz")[0]
                    seen["metrics"] = _get(port, "/metrics")[1]
                    return
                _time.sleep(0.05)

        t = threading.Thread(target=scrape, daemon=True)
        with FakeApiServer() as server:
            for i in range(4):
                server.add_node(f"n{i}", cpu="8", memory="16Gi",
                                pods=12)
            for j in range(24):
                server.add_pod(f"pod-{j:02d}", cpu="250m",
                               memory="256Mi", job=f"job{j // 6}")
            t.start()
            rc = run_loop(parse_args([
                "--k8s_apiserver_host=127.0.0.1",
                f"--k8s_apiserver_port={server.port}",
                "--watch=true",
                f"--metrics_port={port}",
                f"--trace_log={trace_path}",
                "--flow_scheduling_cost_model=quincy",
                "--polling_frequency=50000",
                "--max_rounds=8",
            ]))
            t.join(timeout=30.0)
        assert rc == 0
        assert seen.get("ready"), f"readyz never flipped: {seen}"
        assert seen["healthz"] == 200
        text = seen["metrics"]
        for family in (
            "poseidon_round_latency_ms_bucket",
            "poseidon_rounds_total",
            "poseidon_degrades_total",
            "poseidon_watch_resyncs_total",
            "poseidon_bind_failures_total",
            "poseidon_express_e2b_ms",
            "poseidon_ready 1",
        ):
            assert family in text, f"{family} missing from /metrics"
        from poseidon_tpu.trace import read_trace

        events = list(read_trace(str(trace_path)))
        kinds = {e.event for e in events}
        assert "ROUND" in kinds
        lanes = {e.detail.get("lane") for e in events
                 if e.event == "ROUND" and e.detail
                 and e.detail.get("backend")}
        assert "watch" in lanes
