"""Replay fidelity: dumps re-run offline bit-identically (warm chains,
express windows, aggregated and sharded rounds), and doctored dumps
report divergence instead of crashing."""

import json

import numpy as np
import pytest

from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.cluster import Task
from poseidon_tpu.obs import replay as replay_mod
from poseidon_tpu.obs.flightrec import FlightRecorder, load_dump
from poseidon_tpu.obs.replay import render_report, replay_dump
from poseidon_tpu.synth import make_synthetic_cluster


def _record_session(tmp_path, *, rounds=3, churn=4, seed=0,
                    machines=12, pods=50, model="quincy",
                    **bridge_kw):
    fr = FlightRecorder(str(tmp_path / "fr"), rounds=8)
    bridge = SchedulerBridge(
        cost_model=model, small_to_oracle=False, flightrec=fr,
        **bridge_kw,
    )
    cluster = make_synthetic_cluster(
        machines, pods, seed=seed, prefs_per_task=2
    )
    bridge.observe_nodes(list(cluster.machines))
    bridge.observe_pods(list(cluster.tasks))
    running = []
    seq = 0
    for i in range(rounds):
        if i:
            for _ in range(churn):
                if not running:
                    break
                done = running.pop(0)
                freed = bridge.pod_to_machine[done]
                bridge.observe_pod_event(
                    "DELETED", bridge.tasks[done]
                )
                bridge.observe_pod_event("ADDED", Task(
                    uid=f"x-{seq}", cpu_request=0.1,
                    memory_request_kb=128, data_prefs={freed: 400},
                ))
                seq += 1
        res = bridge.run_scheduler()
        for uid, m in res.bindings.items():
            bridge.confirm_binding(uid, m)
            running.append(uid)
    return bridge


def _assert_identical(path):
    dump = load_dump(path)
    report = replay_dump(dump)
    assert report["identical"] is True, render_report(report)
    assert report["compared"] >= 1
    return report


class TestRoundReplay:
    def test_warm_churned_rounds_bit_identical(self, tmp_path):
        """Every recorded round (cold seed + warm churned) replays to
        the exact recorded assignment and cost — the warm seed riding
        the round's own fetch makes each round independently
        reproducible."""
        bridge = _record_session(tmp_path, rounds=4)
        path = bridge.flight_dump("manual")
        report = _assert_identical(path)
        assert report["compared"] == 4

    def test_preemption_rounds_bit_identical(self, tmp_path):
        bridge = _record_session(
            tmp_path, rounds=3, enable_preemption=True,
            migration_hysteresis=5,
        )
        _assert_identical(bridge.flight_dump("manual"))

    def test_aggregated_round_bit_identical(self, tmp_path):
        bridge = _record_session(
            tmp_path, rounds=3, model="octopus",
            aggregate_classes=True, topk_prefs=1,
            machines=16, pods=60,
        )
        _assert_identical(bridge.flight_dump("manual"))

    @pytest.mark.parametrize("mesh", [1, 8])
    def test_sharded_round_bit_identical(self, tmp_path, mesh):
        bridge = _record_session(
            tmp_path, rounds=2, mesh_width=mesh,
        )
        _assert_identical(bridge.flight_dump("manual"))

    def test_oracle_routed_round_replays(self, tmp_path):
        """A small-instance round (deliberate oracle routing) replays
        through the same routing to the same assignment."""
        fr = FlightRecorder(str(tmp_path / "fr"), rounds=2)
        bridge = SchedulerBridge(
            cost_model="quincy", small_to_oracle=True, flightrec=fr,
        )
        cluster = make_synthetic_cluster(6, 30, seed=4)
        bridge.observe_nodes(list(cluster.machines))
        bridge.observe_pods(list(cluster.tasks))
        res = bridge.run_scheduler()
        assert res.stats.backend.startswith("oracle:")
        _assert_identical(bridge.flight_dump("manual"))


class TestExpressReplay:
    def _express_session(self, tmp_path):
        fr = FlightRecorder(str(tmp_path / "fr"), rounds=6)
        bridge = SchedulerBridge(
            cost_model="quincy", small_to_oracle=False, flightrec=fr,
            express_lane=True,
        )
        cluster = make_synthetic_cluster(
            12, 50, seed=1, prefs_per_task=2
        )
        bridge.observe_nodes(list(cluster.machines))
        bridge.observe_pods(list(cluster.tasks))
        res = bridge.run_scheduler()
        for uid, m in res.bindings.items():
            bridge.confirm_binding(uid, m)
        for k in range(3):
            er = bridge.express_batch([("ADDED", Task(
                uid=f"late-{k}", cpu_request=0.1,
                memory_request_kb=128,
            ))])
            assert er is not None and er.bindings
            for uid, m in er.bindings.items():
                bridge.confirm_binding(uid, m)
        # the correction round starts cold whatever the express batches
        # did to the warm state; the chained replay still runs the
        # express records between the two rounds
        bridge.run_scheduler()
        return bridge, fr

    def test_express_window_and_correction_round(self, tmp_path):
        bridge, fr = self._express_session(tmp_path)
        rounds = [r for r in fr.records if r.kind == "round"]
        assert not rounds[-1].warm_used and rounds[-1].warm_seed is None
        express = [r for r in fr.records if r.kind == "express"]
        assert len(express) == 3
        path = bridge.flight_dump("manual")
        report = _assert_identical(path)
        kinds = [r["kind"] for r in report["records"]]
        assert kinds == ["round", "express", "express", "express",
                         "round"]


class TestDivergence:
    def _doctor(self, path, mutate):
        z = dict(np.load(path.replace(".json", ".npz")))
        mutate(z)
        np.savez_compressed(path.replace(".json", ".npz"), **z)

    def test_doctored_assignment_reports_divergence(self, tmp_path):
        bridge = _record_session(tmp_path, rounds=2)
        path = bridge.flight_dump("manual")

        def mutate(z):
            key = sorted(
                k for k in z if k.endswith("result/assignment")
            )[0]
            z[key] = z[key].copy()
            z[key][0] = -1 if z[key][0] >= 0 else 0

        self._doctor(path, mutate)
        report = replay_dump(load_dump(path))
        assert report["identical"] is False
        bad = [r for r in report["records"] if r["ok"] is False]
        assert bad and "assignment" in bad[0]["divergence"]
        # the CLI reports it and exits 1 — never an assert crash
        assert replay_mod.main([path]) == 1

    def test_doctored_input_reports_divergence(self, tmp_path):
        """Doctoring an INPUT (a pref weight) makes the replayed solve
        disagree with the recorded result — divergence, not a crash."""
        bridge = _record_session(tmp_path, rounds=2)
        path = bridge.flight_dump("manual")

        def mutate(z):
            key = sorted(
                k for k in z if k.endswith("meta/arc_weight")
            )[0]
            w = z[key].copy()
            w[w > 0] = w[w > 0] // 2  # halve every locality weight
            z[key] = w

        self._doctor(path, mutate)
        report = replay_dump(load_dump(path))
        assert report["identical"] is False

    def test_truncated_manifest_is_a_load_error(self, tmp_path):
        bridge = _record_session(tmp_path, rounds=2)
        path = bridge.flight_dump("manual")
        raw = open(path).read()
        open(path, "w").write(raw[: len(raw) // 2])
        assert replay_mod.main([path]) == 2


class TestReplayCli:
    def test_main_explain(self, tmp_path, capsys):
        bridge = _record_session(tmp_path, rounds=2)
        # a uid decided in the LAST round (the --explain target is the
        # replayed final round; earlier rounds' placements are RUNNING
        # by then and out of the place-only graph)
        uid = next(
            u for r, k, u, _d in reversed(bridge.decision_log)
            if k == "PLACE" and r == bridge.round_num
        )
        path = bridge.flight_dump("manual")
        rc = replay_mod.main([path, "--explain", uid])
        out = capsys.readouterr().out
        assert rc == 0
        assert "BIT-IDENTICAL" in out
        assert "sums exactly" in out
        assert f"explain {uid}" in out

    def test_main_explain_unknown_uid_is_readable(
        self, tmp_path, capsys
    ):
        """A typo'd --explain uid yields a readable line in the
        report, never a traceback after the replay already ran."""
        bridge = _record_session(tmp_path, rounds=2)
        path = bridge.flight_dump("manual")
        rc = replay_mod.main([path, "--explain", "no-such-pod"])
        out = capsys.readouterr().out
        assert rc == 0  # the replay itself was bit-identical
        assert "BIT-IDENTICAL" in out
        assert "no-such-pod" in out and "not a task" in out

    def test_main_json(self, tmp_path, capsys):
        bridge = _record_session(tmp_path, rounds=2)
        path = bridge.flight_dump("manual")
        rc = replay_mod.main([path, "--json"])
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["identical"] is True
        assert data["compared"] == 2
