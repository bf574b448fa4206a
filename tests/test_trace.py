"""Trace stream: event emission + the read_trace round-order reader,
forward compatibility, ring-buffer semantics, and the PTA005 runtime
vocabulary guard."""

import json

import pytest

from poseidon_tpu.trace import (
    EVENT_TYPES,
    TraceEvent,
    TraceGenerator,
    read_trace,
)


class TestReadTrace:
    def test_orders_by_round_stable_within_round(self, tmp_path):
        """Pipelined rounds interleave round N's SCHEDULE/ROUND with
        round N+1's SUBMITs in file order; read_trace restores round
        order while keeping file order within a round."""
        path = tmp_path / "trace.jsonl"
        clock = iter(range(100))
        with open(path, "w") as fh:
            gen = TraceGenerator(sink=fh, clock_us=lambda: next(clock))
            gen.emit("SUBMIT", task="p0", round_num=1)
            gen.emit("SUBMIT", task="p1", round_num=2)  # interleaved
            gen.emit("SCHEDULE", task="p0", machine="m0", round_num=1)
            gen.emit("ROUND", round_num=1, detail={"cost": 3})
            gen.emit("MIGRATE", task="q0", machine="m1", round_num=2,
                     detail={"from": "m0"})
            gen.emit("ROUND", round_num=2)
            gen.flush()

        events = list(read_trace(str(path)))
        assert [e.round_num for e in events] == [1, 1, 1, 2, 2, 2]
        assert [e.event for e in events] == [
            "SUBMIT", "SCHEDULE", "ROUND", "SUBMIT", "MIGRATE", "ROUND",
        ]
        assert isinstance(events[0], TraceEvent)
        assert events[4].detail == {"from": "m0"}
        # stability: round 1's events kept their file order
        assert events[1].task == "p0" and events[1].machine == "m0"

    def test_skips_blank_lines(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        ev = {"timestamp_us": 1, "event": "SUBMIT", "task": "p",
              "machine": "", "round_num": 3, "detail": None}
        path.write_text(json.dumps(ev) + "\n\n" + json.dumps(ev) + "\n")
        assert len(list(read_trace(str(path)))) == 2

    def test_forward_compat_drops_unknown_fields(self, tmp_path, caplog):
        """A trace written by a NEWER version (extra per-event fields)
        must read, not TypeError — unknown keys drop with a warning."""
        path = tmp_path / "future.jsonl"
        ev = {"timestamp_us": 1, "event": "SUBMIT", "task": "p",
              "machine": "", "round_num": 1, "detail": {"k": 1},
              "tenant": "acme", "shard": 3}
        path.write_text(json.dumps(ev) + "\n")
        with caplog.at_level("WARNING", logger="poseidon_tpu.trace"):
            events = list(read_trace(str(path)))
        assert len(events) == 1
        assert events[0].task == "p"
        assert events[0].detail == {"k": 1}
        # "tenant" graduated from future-field to known schema (the
        # service lane stamps it); only the still-unknown key drops
        assert events[0].tenant == "acme"
        warning = "\n".join(caplog.messages)
        assert "shard" in warning and "tenant" not in warning

    def test_truncated_final_line_warns_and_drops(
        self, tmp_path, caplog
    ):
        """A crash mid-write (flight-recorder territory: OOM-kill,
        device wedge) tears the FINAL line; the reader drops it with
        one warning instead of raising — torn tails are a normal
        post-mortem artifact."""
        path = tmp_path / "torn.jsonl"
        ev = {"timestamp_us": 1, "event": "SUBMIT", "task": "p",
              "machine": "", "round_num": 1, "detail": None}
        full = json.dumps(ev)
        path.write_text(full + "\n" + full + "\n" + full[: 17])
        with caplog.at_level("WARNING", logger="poseidon_tpu.trace"):
            events = list(read_trace(str(path)))
        assert len(events) == 2
        assert any(
            "truncated final line" in m for m in caplog.messages
        )

    def test_truncated_final_line_after_trailing_blank(
        self, tmp_path, caplog
    ):
        path = tmp_path / "torn2.jsonl"
        ev = json.dumps({"timestamp_us": 1, "event": "SUBMIT",
                         "task": "p", "machine": "", "round_num": 1,
                         "detail": None})
        path.write_text(ev + "\n" + ev[:9] + "\n\n")
        with caplog.at_level("WARNING", logger="poseidon_tpu.trace"):
            events = list(read_trace(str(path)))
        assert len(events) == 1

    def test_mid_file_corruption_still_raises(self, tmp_path):
        """Only the torn TAIL is forgiven; garbage mid-file is real
        corruption and must stay loud."""
        path = tmp_path / "corrupt.jsonl"
        ev = json.dumps({"timestamp_us": 1, "event": "SUBMIT",
                         "task": "p", "machine": "", "round_num": 1,
                         "detail": None})
        path.write_text(ev + "\n{broken\n" + ev + "\n")
        with pytest.raises(json.JSONDecodeError):
            list(read_trace(str(path)))

    def test_forward_compat_no_warning_on_clean_file(
        self, tmp_path, caplog
    ):
        path = tmp_path / "clean.jsonl"
        ev = {"timestamp_us": 1, "event": "SUBMIT", "task": "p",
              "machine": "", "round_num": 1, "detail": None}
        path.write_text(json.dumps(ev) + "\n")
        with caplog.at_level("WARNING", logger="poseidon_tpu.trace"):
            assert len(list(read_trace(str(path)))) == 1
        assert not caplog.messages


class TestRingBuffer:
    def test_sinkless_overflow_drops_oldest(self):
        gen = TraceGenerator(buffer_events=3)
        for i in range(5):
            gen.emit("SUBMIT", task=f"p{i}", round_num=i)
        assert len(gen.events) == 3
        assert [e.task for e in gen.events] == ["p2", "p3", "p4"]

    def test_sinkless_flush_is_noop(self):
        gen = TraceGenerator()
        gen.emit("SUBMIT", task="p0")
        gen.flush()  # must not raise with no sink
        assert len(gen.events) == 1

    def test_sink_writes_and_flush(self, tmp_path):
        """With a sink, events go to the file (not the ring) and
        flush() pushes them through the file buffer."""
        path = tmp_path / "sink.jsonl"
        with open(path, "w") as fh:
            gen = TraceGenerator(sink=fh)
            gen.emit("SUBMIT", task="p0", round_num=1)
            gen.flush()
            # visible on disk BEFORE close: flush really flushed
            on_disk = path.read_text()
            assert json.loads(on_disk.strip())["task"] == "p0"
        assert len(gen.events) == 0  # sink mode: ring stays empty


class TestVocabularyGuard:
    def test_undeclared_event_rejected_at_runtime(self):
        gen = TraceGenerator()
        with pytest.raises(ValueError, match="PTA005"):
            gen.emit("REBALANCE")
        assert len(gen.events) == 0

    def test_span_is_declared(self):
        """The span-fed phase timers travel in the declared ROUND
        record (the profiler trace holds the spans themselves)."""
        import dataclasses

        from poseidon_tpu.bridge import SchedulerStats

        assert "ROUND" in EVENT_TYPES
        gen = TraceGenerator()
        gen.emit("ROUND", round_num=1,
                 detail=dataclasses.asdict(SchedulerStats(round_num=1)))
        detail = gen.events[-1].detail
        for field in ("list_ms", "observe_ms", "build_ms", "dispatch_ms",
                      "upload_ms", "fetch_wait_ms", "finish_ms",
                      "post_ms", "posts", "placed_wait_rounds"):
            assert field in detail

    def test_bridge_emits_migrate_and_preempt_events(self):
        """The rebalancing round's decisions land in the trace
        stream with their machines."""
        from poseidon_tpu.bridge import SchedulerBridge
        from poseidon_tpu.cluster import Machine, Task, TaskPhase

        bridge = SchedulerBridge(
            cost_model="quincy", enable_preemption=True,
            migration_hysteresis=20,
        )
        bridge.observe_nodes([
            Machine(name="m0", max_tasks=2), Machine(name="m1", max_tasks=2),
        ])
        bridge.observe_pods([
            Task(uid="q0", phase=TaskPhase.RUNNING, machine="m0",
                 data_prefs={"m1": 200}),
            Task(uid="q1", phase=TaskPhase.RUNNING, machine="m0"),
            Task(uid="q2", phase=TaskPhase.RUNNING, machine="m0"),
        ])
        r = bridge.run_scheduler()
        assert r.stats.deltas_migrate + r.stats.deltas_preempt >= 1
        kinds = {e.event for e in bridge.trace.events}
        assert "MIGRATE" in kinds or "PREEMPT" in kinds
        for e in bridge.trace.events:
            if e.event == "MIGRATE":
                assert e.detail["from"] and e.machine
            if e.event == "PREEMPT":
                assert e.machine
