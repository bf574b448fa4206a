"""Trace stream: event emission + the read_trace round-order reader,
forward compatibility, ring-buffer semantics, and the PTA005 runtime
vocabulary guard."""

import dataclasses
import io
import json
import logging

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


def _fields(line: str) -> dict:
    """A trace line's fields but the wall-clock stamp."""
    doc = json.loads(line)
    del doc["timestamp_us"]
    return doc


ROWS = [
    ("default/p0", "m0", {"cost": 12, "margin": 3}),
    ("default/p1", "m1", {"cost": 7, "margin": 0.5}),
    ("ns/pé-\"2\"", "", None),
]


def _asdict_line(ts, event, task, machine, round_num, detail, tenant=""):
    """A line as the per-event writer serialised it: the dataclass
    through ``dataclasses.asdict``."""
    return json.dumps(dataclasses.asdict(TraceEvent(
        ts, event, task, machine, round_num, detail, tenant))) + "\n"


class TestEmitMany:
    """``emit_many`` writes, in one sink write, the lines the per-event
    writer wrote one event at a time; ``emit`` is a batch of one."""

    @pytest.mark.parametrize("tenant", ["", "acme"])
    @pytest.mark.parametrize("batched", [True, False])
    def test_lines_equal_the_per_event_format(self, tmp_path, tenant,
                                              batched):
        path = tmp_path / "t.jsonl"
        with open(path, "w") as fh:
            clock = iter(range(100, 200))
            gen = TraceGenerator(sink=fh, clock_us=lambda: next(clock),
                                 tenant=tenant)
            if batched:
                gen.emit_many("SCHEDULE", ROWS, 4)
            else:
                for task, machine, detail in ROWS:
                    gen.emit("SCHEDULE", task=task, machine=machine,
                             round_num=4, detail=detail)
        # same clock readings: byte-identical, key order included
        assert path.read_text() == "".join(
            _asdict_line(100 + i, "SCHEDULE", task, machine, 4, detail,
                         tenant)
            for i, (task, machine, detail) in enumerate(ROWS)
        )

    def test_one_sink_write_and_a_stamp_per_event(self):
        writes: list[str] = []

        class Sink:
            def write(self, text):
                writes.append(text)

        stamps = iter(range(10))
        gen = TraceGenerator(sink=Sink(), clock_us=lambda: next(stamps))
        gen.emit_many("SUBMIT", ROWS, 2)
        assert len(writes) == 1
        lines = writes[0].splitlines()
        assert [json.loads(x)["timestamp_us"] for x in lines] == [0, 1, 2]
        assert writes[0].endswith("\n")
        gen.emit_many("SUBMIT", [], 2)  # an empty batch writes nothing
        assert len(writes) == 1

    def test_sinkless_batch_fills_the_ring_with_events(self):
        stamps = iter(range(10))
        gen = TraceGenerator(clock_us=lambda: next(stamps), tenant="t")
        gen.emit_many("SCHEDULE", ROWS, 1)
        assert list(gen.events) == [
            TraceEvent(i, "SCHEDULE", task, machine, 1, detail, "t")
            for i, (task, machine, detail) in enumerate(ROWS)
        ]

    @pytest.mark.parametrize("before,batch", [(3, 4), (0, 7), (5, 1), (1, 1)])
    def test_ring_overflow_counted_exactly(self, caplog, before, batch):
        """The sinkless ring keeps its maxlen and counts every event a
        batch pushes out, warning once."""
        gen = TraceGenerator(buffer_events=5)
        for i in range(before):
            gen.emit("SUBMIT", task=f"p{i}")
        rows = [(f"q{i}", "", None) for i in range(batch)]
        with caplog.at_level("WARNING", logger="poseidon_tpu.trace"):
            gen.emit_many("SUBMIT", rows, 0)
            gen.emit_many("SUBMIT", rows, 0)
        tasks = [f"p{i}" for i in range(before)] + [t for t, _, _ in rows] * 2
        assert gen.events.maxlen == 5
        assert [e.task for e in gen.events] == tasks[-5:]
        assert gen.dropped_total == max(0, len(tasks) - 5)
        warned = [m for m in caplog.messages if "ring full" in m]
        assert len(warned) == (1 if gen.dropped_total else 0)

    @pytest.mark.parametrize("rows", [ROWS, []])
    def test_undeclared_event_in_a_batch_raises(self, rows):
        gen = TraceGenerator()
        with pytest.raises(ValueError, match="PTA005"):
            gen.emit_many("REBALANCE", rows, 1)
        assert len(gen.events) == 0

    def test_report_over_batched_equals_per_event(self, tmp_path, capsys):
        """``python -m poseidon_tpu.trace report`` and obs/report.py's
        churn totals read a batched file as they read a per-event one."""
        import dataclasses as dc

        from poseidon_tpu.bridge import SchedulerStats
        from poseidon_tpu.obs.report import analyze_trace
        from poseidon_tpu.trace import main

        paths = []
        for batched in (False, True):
            path = tmp_path / f"t{int(batched)}.jsonl"
            with open(path, "w") as fh:
                gen = TraceGenerator(sink=fh)
                for rn in (1, 2):
                    submits = [(f"p{rn}-{i}", "", None) for i in range(4)]
                    places = [(f"p{rn}-{i}", f"m{i}", {"cost": i,
                                                        "margin": 1})
                              for i in range(3)]
                    if batched:
                        gen.emit_many("SUBMIT", submits, rn - 1)
                        gen.emit("FINISH", task="old", round_num=rn - 1)
                        gen.emit_many("SCHEDULE", places, rn)
                    else:
                        gen.emit("FINISH", task="old", round_num=rn - 1)
                        for task, _, _ in submits:
                            gen.emit("SUBMIT", task=task,
                                     round_num=rn - 1)
                        for task, machine, detail in places:
                            gen.emit("SCHEDULE", task=task,
                                     machine=machine, round_num=rn,
                                     detail=detail)
                    stats = SchedulerStats(round_num=rn, pods_placed=3,
                                           total_ms=1.0 + rn)
                    gen.emit("ROUND", round_num=rn, detail=dc.asdict(stats))
            paths.append(str(path))
        reports = [analyze_trace(p) for p in paths]
        assert reports[0] == reports[1]
        assert reports[0]["churn"]["totals"]["SCHEDULE"] == 6
        assert reports[0]["churn"]["totals"]["SUBMIT"] == 8
        outs = []
        for p in paths:
            assert main(["report", p, "--json"]) == 0
            outs.append(capsys.readouterr().out)
        assert outs[0] == outs[1]


def _bridge_round(n_pods=9, n_running=2):
    """A bridge with a sink: nodes, a running pod or two, and
    ``n_pods`` new pending pods; returns the bridge, the sink, the
    pending uids in observe order and the round's result."""
    from poseidon_tpu.bridge import SchedulerBridge
    from poseidon_tpu.cluster import Machine, Task, TaskPhase

    sink = io.StringIO()
    bridge = SchedulerBridge(cost_model="quincy",
                             trace=TraceGenerator(sink=sink))
    bridge.observe_nodes([Machine(name=f"m{i}", max_tasks=4)
                          for i in range(4)])
    pending = [f"ns/p{i}" for i in range(n_pods)]
    bridge.observe_pods(
        [Task(uid=f"ns/r{i}", phase=TaskPhase.RUNNING, machine="m0")
         for i in range(n_running)]
        + [Task(uid=uid, phase=TaskPhase.PENDING) for uid in pending]
    )
    result = bridge.run_scheduler()
    return bridge, sink, pending, result


class TestBridgeRecords:
    """A round's SUBMIT and SCHEDULE records and its PLACE / new-pod
    log lines, written one batch per phase."""

    def test_round_trace_equals_per_event_path(self):
        bridge, sink, pending, result = _bridge_round()
        assert result.stats.pods_placed == len(pending)
        got = [_fields(x) for x in sink.getvalue().splitlines()]
        # the per-event path's lines, from what the bridge decided
        want = [_fields(_asdict_line(0, "SUBMIT", uid, "", 0, None))
                for uid in pending]
        for rn, kind, uid, d in bridge.decision_log:
            assert kind == "PLACE"
            want.append(_fields(_asdict_line(
                0, "SCHEDULE", uid, d["machine"], rn,
                {"cost": d["cost"], "margin": d["margin"]})))
        for kind in ("SUBMIT", "SCHEDULE"):
            assert [e for e in got if e["event"] == kind] == [
                e for e in want if e["event"] == kind
            ]
        # the apply's batch lands before the round's ROUND record
        kinds = [e["event"] for e in got]
        assert kinds == ["SUBMIT"] * 9 + ["SCHEDULE"] * 9 + ["ROUND"]
        assert {e["task"] for e in got if e["event"] == "SCHEDULE"} == set(
            result.bindings)

    def test_watch_event_writes_a_batch_of_one(self):
        from poseidon_tpu.bridge import SchedulerBridge
        from poseidon_tpu.cluster import Machine, Task, TaskPhase

        bridge = SchedulerBridge(cost_model="quincy")
        bridge.observe_nodes([Machine(name="m0", max_tasks=2)])
        bridge.observe_pod_event(
            "ADDED", Task(uid="w0", phase=TaskPhase.PENDING))
        bridge.observe_pod_event(
            "MODIFIED", Task(uid="w0", phase=TaskPhase.PENDING))
        assert [(e.event, e.task) for e in bridge.trace.events] == [
            ("SUBMIT", "w0")]

    def test_records_batched_counts_submit_and_schedule(self):
        bridge, sink, pending, result = _bridge_round()
        events = [json.loads(x)["event"] for x in
                  sink.getvalue().splitlines()]
        assert result.stats.records_batched == (
            events.count("SUBMIT") + events.count("SCHEDULE")) == 18
        # nothing new, nothing placed: the counter reads 0
        for uid, machine in result.bindings.items():
            bridge.confirm_binding(uid, machine)
        again = bridge.run_scheduler()
        assert again.stats.pods_placed == 0
        assert again.stats.records_batched == 0

    def test_info_log_carries_every_line_in_one_record_per_phase(
        self, caplog
    ):
        with caplog.at_level(logging.INFO, logger="poseidon_tpu.bridge"):
            bridge, _, pending, result = _bridge_round()
        lines = "\n".join(caplog.messages).splitlines()
        for uid in pending:
            assert f"new pending pod {uid}" in lines
        rn = result.stats.round_num
        for uid, machine in result.bindings.items():
            assert f"round {rn}: PLACE {uid} -> {machine}" in lines
        per_pod = [r for r in caplog.records
                   if "new pending pod" in r.getMessage()
                   or ": PLACE " in r.getMessage()]
        # one record per phase, a line per pod in each
        assert len(per_pod) == 2
        assert [len(r.getMessage().splitlines()) for r in per_pod] == [
            len(pending), len(result.bindings)]

    def test_no_per_pod_line_formatted_below_info(self):
        """With INFO off, the uids are never formatted into lines."""
        from poseidon_tpu.bridge import SchedulerBridge
        from poseidon_tpu.cluster import Machine, Task, TaskPhase

        formatted = []

        class Uid(str):
            def __format__(self, spec):
                formatted.append(str(self))
                return str.__format__(self, spec)

        logger = logging.getLogger("poseidon_tpu.bridge.bridge")
        seen = []

        class Count(logging.Handler):
            def emit(self, record):
                seen.append(record)

        handler = Count(level=logging.DEBUG)
        logger.addHandler(handler)
        old = logger.level
        try:
            bridge = SchedulerBridge(cost_model="quincy")
            bridge.observe_nodes([Machine(name="m0", max_tasks=8)])
            for level in (logging.WARNING, logging.INFO):
                logger.setLevel(level)
                uids = [Uid(f"ns/{level}-{i}") for i in range(3)]
                bridge.observe_pods(
                    [Task(uid=u, phase=TaskPhase.PENDING) for u in uids])
                result = bridge.run_scheduler()
                assert len(result.bindings) == 3
                if level == logging.WARNING:
                    assert formatted == [] and seen == []
            # at INFO both phases format their pods' lines
            assert sorted(set(formatted)) == sorted(
                f"ns/{logging.INFO}-{i}" for i in range(3))
            assert len(seen) == 2
        finally:
            logger.removeHandler(handler)
            logger.setLevel(old)
