"""The readers of the program-span metrics, on a stats fixture whose
rounds carry the span fields and a trace whose host lines hold
``poseidon/`` spans over part of the device's idle gaps."""

import json
import pathlib

import pytest

import run
import trace_reduce

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[2]
NEW = ("list_ms", "bind_post_ms", "fetch_wait_ms", "finish_ms",
       "placed_wait_rounds")


def rows(name):
    return [json.loads(line) for line in open(FIX / name)]


def ctx(stats, trace=None):
    cfg = json.load(open(ROOT / "benchmark" / "configs" / "quincy_1k.json"))
    return run.ReadContext(
        rounds=stats[1:], before=stats[0], trace=trace,
        trace_window_s=0.12 if trace else 0.0, trace_rounds=stats[1:],
        config=cfg, traffic={}, peaks={})


def read(name, c):
    return run.load_reader(ROOT, name)(c)


def test_span_stats_readers():
    c = ctx(rows("stats_spans.jsonl"))
    # rounds 7 and 9 have pods to place; round 8 (empty) is left out
    assert read("list_ms", c) == pytest.approx((40 + 60) / 2)
    assert read("bind_post_ms", c) == pytest.approx((100 + 200) / 2)
    assert read("fetch_wait_ms", c) == pytest.approx((2 + 4) / 2)
    assert read("finish_ms", c) == pytest.approx((12 + 18) / 2)
    # every round of the window: (8 + 0 + 30) waits over 8 + 0 + 20 pods
    assert read("placed_wait_rounds", c) == pytest.approx(38 / 28)


def test_span_stats_readers_find_nothing_without_the_fields():
    c = ctx(rows("stats_small.jsonl"))
    for name in NEW:
        assert read(name, c) is None


def test_idle_untraced_share():
    trace = trace_reduce.load(str(FIX / "trace_spans.json"))
    c = ctx(rows("stats_spans.jsonl"), trace)
    # ops [0,10) [50,60) [100,110) ms: idle [10,50) and [60,100), 80 ms.
    # Spans other than the root: list [15,35) and finish [45,52) cover
    # 20 + 5 ms of the first gap; bind_post [60,70) on a worker line and
    # post_bindings [65,75) cover [60,75), 15 ms of the second; the
    # non-poseidon events count for nothing. Untraced: 80 - 40 ms.
    assert read("idle_untraced_pct", c) == pytest.approx(50.0)


def test_idle_untraced_share_without_program_spans():
    # the host events of trace_small carry no poseidon/ prefix
    trace = trace_reduce.load(str(FIX / "trace_small.json"))
    c = ctx(rows("stats_small.jsonl"), trace)
    assert read("idle_untraced_pct", c) == pytest.approx(100.0)
    assert read("idle_untraced_pct", ctx(rows("stats_small.jsonl"))) \
        is None
