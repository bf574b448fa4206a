"""The reader of the poll observe's elided share, on a stats fixture
with the counters and on ones without them."""

import json
import pathlib

import pytest

import run

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[2]


def ctx(name):
    stats = [json.loads(line) for line in open(FIX / name)]
    cfg = json.load(open(ROOT / "benchmark" / "configs" / "quincy_1k.json"))
    return run.ReadContext(
        rounds=stats[1:], before=stats[0], trace=None, trace_window_s=0.0,
        trace_rounds=stats[1:], config=cfg, traffic={}, peaks={})


def read(c):
    return run.load_reader(ROOT, "observe_elided_pct")(c)


def test_share_over_the_rounds_with_pods():
    # rounds 8, 10 and 11 (round 7 is the round before the window); the
    # empty round 9 is left out, whatever it elided
    assert read(ctx("stats_observe.jsonl")) == pytest.approx(
        100.0 * (900 + 1500 + 0) / (1000 + 3000 + 0))


def test_nothing_without_the_fields():
    assert read(ctx("stats_small.jsonl")) is None
    assert read(ctx("stats_spans.jsonl")) is None
    assert read(ctx("stats_auction.jsonl")) is None
