"""The reader of the dense auction's bidding rounds, on a stats fixture
with the field and on one without it."""

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
    return run.load_reader(ROOT, "auction_rounds")(c)


def test_mean_over_the_rounds_the_auction_solved():
    # rounds 8 and 11 (round 7 is the round before the window); the
    # empty round 9 and the oracle's round 10 are left out
    assert read(ctx("stats_auction.jsonl")) == pytest.approx((9 + 1) / 2)


def test_nothing_without_the_field():
    assert read(ctx("stats_small.jsonl")) is None
    assert read(ctx("stats_spans.jsonl")) is None
