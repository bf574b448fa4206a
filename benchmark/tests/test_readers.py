"""The trace reduction and every per-layer reader, on a small recorded
trace and a stats JSONL fixture."""

import json
import pathlib

import pytest

import flow_bytes
import run
import trace_reduce

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[2]
MS = 1e6


@pytest.fixture
def trace():
    return trace_reduce.load(str(FIX / "trace_small.json"))


@pytest.fixture
def stats():
    return [json.loads(line) for line in open(FIX / "stats_small.jsonl")]


def ctx(stats, trace=None, window_s=0.0, peaks=None):
    cfg = json.load(open(ROOT / "benchmark" / "configs" / "quincy_1k.json"))
    return run.ReadContext(
        rounds=stats[1:], before=stats[0], trace=trace,
        trace_window_s=window_s, trace_rounds=stats[1:], config=cfg,
        traffic={}, peaks=peaks or {})


def read(name, c):
    return run.load_reader(ROOT, name)(c)


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]


def test_busy_is_the_union_of_ops(trace):
    plane = trace_reduce.device_planes(trace)[0]
    # [0, 30) + [40, 41) + [100, 120)
    assert trace_reduce.busy_ns(plane) == pytest.approx(51 * MS)


def test_program_time_by_jit_name(trace):
    plane = trace_reduce.device_planes(trace)[0]
    assert trace_reduce.program_ns(plane, "_resident_chain") == (
        pytest.approx(50 * MS), 2)
    assert trace_reduce.program_ns(plane, "absent") == (0.0, 0)


def test_top_ops_and_idle_gaps(trace):
    plane = trace_reduce.device_planes(trace)[0]
    top = trace_reduce.top_ops(plane, 2)
    assert [n for n, _ in top] == ["while.2", "fusion.1"]
    assert top[0][1] == pytest.approx(0.040)
    gaps = trace_reduce.idle_gaps(trace, plane)
    # the 59 ms gap [41, 100) is the longest; its middle is in both
    # host events, and the innermost (the POSTs) names it
    assert gaps[0] == ["host: _post_bindings", pytest.approx(0.059)]
    assert gaps[1] == ["host: finish_round", pytest.approx(0.010)]


def test_stats_readers(stats):
    c = ctx(stats)
    assert read("round_wall_ms", c) == pytest.approx(250.0)
    assert read("observe_ms", c) == pytest.approx(19.0 / 3)
    assert read("build_ms", c) == pytest.approx(5.0)
    assert read("upload_ms", c) == pytest.approx(2.5)
    assert read("degraded_rounds", c) == 2


def test_trace_readers(stats, trace):
    c = ctx(stats, trace, window_s=0.2, peaks={"hbm_bytes_per_s": 819e9})
    assert read("solve_device_ms", c) == pytest.approx(25.0)
    assert read("device_idle_pct", c) == pytest.approx(100 * (1 - 0.051
                                                                / 0.2))
    # two traced device rounds of 20 and 30 pending pods, 2.3 preference
    # arcs a pod, 1,000 machines in 32 racks: their mean bytes at
    # 819 GB/s over the program's mean 25 ms an execution
    b = [flow_bytes.network_bytes(p, p * 2.3, 1000, 1000, 32, 1)
         for p in (20, 30)]
    want = 100 * (sum(b) / 2) / 819e9 / 0.025
    assert read("resident_chain_roofline", c) == pytest.approx(want)
    assert 0 < want < 100


def test_readers_find_nothing_without_a_trace(stats):
    c = ctx(stats)
    for name in ("solve_device_ms", "device_idle_pct",
                 "resident_chain_roofline"):
        assert read(name, c) is None


def test_network_bytes_counts_the_taxonomy():
    # 10 pods with 2 prefs each, 4 machines (all racked) in 2 racks,
    # 3 jobs: arcs 20 + 20 + 8 + 4 + 3 = 55; nodes 10+1+2+4+3+1 = 21
    assert flow_bytes.network_bytes(10, 20, 4, 4, 2, 3) == 55 * 16 + 21 * 4
