"""A configuration, a traffic mix or a per-layer metric is added by adding
files and entries: the harness finds each by name, with no edit to any
file it already had."""

import json
import pathlib
import shutil

import run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def copy_benchmark(tmp: pathlib.Path) -> pathlib.Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp


def test_new_files_are_found_by_name(tmp_path):
    root = copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "benchmark/configs/quincy_1k.json").read_text())
    cfg["cluster"]["nodes"] = 2000
    (root / "benchmark/configs/quincy_2k.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/trickle.json").write_text(json.dumps({
        "kind": "open", "rate_pods_per_s": 20,
        "job_size": {"alpha": 1.1, "min": 1, "max": 1},
        "initial_running": 10000, "occupancy_target_pods": 10000,
        "warmup_pods": 64}))
    (root / "benchmark/metrics/pending_per_round.py").write_text(
        "def read(ctx):\n"
        "    xs = [r['pods_pending'] for r in ctx.rounds]\n"
        "    return sum(xs) / len(xs) if xs else None\n")
    spec["configs"].append({"name": "quincy_2k", "source": "x",
                            "file": "benchmark/configs/quincy_2k.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "quincy_2k.trickle",
                              "config": "quincy_2k", "traffic": "trickle",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({
        "name": "pending_per_round", "unit": "pods", "better": "lower",
        "source": "program_counter", "layer": "daemon loop (cli.run_loop)",
        "moves": "bind_p50_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell(root, "quincy_2k.trickle")
    assert cell.config["cluster"]["nodes"] == 2000
    assert cell.traffic["rate_pods_per_s"] == 20
    names = [m["name"] for m in cell.per_layer]
    assert "pending_per_round" in names
    assert "pods_bound_per_s" not in [m["name"] for m in cell.end_to_end]
    ctx = run.ReadContext(
        rounds=[{"pods_pending": 4}, {"pods_pending": 8}], before=None,
        trace=None, trace_window_s=0.0, trace_rounds=[], config=cell.config,
        traffic=cell.traffic, peaks={})
    got = run.per_layer(cell, ctx)
    assert got["pending_per_round"] == {"value": 6.0, "unit": "pods"}
    # a reader that finds nothing is left out of the line
    assert "solve_device_ms" not in got
    # nothing the benchmark already had was edited
    for p, data in before.items():
        assert p.read_bytes() == data


def test_every_cell_resolves_in_the_committed_benchmark():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(ROOT, w["name"])
        assert cell.config["cost_model"]
        assert (ROOT / "benchmark/pricing"
                / f"{cell.config['cost_model']}.py").exists()
        for m in cell.per_layer:
            assert (ROOT / "benchmark/metrics" / f"{m['name']}.py").exists()
        assert "setup_s" in [m["name"] for m in cell.end_to_end]


def test_a_watch_observed_cell_is_refused(tmp_path):
    """The reference rebuilds each round's view from the pod LISTs the
    daemon polled; a cell observing through watch has none to read."""
    import pytest

    root = copy_benchmark(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "octopus_5k_watch", "source": "x",
                            "file": "benchmark/configs/octopus_5k.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "octopus_5k_watch.backlog",
                              "config": "octopus_5k_watch",
                              "traffic": "backlog", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(run.BenchError, match="watch"):
        run.load_cell(root, "octopus_5k_watch.backlog")
