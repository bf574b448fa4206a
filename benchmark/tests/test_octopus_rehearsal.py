"""A whole run of the place-only Octopus configuration on the CPU at 80
nodes (above the program's small-instance cut to the CPU oracle, 64
machines), its backlog waves scaled down in the cell's proportions (a
wave of 0.4 of the pods running, on half the seats): the program's rounds
cost the plain reference's optimum, priced by the pods already running,
and every other check holds. A wave that deleted more than half the pods
the daemon knows would trip its mass-eviction guard (``bridge.
_hold_shrink``), which keeps the deleted pods for up to three polls by
design; the cell's waves stay under it. About half a minute."""

import json
import pathlib
import shutil

import pytest

from test_faults import failed, run_case

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinyo")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "traffic").mkdir()
    shutil.copytree(BENCH / "pricing", root / "benchmark" / "pricing")
    shutil.copytree(BENCH / "metrics", root / "benchmark" / "metrics")
    cfg = json.loads((BENCH / "configs" / "octopus_5k_poll.json")
                     .read_text())
    cfg["cluster"]["nodes"] = 80
    (root / "benchmark" / "configs" / "tinyo.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "traffic" / "backlog.json").write_text(
        json.dumps({"kind": "waves", "wave_pods": 160,
                    "initial_running": 400, "warmup_waves": 1,
                    "warmup_tail_pods": 16, "settle_s": 1.0}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tinyo", "source": "x",
                        "file": "benchmark/configs/tinyo.json",
                        "reduced": [], "why": "x"}]
    spec["workloads"] = [{"name": "tinyo.backlog", "config": "tinyo",
                          "traffic": "backlog", "chips": 1, "why": "x"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_place_only_octopus_is_correct(tiny_root):
    line = run_case(tiny_root, "tinyo.backlog", "", 2**31 + 7)
    assert failed(line) == set(), line["checks"]
    assert line["correct"] is True
    assert line["checks"]["round_cost_gap"]["value"] == 0
