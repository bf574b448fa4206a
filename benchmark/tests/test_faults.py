"""A whole run on the CPU at a small size, with the chip check skipped:
sound, it comes out correct; with the control or any fault that a cell
can have planted under the timed path (``faults.py``), it does not.

Each case runs in a process of its own (a plant patches the program for
the life of its process). About half a minute a case."""

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"

# the two configurations at 80 nodes (above the program's small-instance
# cut to the CPU oracle, 64 machines), with their traffic scaled down;
# Octopus's backlog waves in the cell's proportions (a wave of 0.4 of the
# pods running, on half the seats): a wave that deleted more than half
# the pods the daemon knows would trip its mass-eviction guard (``bridge.
# _hold_shrink``), which keeps the deleted pods for up to three polls by
# design
TINY = {
    "tinyq": ("quincy_1k", {"nodes": 80, "machines_per_rack": 16},
              "burst", {"kind": "open", "rate_pods_per_s": 150,
                        "job_size": {"alpha": 1.1, "min": 1, "max": 300},
                        "strata": 4,
                        "initial_running": 300,
                        "occupancy_target_pods": 300, "warmup_pods": 400,
                        "settle_s": 1.0}),
    "tinyo": ("octopus_5k_poll", {"nodes": 80},
              "backlog", {"kind": "waves", "wave_pods": 160,
                          "initial_running": 400, "warmup_waves": 1,
                          "warmup_tail_pods": 16, "settle_s": 1.0}),
}

DRIVER = """
import pathlib, sys
sys.path.insert(0, {bench!r}); sys.path.insert(1, {root!r})
import faults, run
plant = faults.PLANTS[{plant!r}] if {plant!r} else None
sys.exit(run.main({argv!r}, allow_platform="cpu", plant=plant,
                  root=pathlib.Path({tiny!r})))
"""


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "traffic").mkdir()
    shutil.copytree(BENCH / "pricing", root / "benchmark" / "pricing")
    shutil.copytree(BENCH / "metrics", root / "benchmark" / "metrics")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, (base, cluster, traffic, mix) in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        cfg["cluster"].update(cluster)
        path = f"benchmark/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        (root / "benchmark" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(mix))
        spec["configs"].append({"name": name, "source": "x", "file": path,
                                "reduced": [], "why": "x"})
        spec["workloads"].append({"name": f"{name}.{traffic}",
                                  "config": name, "traffic": traffic,
                                  "chips": 1, "why": "x"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
        if m["name"] == "pods_bound_per_s":
            m["workloads"] = ["tinyo.backlog"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_case(root, cell: str, plant: str, seed: int) -> dict:
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "6",
            "--trace", "0"]
    code = DRIVER.format(bench=str(BENCH), root=str(ROOT), plant=plant,
                         argv=argv, tiny=str(root))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=ROOT, timeout=600,
                          env={"JAX_PLATFORMS": "cpu",
                               "PATH": "/usr/bin:/bin:/usr/local/bin",
                               "HOME": str(root)})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def failed(line: dict) -> set:
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct(tiny_root):
    line = run_case(tiny_root, "tinyq.burst", "", 2**31 + 7)
    assert line["correct"] is True, line["checks"]
    assert failed(line) == set()


def test_place_only_octopus_is_correct(tiny_root):
    """Place-only Octopus's rounds cost the plain reference's optimum,
    priced by the pods already running, and every other check holds."""
    line = run_case(tiny_root, "tinyo.backlog", "", 2**31 + 7)
    assert failed(line) == set(), line["checks"]
    assert line["correct"] is True
    assert line["checks"]["round_cost_gap"]["value"] == 0


@pytest.mark.parametrize("cell,plant,must_fail", [
    ("tinyq.burst", "early_stop", {"round_cost_gap"}),
    ("tinyq.burst", "stale", {"never_bound"}),
    ("tinyq.burst", "half", {"never_bound"}),
    ("tinyq.burst", "altered", None),
    ("tinyq.burst", "misbound", {"actuation_mismatch"}),
    ("tinyq.burst", "unseen", {"round_cost_gap", "observed_mismatch",
                               "never_bound"}),
])
def test_planted_fault_is_not_correct(tiny_root, cell, plant, must_fail):
    line = run_case(tiny_root, cell, plant, 2**31 + 11)
    assert line["correct"] is False
    if must_fail:
        assert must_fail <= failed(line), line["checks"]
