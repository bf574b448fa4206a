"""From a profiler trace to device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData`` into plain lists (the
only function here that imports JAX); everything else works on those
lists, so the tests can feed it a small recorded trace as JSON.

A trace, as this module holds it: ``{"planes": [{"name": ...,
"lines": [{"name": ..., "events": [[name, start_ns, dur_ns], ...]}]}]}``.

- Device planes are those named ``/device:TPU:<n>``. Their ``XLA Ops``
  line holds every operation the device ran; the busy time of a device is
  the union of those intervals, and its idle share is one less busy over
  the traced window.
- A jitted program's device time is the sum of the durations of its
  executions on the ``XLA Modules`` line, found by the jit name in the
  module's name (``jit_<function>``).
- Host planes (``/host:CPU``) hold what the host threads were doing; an
  idle gap on the device is named by the innermost host event that
  covers its middle.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """The trace at ``path`` (an .xplane.pb, or a .json in this module's
    own layout) as plain lists."""
    if path.endswith(".json"):
        with open(path) as fh:
            return json.load(fh)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in ln.events
            ]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane: dict, line_name: str) -> list[list]:
    for ln in plane["lines"]:
        if ln["name"] == line_name:
            return ln["events"]
    return []


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(plane: dict) -> float:
    """Union of the device's operation intervals, in ns."""
    ivs = union((s, s + d) for _n, s, d in line_events(plane, OPS_LINE))
    return sum(e - s for s, e in ivs)


def program_ns(plane: dict, jit_name: str) -> tuple[float, int]:
    """(device ns, executions) of the jitted program ``jit_name`` (the
    function's name; matched as ``jit_<name>`` in module names)."""
    key = f"jit_{jit_name}"
    total, n = 0.0, 0
    for name, _s, d in line_events(plane, MODULES_LINE):
        if key in name:
            total += d
            n += 1
    return total, n


def op_label(name: str) -> str:
    """``%while.16 = (s32[4096]{...}, ...) while(...), ...`` becomes
    ``%while.16 while``: the instruction and its opcode, without the
    shapes and operands that make up most of an HLO line."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r" ([a-z][a-z0-9-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def top_ops(plane: dict, k: int = 10) -> list[list]:
    """The k operations that took most device time: [[label, seconds]]."""
    acc: dict[str, float] = {}
    for name, _s, d in line_events(plane, OPS_LINE):
        name = op_label(name)
        acc[name] = acc.get(name, 0.0) + d
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]


def idle_gaps(trace: dict, plane: dict, k: int = 10) -> list[list]:
    """The k longest gaps between device operations inside the traced
    span, each named by the innermost host event covering its middle:
    [[name, seconds]]."""
    ivs = union((s, s + d) for _n, s, d in line_events(plane, OPS_LINE))
    gaps = [(ivs[i][1], ivs[i + 1][0]) for i in range(len(ivs) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [ev for p in trace["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]]
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        cover = [ev for ev in host if ev[1] <= mid <= ev[1] + ev[2]]
        name = (min(cover, key=lambda ev: ev[2])[0] if cover
                else "no traced host activity")
        out.append([f"host: {name}", (e - s) / 1e9])
    return out
