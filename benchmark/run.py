#!/usr/bin/env python3
"""The benchmark's one command: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n>
                             --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: the cell in
``BENCHMARK.json``, its configuration file (named there), its traffic
file ``benchmark/traffic/<traffic>.json``, its reference pricing
``benchmark/pricing/<cost_model>.py`` and each per-layer metric's reader
``benchmark/metrics/<metric>.py``.

One run, in this process (which holds the chip):

1. refuse unless JAX sees a TPU and as many chips as the cell asks for;
2. start the apiserver and traffic generator in a child process that
   never imports JAX (``apiserver.py``), which loads the cluster in
   memory;
3. run the daemon, ``cli.run_loop``, with ``deploy/poseidon-tpu.cfg``
   and the overlays the configuration (and traffic) file lists, every
   output directory in a fresh directory of this run;
4. warm up (the child sends the largest burst the mix makes, then the
   daemon runs one shadow audit), then measure ``--seconds`` of
   traffic, drain, and stop the loop through its ``stop_event``;
5. check what the window produced against the plain reference
   (``reference.py``), and print one JSON line last on standard output.

End-to-end numbers are the child's stamps: a pod's due time and the
arrival of its binding at the apiserver, on one monotonic clock.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()   # the set-up clock starts here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent))   # the system under test

import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

RUNS = BENCH.parent / ".bench_runs"
# rounds whose inputs the reference re-solves: this many drawn from the
# seed among the window's rounds with pods to place, plus the largest
REFERENCE_ROUNDS = 6
# the whole run, set-up and check included, ends well inside the
# contract's 360 s (1200 s for a checkout's first, compiling run)
DEADLINE_S = 330.0
FIRST_RUN_DEADLINE_S = 1150.0
# a traced run keeps the profiler on for at most this much of the window
TRACE_MAX_S = 10.0
# the longest the warm-up waits for its shadow audit
AUDIT_MAX_S = 120.0


class BenchError(RuntimeError):
    """The run cannot produce a result."""


# ---- the benchmark's data, by name --------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path
    chips: int
    config: dict
    traffic: dict
    config_path: pathlib.Path
    traffic_path: pathlib.Path
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    spec = traffic_gen.load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config_path = root / configs[w["config"]]["file"]
    traffic_path = root / "benchmark" / "traffic" / f"{w['traffic']}.json"

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    config = traffic_gen.load_json(config_path)
    traffic = traffic_gen.load_json(traffic_path)
    if "--watch=true" in config.get("flags", []) + traffic.get("flags", []):
        raise BenchError(f"{workload}: the reference rebuilds what each "
                         f"round saw from the pod LISTs it polled; a cell "
                         f"that observes through watch has none")

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if mine(m) and m["moves"] in reported]
    return Cell(workload, root, int(w["chips"]), config, traffic,
                config_path, traffic_path, e2e, per_layer)


def load_reader(root: pathlib.Path, name: str):
    path = root / "benchmark" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the chip ------------------------------------------------------------

def require_chip(chips: int, allow_platform: str | None = None) -> dict:
    """The accelerator JAX sees; raises unless it is ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and platform != allow_platform:
        raise BenchError(f"no TPU: JAX found platform {platform!r} with "
                         f"{len(devs)} device(s); this benchmark has no "
                         f"CPU fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_memory_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCount:
    """XLA compiles while ``on``, on the daemon loop's thread (this
    process's main thread) and on any other (the program's background
    threads, such as its shadow audit). JAX's monitoring event; the
    listener stays registered, as JAX cannot remove one."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.loop = self.other = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_kw):
        if self.on and name == self.EVENT:
            if threading.current_thread() is threading.main_thread():
                self.loop += 1
            else:
                self.other += 1
            self.seconds += float(secs)


# ---- what the daemon did, round by round ------------------------------------

class Recorder:
    """The ``round_hook`` and a wrapper of the bridge's ``begin_round``.

    The hook keeps each finished round's stats and decisions (references
    only). The wrapper stamps when each round begins, which says which
    pod LIST the round was built from, and copies the program's tasks
    (the bridge replaces a task object when it changes, so the copy is
    what the round observed); the reference never reads that copy, the
    check compares it with what the apiserver held. After the round the
    copy is kept only for the rounds the reference will check, drawn
    from the seed."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng([int(seed) % (1 << 64), 11])
        self.rounds: list[dict] = []
        self.t0 = self.t1 = None
        self.views: dict[int, tuple] = {}
        self.pool: list[tuple[int, tuple]] = []   # the reservoir
        self.seen = 0
        self.largest: tuple[int, int, tuple] | None = None
        self.wrapped = False
        self.audit_seats = 0      # > 0: run one shadow audit now
        self.audit = None         # (auditor, audits done before) or False

    def hook(self, _n, result, bridge) -> None:
        s = result.stats
        now = time.monotonic()
        view = self.views.pop(s.round_num, None)
        t_begin = view[0] if view else now - s.wall_ms / 1000
        self.rounds.append({
            "stats": dataclasses.asdict(s), "t_begin": t_begin,
            "t_view": view[0] if view else None,
            "t_end": now, "bindings": result.bindings,
            "unscheduled": result.unscheduled,
            "migrations": result.migrations,
            "preemptions": result.preemptions,
        })
        if view is not None and s.pods_pending > 0 and self.in_window(
                t_begin):
            self._sample(s.round_num, view, s.pods_pending)
        if not self.wrapped:
            self._wrap(bridge)
        if self.audit_seats:
            self._start_audit(bridge)

    def _start_audit(self, bridge) -> None:
        """The program's shadow audit (``--audit_every``) once, at the
        end of the warm-up, with its CPU pricing shapes pinned to the
        whole cluster's: its compiles then fall in the set-up, and no
        audit in the window meets a shape it has not compiled."""
        aud = getattr(bridge, "auditor", None)
        if aud is None:
            self.audit_seats, self.audit = 0, False
            return
        aud.prewarm(tasks=self.audit_seats, machines=len(bridge.machines))
        before = aud.completed + aud.failures
        if aud.capture(round_num=bridge.round_num,
                       cost_model=bridge.cost_model,
                       hysteresis=bridge.migration_hysteresis,
                       machines=bridge.machines, tasks=bridge.tasks,
                       knowledge=bridge.knowledge):
            self.audit_seats, self.audit = 0, (aud, before)

    def warm_audit(self, seats: int, limit_s: float) -> dict:
        """Ask the loop for the warm-up's audit and wait until it ends."""
        self.audit_seats = seats
        end = time.monotonic() + limit_s
        while time.monotonic() < end:
            if self.audit is False:
                return {"audit": "off"}
            if self.audit is not None:
                aud, before = self.audit
                if aud.completed + aud.failures > before:
                    last = aud.last
                    return {"audit_ms": last.audit_ms, "error": last.error}
            time.sleep(0.05)
        raise BenchError(f"the warm-up's shadow audit took over "
                         f"{limit_s:.0f} s")

    def in_window(self, t: float) -> bool:
        return self.t0 is not None and self.t0 <= t < self.t1

    def _sample(self, round_num: int, view, pending: int) -> None:
        """Reservoir sampling (Algorithm R) over the window's rounds
        with pods to place, and the largest of them besides."""
        self.seen += 1
        if len(self.pool) < REFERENCE_ROUNDS:
            self.pool.append((round_num, view))
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < REFERENCE_ROUNDS:
                self.pool[j] = (round_num, view)
        if self.largest is None or pending > self.largest[0]:
            self.largest = (pending, round_num, view)

    @property
    def kept(self) -> dict[int, tuple]:
        out = dict(self.pool)
        if self.largest is not None:
            out[self.largest[1]] = self.largest[2]
        return out

    def _wrap(self, bridge) -> None:
        begin = bridge.begin_round
        views = self.views

        def begin_round():
            views[bridge.round_num + 1] = (time.monotonic(),
                                           list(bridge.tasks.values()))
            return begin()

        bridge.begin_round = begin_round
        self.wrapped = True

    def window_rounds(self) -> list[dict]:
        return [r for r in self.rounds if self.in_window(r["t_begin"])]

    def round_before_window(self) -> dict | None:
        before = [r for r in self.rounds
                  if self.t0 is not None and r["t_begin"] < self.t0]
        return before[-1] if before else None


# ---- the child ---------------------------------------------------------------

class Child:
    """The apiserver/generator process and its line protocol (read by a
    thread of its own, so every wait has a limit)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, rundir):
        cmd = [sys.executable, str(BENCH / "apiserver.py"),
               "--config", str(cell.config_path),
               "--traffic", str(cell.traffic_path),
               "--seed", str(seed), "--seconds", str(seconds)]
        self.err = open(rundir / "apiserver.err", "w")
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.err, text=True, bufsize=1)
        self.lines: queue.Queue = queue.Queue()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def expect(self, key: str, timeout: float) -> dict:
        try:
            line = self.lines.get(timeout=timeout)
        except queue.Empty:
            raise BenchError(f"the apiserver child sent no {key!r} within "
                             f"{timeout:.0f} s") from None
        if line is None:
            raise BenchError(f"the apiserver child exited "
                             f"(rc={self.proc.poll()})")
        msg = json.loads(line)
        if key not in msg:
            raise BenchError(f"apiserver child said {msg}, expected {key}")
        return msg

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send("exit")
                self.proc.wait(timeout=20)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self.reader.join(timeout=5)
        self.err.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def daemon_argv(cell: Cell, port: int, rundir: pathlib.Path) -> list[str]:
    """The deployed flagfile, the cell's overlays, and this run's
    outputs (so ``--restore=auto`` never finds an earlier run's state)."""
    root = BENCH.parent
    return [
        f"--flagfile={root / 'deploy' / 'poseidon-tpu.cfg'}",
        *cell.config.get("flags", []),
        *cell.traffic.get("flags", []),
        "--k8s_apiserver_host=127.0.0.1",
        f"--k8s_apiserver_port={port}",
        f"--stats_json={rundir / 'stats.jsonl'}",
        f"--trace_log={rundir / 'trace.jsonl'}",
        f"--flight_dir={rundir / 'flightrec'}",
        f"--checkpoint_dir={rundir / 'ckpt'}",
        f"--metrics_port={free_port()}",
    ]


def quiet_logging() -> None:
    """The daemon logs every placement at INFO: formatted as deployed,
    but written to /dev/null, with warnings and errors to stderr.
    (``run_loop``'s own ``basicConfig`` is then a no-op.)"""
    fmt = logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s")
    root = logging.getLogger()
    root.setLevel(logging.INFO)
    sink = logging.StreamHandler(open(os.devnull, "w"))
    sink.setFormatter(fmt)
    loud = logging.StreamHandler(sys.stderr)
    loud.setLevel(logging.WARNING)
    loud.setFormatter(fmt)
    root.handlers[:] = [sink, loud]


# ---- one run -------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    compiles_loop: int = 0
    compiles_other: int = 0
    compile_s: float = 0.0
    trace_dir: str | None = None
    trace_window_s: float = 0.0
    warm: dict | None = None
    audit: dict | None = None
    drained: dict | None = None
    error: str | None = None


def drive(cell: Cell, args, rundir: pathlib.Path, cache: str,
          plant=None) -> tuple[Window, Recorder, dict, int]:
    """Run the daemon through warm-up, window and drain; return what
    happened and the child's report."""
    from poseidon_tpu.cli import parse_args, run_loop

    if plant is not None and not plant.at_window:
        plant.patch()
    compiles = CompileCount()
    rec = Recorder(args.seed)
    win = Window()
    child = Child(cell, args.seed, args.seconds, rundir)
    stop = threading.Event()
    first_run = not any(pathlib.Path(cache).glob("*"))
    deadline = T_START + (FIRST_RUN_DEADLINE_S if first_run
                          else DEADLINE_S)

    def control():
        try:
            win.warm = child.expect("warm", deadline - time.monotonic())
            if not win.warm["warm"]:
                raise BenchError(f"warm-up did not bind its pods: "
                                 f"{win.warm}")
            c = cell.config["cluster"]
            win.audit = rec.warm_audit(
                int(c["nodes"]) * int(c["max_tasks_per_pu"]),
                min(AUDIT_MAX_S, deadline - time.monotonic()))
            if args.trace:
                import jax

                win.trace_dir = str(rundir / "profile")
                # no Python tracer: it slows every host thread several
                # fold; the runtime's own host events stay
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(win.trace_dir,
                                         profiler_options=opts)
                t_trace = time.monotonic()
            if plant is not None and plant.at_window:
                plant.patch()
            compiles.on = True
            child.send("window")
            msg = child.expect("t0", 60)
            win.t0, win.t1 = msg["t0"], msg["t1"]
            rec.t0, rec.t1 = win.t0, win.t1
            win.setup_s = win.t0 - T_START
            if args.trace:
                # trace at most TRACE_MAX_S of the window
                time.sleep(max(0.0, min(win.t1, win.t0 + TRACE_MAX_S)
                               - time.monotonic()))
                win.trace_window_s = time.monotonic() - t_trace
                jax.profiler.stop_trace()
            child.expect("window_end", args.seconds + 60)
            compiles.on = False
            win.compiles_loop = compiles.loop
            win.compiles_other = compiles.other
            win.compile_s = compiles.seconds
            win.drained = child.expect("drained", deadline - time.monotonic())
        except Exception as e:  # noqa: BLE001 -- reported, run fails
            win.error = f"{type(e).__name__}: {e}"
        finally:
            stop.set()

    def watchdog():
        while not stop.wait(1.0):
            if time.monotonic() > deadline:
                win.error = win.error or "run deadline passed"
                stop.set()

    try:
        msg = child.expect("port", 120)
        argv = daemon_argv(cell, msg["port"], rundir)
        quiet_logging()
        threads = [threading.Thread(target=control, daemon=True),
                   threading.Thread(target=watchdog, daemon=True)]
        child.send("go")
        for t in threads:
            t.start()
        rc = run_loop(parse_args(argv), stop_event=stop, round_hook=rec.hook)
        threads[0].join(timeout=30)
        if rc != 0:
            win.error = win.error or f"run_loop returned {rc}"
        if win.error:
            raise BenchError(win.error)
        memory = peak_memory_bytes(cell.chips)
        report_path = rundir / "report.json"
        child.send(f"report {report_path}")
        child.expect("reported", 120)
        report = traffic_gen.load_json(report_path)
    finally:
        child.close()
    return win, rec, report, memory


# ---- end-to-end numbers -----------------------------------------------------------

def latencies_ms(report: dict) -> np.ndarray:
    """Due time to the binding's arrival, for every pod due in the
    window; a pod still unbound counts the latency it had reached."""
    t0, t1, end = report["t0"], report["t1"], report["report_t"]
    lat = [((bound if bound is not None else end) - due) * 1000.0
           for _k, due, _c, bound, *_ in report["pods"] if t0 <= due < t1]
    return np.asarray(lat, float)


def end_to_end(cell: Cell, win: Window, report: dict) -> dict:
    lat = latencies_ms(report)
    if lat.size == 0:
        raise BenchError("no pod was due in the window")
    values = {
        "bind_p50_ms": float(np.percentile(lat, 50)),
        "bind_p95_ms": float(np.percentile(lat, 95)),
        "pods_bound_per_s": report["bound_in_window"] / (win.t1 - win.t0),
        "setup_s": win.setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


# ---- per-layer numbers ---------------------------------------------------------------

@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader may read."""

    rounds: list[dict]          # SchedulerStats of the window's rounds
    before: dict | None         # SchedulerStats of the round before
    trace: dict | None          # trace_reduce layout (traced run only)
    trace_window_s: float
    trace_rounds: list[dict]    # SchedulerStats of the traced rounds
    config: dict
    traffic: dict
    peaks: dict                 # this device kind's peaks


def device_peaks(kind: str) -> dict:
    table = traffic_gen.load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json")
    return table[kind]


def per_layer(cell: Cell, ctx: ReadContext) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.root, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_device(trace: dict, chips: int, window_s: float,
                 memory: int, device: dict) -> tuple[dict, dict]:
    planes = trace_reduce.device_planes(trace)[:chips]
    if not planes:
        raise BenchError("the trace holds no TPU device plane")
    busy = sum(trace_reduce.busy_ns(p) for p in planes) / len(planes) / 1e9
    dev = dict(device, memory_peak_bytes=memory, busy_s=busy,
               window_s=window_s)
    breakdown = {"device_ops": trace_reduce.top_ops(planes[0]),
                 "idle_gaps": trace_reduce.idle_gaps(trace, planes[0])}
    return dev, breakdown


# ---- correctness -------------------------------------------------------------------

def check(cell: Cell, rec: Recorder, report: dict
          ) -> tuple[dict, list[str]]:
    """Every number compared, with its limit; and what went wrong."""
    notes: list[str] = []
    pods = {k: row for k, *row in report["pods"]}
    t0, t1 = report["t0"], report["t1"]
    never = sum(1 for due, _c, bound, *_ in pods.values()
                if t0 <= due < t1 and bound is None)
    # actuation: every decision of every round is what the apiserver
    # holds, and the apiserver holds nothing no round decided
    decided: dict[str, str] = {}
    twice = 0
    for r in rec.rounds:
        for k, m in r["bindings"].items():
            twice += k in decided
            decided[k] = m
        twice += len(r["migrations"]) + len(r["preemptions"])
    held = {k: row[3] for k, row in pods.items() if row[3]}
    initial = {k for k, row in pods.items() if row[0] < t0 and row[2] is None
               and row[3]}
    wrong = sum(1 for k, m in decided.items() if held.get(k) != m)
    phantom = sum(1 for k in held if k not in decided and k not in initial)
    actuation = wrong + phantom + twice + report["conflicts"] \
        + report["rejected"]
    if actuation:
        notes.append(f"actuation: {wrong} decisions not held as decided, "
                     f"{phantom} bindings no round decided, {twice} "
                     f"repeated or moving decisions, {report['conflicts']} "
                     f"conflicts, {report['rejected']} rejected POSTs")
    # the reference, on the rounds drawn from the seed
    pricing = reference.load_pricing(cell.root / "benchmark" / "pricing",
                                     cell.config["cost_model"])
    gap, infeasible, mismatch, checked = 0, 0, 0, []
    kept = rec.kept
    for r, inp in apiserver_views(report, rec.rounds):
        n = inp.round_num
        view = kept.get(n)
        if view is None:
            continue
        bad = observed_mismatch(inp, view, pods)
        if bad:
            notes.append(f"round {n}: {bad} pods observed otherwise than "
                         f"the apiserver held them")
        mismatch += bad
        v = reference.check_round(inp, pricing, r["bindings"])
        checked.append(v)
        if v.program is None:
            infeasible += 1
            notes.append(f"round {n}: " + "; ".join(v.infeasible[:3]))
        else:
            gap = max(gap, v.gap)
            if v.gap:
                notes.append(f"round {n}: cost {v.program} against the "
                             f"optimum {v.optimum}")
    if not checked:
        notes.append("no round of the window was checked")
    for v in checked:
        notes.append(f"checked round {v.round_num}: pending={v.pending} "
                     f"placed={v.placed} optimum={v.optimum} "
                     f"program={v.program}")
    checks = {
        "round_cost_gap": (gap if checked else 1, 0),
        "round_infeasible": (infeasible, 0),
        "observed_mismatch": (mismatch, 0),
        "apiserver_overcommit": (report["overcommit"], 0),
        "constraint_violations": (report["constraint_violations"], 0),
        "actuation_mismatch": (actuation, 0),
        "never_bound": (never, 0),
    }
    return ({k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()},
            notes)


def apiserver_views(report: dict, rounds: list[dict]):
    """What each round saw, rebuilt from the apiserver's record alone:
    ``(round, RoundInput)`` for every round with a begin stamp.

    A round is built from the last pod LIST the child served before the
    round began (every pod LIST snapshots the state at a resourceVersion
    S). At S a pod was present if it was added at or before S and not
    deleted by then, and bound if its binding was observable by then.
    The round's pending pods are the present, unbound pods that no
    earlier round decided; its running pods are the bound ones on their
    nodes, and the pods earlier rounds decided but whose bindings were
    not yet observable (the pipelined loop POSTs a round's bindings
    while the next round solves), on the machines decided. A pod's wait
    is the number of earlier rounds it was pending in. Machines carry the
    labels and taints the child served, pending pods their constraints."""
    lists = report["lists"]
    list_t = np.asarray([t for t, _rv in lists])
    keys = [row[0] for row in report["pods"]]
    index = {k: i for i, k in enumerate(keys)}
    inf = np.iinfo(np.int64).max

    def col(j):
        return np.asarray([inf if row[j] is None else row[j]
                           for row in report["pods"]], np.int64)
    added, bound, deleted = col(10), col(11), col(12)
    machines = [(name, rack, cap) for name, rack, cap, *_ in report["nodes"]]
    labels = {row[0]: row[3] for row in report["nodes"] if row[3]}
    taints = {row[0]: row[4] for row in report["nodes"] if row[4]}
    midx = {m: i for i, (m, _r, _c) in enumerate(machines)}
    node = np.asarray([midx.get(row[4], -1) for row in report["pods"]])
    prefs = {row[0]: row[9] for row in report["pods"]}
    cons = {row[0]: row[13] for row in report["pods"] if row[13]}
    decided = np.full(len(keys), -1)      # machine an earlier round chose
    wait = np.zeros(len(keys), np.int64)
    for r in rounds:
        t_begin = r.get("t_view")
        i = int(np.searchsorted(list_t, t_begin)) - 1 \
            if t_begin is not None else -1
        if i >= 0:
            snap = lists[i][1]
            present = (added <= snap) & (snap < deleted)
            seen_bound = present & (bound <= snap)
            pend = present & ~seen_bound & (decided < 0)
            on = np.concatenate([node[seen_bound],
                                 decided[present & ~seen_bound
                                         & (decided >= 0)]])
            count = np.bincount(on[on >= 0], minlength=len(machines))
            pending = [keys[j] for j in np.flatnonzero(pend)]
            yield r, reference.RoundInput(
                round_num=r["stats"]["round_num"], machines=machines,
                running={machines[m][0]: int(c)
                         for m, c in enumerate(count) if c},
                pending=pending,
                waits={k: int(wait[index[k]]) for k in pending},
                prefs={k: prefs[k] for k in pending},
                labels=labels, taints=taints,
                constraints={k: cons[k] for k in pending if k in cons})
            wait[pend] += 1
        for k, m in r["bindings"].items():
            j = index.get(k)
            if j is not None:
                decided[j] = midx.get(m, -1)


def observed_mismatch(inp: reference.RoundInput, view, pods: dict) -> int:
    """Pods the round's program saw otherwise than the apiserver held
    them: pending on one side only, or pending with another request or
    other preferences than the generator made."""
    _t, tasks = view
    seen = {t.uid: t for t in tasks
            if getattr(t.phase, "value", t.phase) == "Pending"}
    want = set(inp.pending)
    bad = len(want.symmetric_difference(seen))
    for k in want & seen.keys():
        t, row = seen[k], pods[k]
        if (round(t.cpu_request * 1000) != row[6]
                or t.memory_request_kb != row[7]
                or dict(t.data_prefs) != row[8]):
            bad += 1
    return bad


def print_window(rec: Recorder, win: Window, report: dict) -> None:
    """What the window was like, on stderr: the backlog's trend, the
    compiles, the drain, and how late the generator ran."""
    lat = latencies_ms(report)
    due = np.asarray([r[1] for r in report["pods"]
                      if report["t0"] <= r[1] < report["t1"]])
    mid = (report["t0"] + report["t1"]) / 2
    halves = [float(np.percentile(x, 50)) if x.size else 0.0
              for x in (lat[due < mid], lat[due >= mid])]
    pend = [r["stats"]["pods_pending"] for r in rec.window_rounds()]
    print(f"backlog: pending_max={max(pend, default=0)} "
          f"bound_per_s={report['bound_in_window'] / (win.t1 - win.t0)} "
          f"p50_first_half_ms={halves[0]} p50_second_half_ms={halves[1]}",
          file=sys.stderr)
    print(f"window: t0={win.t0} seconds={win.t1 - win.t0} "
          f"rounds={len(pend)} compiles_in_window: "
          f"loop={win.compiles_loop} other_threads={win.compiles_other} "
          f"compile_s={win.compile_s} drained={win.drained} warm={win.warm} "
          f"warm_audit={win.audit} "
          f"generator_lateness_max_s={report['lateness_max_s']} "
          f"p99_s={report['lateness_p99_s']} "
          f"waves_in_window={report['waves_in_window']}", file=sys.stderr)
    load = report["window_load"]
    seconds = win.t1 - win.t0
    print(f"apiserver child over the window: cpu_share="
          f"{load['cpu_s'] / seconds} (one core is 1; its threads share "
          f"one interpreter lock) lock_wait_share="
          f"{load['lock_wait_s'] / seconds} posts={load['posts']} "
          f"lock_wait_ms_per_post="
          f"{1000 * load['lock_wait_s'] / max(load['posts'], 1)}",
          file=sys.stderr)


def traced(cell: Cell, rec: Recorder, win: Window, memory: int,
           device: dict, peaks: dict) -> dict:
    """A traced run's per-layer metrics, device busy time and breakdown."""
    trace = trace_reduce.load(trace_reduce.find_xplane(win.trace_dir))
    t_end = win.t0 + win.trace_window_s
    ctx = ReadContext(
        rounds=[r["stats"] for r in rec.window_rounds()],
        before=(rec.round_before_window() or {}).get("stats"),
        trace=trace, trace_window_s=win.trace_window_s,
        trace_rounds=[r["stats"] for r in rec.rounds
                      if win.t0 <= r["t_begin"] < t_end],
        config=cell.config, traffic=cell.traffic, peaks=peaks)
    dev, breakdown = trace_device(trace, cell.chips, win.trace_window_s,
                                  memory, device)
    return {"metrics": per_layer(cell, ctx), "device": dev,
            "breakdown": breakdown}


# ---- main ------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: list[str] | None = None, *, allow_platform: str | None = None,
         plant=None, root: pathlib.Path | None = None) -> int:
    """``allow_platform`` and ``plant`` (a ``faults.Plant``) exist for
    the tests under ``benchmark/tests`` and for ``control.py`` (a CPU
    rehearsal, a fault planted in the timed path); the command line
    never sets them."""
    args = parse(sys.argv[1:] if argv is None else argv)
    # JAX's persistent cache sits in the checkout, whatever the machine
    # sets; JAX reads the variable when it is first imported, just below,
    # and the program's configure_compile_cache takes it from there
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent
                                                  / ".jax_cache")
    try:
        cell = load_cell(root or BENCH.parent, args.workload)
        device = require_chip(cell.chips, allow_platform)
        peaks = device_peaks(device["kind"]) if allow_platform is None \
            else {}
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        from poseidon_tpu.guards import configure_compile_cache
    except ImportError as e:
        print(f"benchmark: the system under test is missing: {e}",
              file=sys.stderr)
        return 2
    cache = configure_compile_cache()
    subprocess.run(["make", "-s", "-C", str(BENCH.parent / "poseidon_tpu"
                                            / "oracle"),
                    "build/mcmf_oracle"], check=True)
    rundir = RUNS / args.workload
    shutil.rmtree(rundir, ignore_errors=True)
    rundir.mkdir(parents=True)
    try:
        win, rec, report, memory = drive(cell, args, rundir, cache, plant)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(rundir / "ckpt", ignore_errors=True)
    print_window(rec, win, report)
    try:
        if args.trace:
            result = traced(cell, rec, win, memory, device, peaks)
        else:
            result = {"metrics": end_to_end(cell, win, report),
                      "device": dict(device, memory_peak_bytes=memory)}
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    t_check = time.monotonic()
    checks, notes = check(cell, rec, report)
    notes.append(f"the reference took {time.monotonic() - t_check:.1f} s")
    for line in notes[-40:]:
        print(f"check: {line}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    # the numbers compared, each beside its limit, stay the line's last key
    print(json.dumps({
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": int(latencies_ms(report).size),
        "failed": checks["never_bound"]["value"],
        **result,
        "checks": checks,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
