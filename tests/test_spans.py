"""Program spans (obs/spans.py): the ``poseidon/`` profiler annotations
around the daemon's host phases, the stats fields they feed, and the
obs server's ``/debug/profile`` capture."""

import glob
import pathlib
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from poseidon_tpu.bridge import SchedulerBridge, SchedulerStats
from poseidon_tpu.cluster import Machine, Task
from poseidon_tpu.obs import HealthState, MetricsRegistry, ObsServer
from poseidon_tpu.obs.spans import span

REPO = pathlib.Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _get(port, path, timeout=30.0):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _host_lines(trace_dir):
    """{line id: [event names]} of the host plane of the one capture
    under ``trace_dir``."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                out[(i, line.name)] = [ev.name for ev in line.events]
    return out


def test_span_adds_its_duration_to_the_field_and_nests():
    s = SchedulerStats()
    timings = {}
    with span("outer", s, "build_ms", round=3) as outer:
        with span("inner", timings, "upload_ms") as inner:
            time.sleep(0.02)
        with span("inner", timings, "upload_ms"):
            time.sleep(0.01)
    # the field is the span's own measurement, summed over entries
    assert s.build_ms == outer.ms
    assert timings["upload_ms"] >= 30.0
    assert inner.ms >= 20.0
    assert outer.ms >= timings["upload_ms"]
    # no stats: only the annotation and the measurement
    with span("bare") as bare:
        pass
    assert bare.ms >= 0.0
    # an exception inside still closes the span and charges the field
    with pytest.raises(ValueError):
        with span("fails", s, "dispatch_ms"):
            raise ValueError("boom")
    assert s.dispatch_ms > 0.0


@pytest.fixture(scope="module")
def profiled_daemon(tmp_path_factory):
    """Four poll rounds of ``run_loop`` against the fake apiserver
    inside one profiler session (CPU backend). The cluster holds 12
    seats for 14 pods; after each round the hook completes two running
    pods and submits two new ones, so some pods wait a round. Every
    round after the first carries in its stats the POSTs made since the
    previous round's stats: that round's bindings, POSTed after its
    join in its own tick."""
    from poseidon_tpu.apiclient import FakeApiServer
    from poseidon_tpu.cli import parse_args, run_loop

    trace_dir = tmp_path_factory.mktemp("profile")
    out = {"stats": [], "bindings": []}
    with FakeApiServer() as server:
        for i in range(3):
            server.add_node(f"n{i}", cpu="8", memory="16Gi", pods=4)
        for j in range(14):
            server.add_pod(f"pod-{j:02d}", cpu="250m", memory="256Mi",
                           job=f"job{j // 7}")
        nxt = [14]

        def hook(_n, result, bridge):
            out["stats"].append(result.stats)
            out["bindings"].append(dict(result.bindings))
            out["bridge"] = bridge
            # complete two pods whose bindings the apiserver holds
            done = out.setdefault("done", set())
            for key in [k for k, _m in server.bindings
                        if k not in done][:2]:
                server.succeed_pod(key)
                done.add(key)
            for _ in range(2):
                server.add_pod(f"pod-{nxt[0]:02d}", cpu="250m",
                               memory="256Mi", job="job9")
                nxt[0] += 1

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            rc = run_loop(parse_args([
                "--k8s_apiserver_host=127.0.0.1",
                f"--k8s_apiserver_port={server.port}",
                "--flow_scheduling_cost_model=quincy",
                "--polling_frequency=20000",
                "--max_rounds=4",
            ]), round_hook=hook)
        finally:
            jax.profiler.stop_trace()
        out["posted"] = len(server.bindings)
    out["rc"] = rc
    out["lines"] = _host_lines(trace_dir)
    return out


def test_profiled_pipelined_rounds_name_every_phase(profiled_daemon):
    assert profiled_daemon["rc"] == 0
    lines = profiled_daemon["lines"]
    names = {n for evs in lines.values() for n in evs}
    for want in ("poseidon/tick", "poseidon/list", "poseidon/list_page",
                 "poseidon/observe", "poseidon/begin", "poseidon/build",
                 "poseidon/dispatch", "poseidon/finish",
                 "poseidon/apply", "poseidon/round_done",
                 "poseidon/post_bindings", "poseidon/tick_sleep"):
        assert want in names, f"{want} missing from the host plane"
    # the main thread holds the phases; each POST is on a pool worker
    main = [k for k, evs in lines.items() if "poseidon/tick" in evs]
    assert len(main) == 1
    assert "poseidon/post_bindings" in lines[main[0]]
    assert "poseidon/bind_post" not in lines[main[0]]
    workers = [k for k, evs in lines.items()
               if "poseidon/bind_post" in evs]
    assert workers and main[0] not in workers


def test_profiled_rounds_fill_the_span_fields(profiled_daemon):
    stats = profiled_daemon["stats"]
    bridge = profiled_daemon["bridge"]
    placed = [s for s in stats if s.pods_pending > 0]
    assert placed
    assert all(s.list_ms > 0.0 for s in stats)
    assert all(s.finish_ms > 0.0 for s in placed)
    # every POST is counted once: in the stats emitted after it, or
    # still in the bridge's open window when the loop stopped
    posts = sum(s.posts for s in stats) + bridge.pending_posts
    assert posts == profiled_daemon["posted"]
    assert posts == sum(len(b) for b in profiled_daemon["bindings"])
    assert any(s.posts > 0 and s.post_ms > 0.0 for s in stats)
    assert sum(s.placed_wait_rounds for s in stats) > 0
    for s in stats:
        assert s.placed_wait_rounds <= s.pods_placed * s.round_num


def test_placed_wait_rounds_sums_the_waits_of_placed_pods():
    """One seat, three pods: each round places one, the others age, so
    the round that places a pod that waited k rounds adds k."""
    bridge = SchedulerBridge(cost_model="quincy", small_to_oracle=False)
    bridge.observe_nodes([Machine(name="m0", cpu_capacity=4.0,
                                  cpu_allocatable=4.0,
                                  memory_capacity_kb=1 << 22,
                                  memory_allocatable_kb=1 << 22,
                                  max_tasks=1)])
    bridge.observe_pods([Task(uid=f"p{i}", cpu_request=0.1,
                              memory_request_kb=64) for i in range(3)])
    waits = []
    for _ in range(3):
        res = bridge.run_scheduler()
        assert res.stats.pods_placed == 1
        waits.append(res.stats.placed_wait_rounds)
        (uid, machine), = res.bindings.items()
        bridge.confirm_binding(uid, machine)
        bridge.observe_pod_event("DELETED", bridge.tasks[uid])
    assert waits == [0, 1, 2]


def test_debug_profile_writes_a_trace_and_refuses_a_second(tmp_path):
    with ObsServer(MetricsRegistry(), HealthState(), port=0,
                   host="127.0.0.1", profile_dir=str(tmp_path)) as srv:
        first = {}

        def capture():
            first["resp"] = _get(srv.port, "/debug/profile?seconds=1.5")

        t = threading.Thread(target=capture)
        t.start()
        deadline = time.monotonic() + 10.0
        while not srv._profiling and time.monotonic() < deadline:
            time.sleep(0.01)
        code, body = _get(srv.port, "/debug/profile?seconds=1")
        assert code == 409 and "already running" in body
        t.join(timeout=30.0)
        code, body = first["resp"]
        assert code == 200
        path = body.strip()
        assert path.startswith(str(tmp_path))
        assert glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
        # a session someone else holds in the process is refused too
        jax.profiler.start_trace(str(tmp_path / "other"))
        try:
            code, body = _get(srv.port, "/debug/profile?seconds=0.1")
        finally:
            jax.profiler.stop_trace()
        assert code == 409
    # no directory, no endpoint
    with ObsServer(MetricsRegistry(), HealthState(), port=0,
                   host="127.0.0.1") as srv:
        assert _get(srv.port, "/debug/profile")[0] == 404


def test_span_event_type_is_gone_and_the_surface_stays_consistent(
        tmp_path):
    from poseidon_tpu.analysis import analyze_tree
    from poseidon_tpu.trace import EVENT_TYPES, TraceGenerator

    assert "SPAN" not in EVENT_TYPES
    with pytest.raises(ValueError, match="PTA005"):
        TraceGenerator().emit("SPAN", round_num=1)
    violations, _ = analyze_tree(REPO)
    assert [v for v in violations if v.code == "PTA005"] == []
    # an emit of the retired type is flagged statically as well
    (tmp_path / "poseidon_tpu").mkdir()
    vocab = tmp_path / "poseidon_tpu/trace.py"
    vocab.write_text((REPO / "poseidon_tpu/trace.py").read_text())
    bad = tmp_path / "poseidon_tpu/emitter.py"
    bad.write_text("def go(self):\n    self.trace.emit(\"SPAN\")\n")
    violations, _ = analyze_tree(tmp_path, [vocab, bad])
    assert [v.code for v in violations] == ["PTA005"]

