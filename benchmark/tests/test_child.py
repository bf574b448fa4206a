"""The apiserver and generator child: it imports no JAX and nothing of
the program, and a pod's latency runs from its due time, not from the
moment a late generator got round to creating it."""

import json
import pathlib
import subprocess
import sys
import time

import apiserver
import run

BENCH = pathlib.Path(__file__).resolve().parents[1]

CFG = {
    "cluster": {"nodes": 4, "machines_per_rack": 2, "max_tasks_per_pu": 2,
                "node_cpu_milli": [1000], "node_memory_kb": [1048576]},
    "pods": {"cpu_milli": [100], "memory_kb": [1024], "machine_prefs": 1,
             "machine_pref_weight": [20, 200], "rack_pref_share": 0.5,
             "rack_pref_weight": [10, 100]},
}
OPEN = {"kind": "open", "rate_pods_per_s": 20,
        "job_size": {"alpha": 1.1, "min": 1, "max": 3},
        "initial_running": 2, "occupancy_target_pods": 4,
        "warmup_pods": 2, "settle_s": 0.0}


def test_child_imports_no_jax_and_no_program():
    code = (
        "import runpy, sys; sys.argv = ['apiserver.py', '--help'];\n"
        "try:\n    runpy.run_path(sys.argv[0] if False else "
        f"{str(BENCH / 'apiserver.py')!r}, run_name='__main__')\n"
        "except SystemExit:\n    pass\n"
        "bad = sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'poseidon_tpu'))\n"
        "print(bad)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=BENCH, check=True).stdout
    assert out.strip().splitlines()[-1] == "[]"


def test_latency_runs_from_the_due_time():
    cl = apiserver.Cluster(CFG, OPEN, seed=5, seconds=1.0)
    cl.populate()
    with cl.cond:
        win = cl.start_window()
        # the generator wakes late: fire the first job 0.2 s after due
        due, _, action, payload = min(
            e for e in cl.heap if e[2] == "job")
        cl.heap.remove((due, _, action, payload))
    time.sleep(max(0.0, due + 0.2 - time.monotonic()))
    with cl.cond:
        cl._fire(due, action, payload)
        key = payload[0].key
        code, _doc = cl.bind(key, cl.nodes[0].name)
    assert code == 201
    rep = cl.report()
    row = next(r for r in rep["pods"] if r[0] == key)
    _k, due_t, created_t, bound_t = row[:4]
    assert due_t == due and created_t >= due + 0.2
    assert rep["lateness_max_s"] >= 0.2
    lat = dict(zip([r[0] for r in rep["pods"]
                    if win["t0"] <= r[1] < win["t1"]],
                   run.latencies_ms(rep)))
    assert lat[key] == (bound_t - due_t) * 1000.0
    assert lat[key] >= 200.0


def test_unbound_pods_count_with_the_latency_reached():
    rep = {"t0": 0.0, "t1": 10.0, "report_t": 30.0,
           "pods": [["a", 1.0, 1.0, 2.0, "m"], ["b", 5.0, 5.0, None, ""],
                    ["c", 11.0, 11.0, None, ""]]}
    assert list(run.latencies_ms(rep)) == [1000.0, 25000.0]


def test_lists_page_from_one_snapshot():
    cl = apiserver.Cluster(CFG, OPEN, seed=5, seconds=1.0)
    cl.populate()
    with cl.cond:
        for k in range(6):
            cl.add_pod(cl.factory.make(f"x-{k}", "x"), time.monotonic())
        first = json.loads(cl.page("pods", {"limit": ["3"]}))
        # the list moves under the pager: two pods go, one comes
        for key in list(cl.pods)[:2]:
            cl.delete_pod(key)
        cl.add_pod(cl.factory.make("late", "x"), time.monotonic())
        rest = []
        token = first["metadata"]["continue"]
        while token:
            doc = json.loads(cl.page("pods", {"limit": ["3"],
                                              "continue": [token]}))
            rest += doc["items"]
            token = doc["metadata"].get("continue", "")
    names = [i["metadata"]["name"] for i in first["items"] + rest]
    assert len(names) == 8 and len(set(names)) == 8 and "late" not in names


def test_binding_to_a_full_node_is_counted():
    cl = apiserver.Cluster(CFG, OPEN, seed=5, seconds=1.0)
    with cl.cond:
        keys = []
        for k in range(3):
            spec = cl.factory.make(f"y-{k}", "y")
            cl.add_pod(spec, time.monotonic())
            keys.append(f"default/{spec.name}")
        node = cl.nodes[0].name
        assert [cl.bind(k, node)[0] for k in keys] == [201, 201, 201]
        assert cl.bind(keys[0], node)[0] == 409
    assert cl.overcommit == 1 and cl.conflicts == 1


def test_every_seed_offers_the_same_jobs_spread_over_the_window():
    import traffic_gen

    mix = {"kind": "open", "rate_pods_per_s": 220, "strata": 10,
           "job_size": {"alpha": 1.1, "min": 1, "max": 2000}}
    nodes = traffic_gen.make_nodes(CFG, 1)
    runs = {}
    for seed in (2147484001, 2147484002):
        factory = traffic_gen.PodFactory(CFG, nodes, seed, 4)
        runs[seed] = traffic_gen.open_jobs(mix, factory, 51.0, seed)
    a, b = runs.values()
    assert sorted(len(j.pods) for j in a) == sorted(len(j.pods) for j in b)
    assert sorted(j.offset_s for j in a) != sorted(j.offset_s for j in b)
    for jobs in (a, b):
        sizes = [len(j.pods) for j in jobs]
        top = sorted(range(len(jobs)), key=lambda i: -sizes[i])[:10]
        # the ten largest jobs fall one to each tenth of the arrivals
        assert sorted(i * 10 // len(jobs) for i in top) == list(range(10))


def test_a_read_sees_every_pod_due_without_the_generator_thread():
    """A LIST fires what is due first: however late the generator's own
    thread wakes (here it never runs), no read misses a due pod."""
    import http.client
    import threading

    cl = apiserver.Cluster(CFG, OPEN, seed=5, seconds=1.0)
    cl.populate()
    httpd = apiserver._QuietServer(("127.0.0.1", 0),
                                   apiserver.make_handler(cl))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        cl.start_window()
        with cl.cond:
            due = min(e[0] for e in cl.heap if e[2] == "job")
            first = next(e[3] for e in cl.heap if e[0] == due)
        time.sleep(max(0.0, due - time.monotonic()) + 0.05)
        conn = http.client.HTTPConnection("127.0.0.1",
                                          httpd.server_address[1])
        conn.request("GET", "/api/v1/pods")
        names = {i["metadata"]["name"]
                 for i in json.loads(conn.getresponse().read())["items"]}
        assert {p.spec.name for p in first} <= names
    finally:
        httpd.shutdown()
        httpd.server_close()
