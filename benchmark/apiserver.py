"""The apiserver and the traffic generator, in a child process of their own.

Imports the standard library and numpy only, never ``poseidon_tpu`` (whose
``__init__`` imports JAX) or ``jax``: the chip belongs to the parent.

The HTTP surface is a copy of ``poseidon_tpu/apiclient/fake_server.py``
cut to what the daemon uses (node and pod LISTs with ``limit``/
``continue`` paging, the watch protocol, the bindings POST, the single-pod
GET and evictions), with three changes for measuring:

- a LIST's pages are cut from one snapshot taken at its first page, as a
  real apiserver's continue token does; the original pages a list that
  moves under it, which loses or repeats pods;
- every object is serialised once per change, and a LIST or a watch
  stream sends those bytes;
- the server stamps each pod's due time and the arrival of its binding
  on this process's ``time.monotonic()`` clock, and counts bindings that
  land on a node already at its pod capacity, and bindings that land on
  a node the pod's placement constraints exclude (``constraints.py``;
  like a real apiserver, it accepts both);
- it records, per pod, the resourceVersions at which it was added, was
  seen bound and was deleted, and, per pod LIST, the resourceVersion its
  snapshot holds: the reference rebuilds from them what each round saw;
- it measures itself over the window: its CPU seconds, the time its
  requests waited for the state's lock, and its POSTs.

Every pod the generator will create is built (its JSON included) before
it is due, outside the lock, so creating a job or a wave is an insertion.
Every read (a LIST, a GET, a watch stream) first fires whatever is due by
then, so no read misses a pod that was due, however late the generator's
own thread wakes.

Protocol with the parent, one JSON object per line: this process prints
``{"port": p}`` once the cluster is loaded; on ``go`` it warms up and
prints ``{"warm": ...}``; on ``window`` it starts the measured window and
prints ``{"t0": .., "t1": ..}``, then ``{"window_end": ..}`` and, once
every pod due in the window is bound or the drain limit passed,
``{"drained": ..}``; ``report <path>`` writes the record of the run to
``path``; ``exit`` stops the server.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import gc
import heapq
import json
import pathlib
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import constraints  # noqa: E402
import traffic_gen  # noqa: E402

EVENT_RETENTION = 400_000   # watch history kept (a real apiserver keeps
                            # minutes of it; the daemon must never lag
                            # into a 410 because this copy forgot early)
SNAPSHOTS_KEPT = 16         # paged LIST snapshots held for continue tokens
WATCH_IDLE_CLOSE_S = 0.25   # as the original: bookmark + close when idle
WARMUP_LIMIT_S = 600.0      # the longest the warm-up waits for its pods
DRAIN_LIMIT_S = 60.0        # ... and the drain for the window's pods


def _dumps(doc: dict) -> bytes:
    return json.dumps(doc, separators=(",", ":")).encode()


class Pod:
    """One pod, built before it is due; ``Cluster.insert`` creates it."""

    __slots__ = ("key", "spec", "doc", "raw", "due", "created", "bound",
                 "node", "deleted", "wave", "lifetime", "rv_added",
                 "rv_bound", "rv_deleted")

    def __init__(self, spec, wave=-1, node="", lifetime=0.0):
        self.key = f"default/{spec.name}"
        self.spec = spec
        self.due = self.created = self.bound = self.deleted = None
        self.rv_added = self.rv_bound = self.rv_deleted = None
        self.node = node
        self.wave = wave
        self.lifetime = lifetime
        meta = {"name": spec.name, "namespace": "default",
                "labels": {"job-name": spec.job}}
        if spec.prefs:
            meta["annotations"] = {
                "poseidon.io/data-prefs": json.dumps(spec.prefs)}
        con = spec.constraint or {}
        self.doc = {
            "metadata": meta,
            "spec": {
                "containers": [{"resources": {"requests": {
                    "cpu": f"{spec.cpu_milli}m",
                    "memory": f"{spec.memory_kb}Ki",
                }}}],
                **({"nodeSelector": con["node_selector"]}
                   if "node_selector" in con else {}),
                **({"affinity": {"nodeAffinity": con["affinity"]}}
                   if "affinity" in con else {}),
                **({"tolerations": con["tolerations"]}
                   if "tolerations" in con else {}),
                **({"nodeName": node} if node else {}),
            },
            "status": {"phase": "Running" if node else "Pending"},
        }
        self.raw = _dumps(self.doc)


class Cluster:
    """The apiserver's state and the generator's bookkeeping, under one
    lock (``cond``)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 seconds: float):
        self.cond = threading.Condition()
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.kind = traffic["kind"]
        self.nodes = traffic_gen.make_nodes(cfg, seed)
        self.node_of = {n.name: n for n in self.nodes}
        self.node_raw = {n.name: _dumps(self._node_doc(n))
                         for n in self.nodes}
        self.live_bound = collections.Counter()
        self.pods: dict[str, Pod] = {}
        self.gone: list[Pod] = []          # deleted pods, for the record
        self.pending_ops: list[Pod] = []   # bindings not yet observable
        self.rv = 0
        self.events = {"pods": [], "nodes": []}   # (rv, line bytes)
        self.floor = {"pods": 0, "nodes": 0}      # compacted below
        self.snapshots: collections.OrderedDict = collections.OrderedDict()
        self.snap_seq = 0
        self.lists: list[tuple[float, int]] = []  # pod LISTs: (t, rv)
        self.closing = False
        # counters for the record
        self.overcommit = 0
        self.constraint_violations = 0
        self.conflicts = 0
        self.rejected = 0
        self.posts = 0
        self.lock_wait_s = 0.0     # requests' waits for ``cond``
        self.window_load: dict = {}
        # the generator's clock
        self.t0 = None
        self.t1 = None
        self.heap: list = []          # (due, seq, action, payload)
        self.seq = 0
        self.lateness: list[float] = []
        self.factory = traffic_gen.PodFactory(cfg, self.nodes, seed, 4)
        # waves draw from a stream of their own: the next wave is built
        # on a thread of its own while the warm-up still draws pods
        self.wave_factory = traffic_gen.PodFactory(cfg, self.nodes, seed, 6)
        self.wave_builder: tuple[int, threading.Thread, list] | None = None
        self.running_fifo: collections.deque = collections.deque()
        self.wave_left: dict[int, int] = {}
        self.wave_no = 0
        self.waves_in_window = 0
        self.warm_pods: list[str] = []
        self.initial: list[str] = []       # pods running at the start
        self.out_lock = threading.Lock()
        with self.cond:
            for n in self.nodes:
                self._emit("nodes", "ADDED", self.node_raw[n.name])

    # ---- objects --------------------------------------------------------

    @staticmethod
    def _node_doc(n) -> dict:
        res = {"cpu": f"{n.cpu_milli}m", "memory": f"{n.memory_kb}Ki",
               "pods": str(n.pods)}
        return {"metadata": {"name": n.name, "labels": n.all_labels},
                **({"spec": {"taints": list(n.taints)}} if n.taints else {}),
                "status": {"capacity": res, "allocatable": dict(res)}}

    @contextlib.contextmanager
    def locked(self):
        """``cond``, for a request: the wait to take it is counted."""
        t = time.monotonic()
        with self.cond:
            self.lock_wait_s += time.monotonic() - t
            yield

    def _emit(self, kind: str, typ: str, raw: bytes) -> int:
        """Append one watch event (lock held): the object's bytes with
        its resourceVersion spliced into ``metadata``. Returns that
        resourceVersion."""
        self.rv += 1
        obj = raw.replace(b'"metadata":{', b'"metadata":{"resourceVersion":"'
                          + str(self.rv).encode() + b'",', 1)
        line = b'{"type":"' + typ.encode() + b'","object":' + obj + b"}\n"
        log = self.events[kind]
        log.append((self.rv, line))
        if len(log) > EVENT_RETENTION:
            cut = len(log) - EVENT_RETENTION
            self.floor[kind] = log[cut - 1][0]
            del log[:cut]
        self.cond.notify_all()
        return self.rv

    def insert(self, pod: Pod, due: float) -> Pod:
        """Create a built pod (lock held)."""
        pod.due = due
        pod.created = time.monotonic()
        self.pods[pod.key] = pod
        if pod.node:
            self.live_bound[pod.node] += 1
            self.running_fifo.append(pod.key)
            self.initial.append(pod.key)
        self.lateness.append(pod.created - due)
        pod.rv_added = self._emit("pods", "ADDED", pod.raw)
        if pod.node:
            pod.rv_bound = pod.rv_added
        return pod

    def add_pod(self, spec, due: float, *, node: str = "") -> Pod:
        return self.insert(Pod(spec, node=node), due)

    def delete_pod(self, key: str) -> None:
        pod = self.pods.pop(key, None)
        if pod is None:
            return
        pod.deleted = time.monotonic()
        if pod.node:
            self.live_bound[pod.node] -= 1
        self.gone.append(pod)
        pod.rv_deleted = self._emit("pods", "DELETED", pod.raw)

    def apply_pending(self) -> None:
        """Bindings become observable on the next read, in POST order
        (as in the original: acknowledged before observable)."""
        for pod in self.pending_ops:
            if pod.deleted is not None:
                continue
            pod.doc["spec"]["nodeName"] = pod.node
            pod.doc["status"]["phase"] = "Running"
            pod.raw = _dumps(pod.doc)
            pod.rv_bound = self._emit("pods", "MODIFIED", pod.raw)
        self.pending_ops.clear()

    # ---- the bindings POST ------------------------------------------------

    def bind(self, key: str, node: str) -> tuple[int, dict]:
        now = time.monotonic()
        self.posts += 1
        pod = self.pods.get(key)
        if pod is None:
            self.rejected += 1
            return 404, {"error": f"no pod {key}"}
        n = self.node_of.get(node)
        if n is None:
            self.rejected += 1
            return 404, {"error": f"no node {node}"}
        if pod.node:
            self.conflicts += 1
            return 409, {"kind": "Status", "code": 409,
                         "reason": "Conflict",
                         "message": f"pod {key} is already bound to "
                                    f"{pod.node}"}
        if self.live_bound[node] >= n.pods:
            self.overcommit += 1
        # an unconstrained pod fits an untainted node: no test on the POST
        # path, which bounds how fast a wave binds
        if (pod.spec.constraint or n.taints) and not constraints.feasible(
                pod.spec.constraint, n.all_labels, n.taints):
            self.constraint_violations += 1
        self.live_bound[node] += 1
        pod.node = node
        pod.bound = now
        self.pending_ops.append(pod)
        if self.kind == "waves":
            self.running_fifo.append(key)
        if self.kind == "open" and pod.lifetime > 0:
            self._push(now + pod.lifetime, "delete", key)
        if pod.wave >= 0:
            left = self.wave_left[pod.wave] - 1
            self.wave_left[pod.wave] = left
            if left == 0:
                self._push(now, "wave_done", pod.wave)
        # no notify: a binding wakes nobody (the generator wakes for its
        # own actions, a watcher for events, at its idle close at latest)
        return 201, {"status": "Bound"}

    # ---- LIST snapshots ----------------------------------------------------

    def page(self, kind: str, query: dict) -> bytes:
        token = query.get("continue", [""])[0]
        limit = int(query.get("limit", ["0"])[0] or 0)
        if token:
            sid, off = (int(x) for x in token.split(":"))
            snap = self.snapshots.get(sid)
            if snap is None:
                raise KeyError("continue token expired")
            items, rv = snap
        else:
            if kind == "pods":
                items = [p.raw for p in self.pods.values()]
            else:
                items = list(self.node_raw.values())
            rv = self.rv
            off = 0
            sid = self.snap_seq = self.snap_seq + 1
            if kind == "pods":
                self.lists.append((time.monotonic(), rv))
            if limit > 0 and len(items) > limit:
                self.snapshots[sid] = (items, rv)
                while len(self.snapshots) > SNAPSHOTS_KEPT:
                    self.snapshots.popitem(last=False)
        end = len(items) if limit <= 0 else min(off + limit, len(items))
        meta = b'{"resourceVersion":"' + str(rv).encode() + b'"'
        if end < len(items):
            meta += b',"continue":"' + f"{sid}:{end}".encode() + b'"'
        return (b'{"items":[' + b",".join(items[off:end]) + b'],"metadata":'
                + meta + b"}}")

    # ---- the generator -----------------------------------------------------

    def _push(self, due: float, action: str, payload) -> None:
        self.seq += 1
        heapq.heappush(self.heap, (due, self.seq, action, payload))
        if self.heap[0][1] == self.seq:
            self.cond.notify_all()   # earlier than the generator waits for

    def fire_due(self) -> None:
        """Fire every action due by now (lock held)."""
        now = time.monotonic()
        while self.heap and self.heap[0][0] <= now:
            due, _, action, payload = heapq.heappop(self.heap)
            self._fire(due, action, payload)

    def run_generator(self) -> None:
        """Fire every due action on time (one thread; lock held while
        firing); a read fires them too, should this thread wake late."""
        with self.cond:
            while not self.closing:
                self.fire_due()
                wait = (self.heap[0][0] - time.monotonic()) if self.heap \
                    else 0.05
                self.cond.wait(max(min(wait, 0.05), 0.0005))

    def _fire(self, due: float, action: str, payload) -> None:
        if action == "job":
            for pod in payload:
                self.insert(pod, due)
        elif action == "delete":
            self.delete_pod(payload)
        elif action == "wave_done":
            self.wave_left.pop(payload, None)
            if self.t0 is not None and due < self.t1:
                self.submit_wave(due)
        elif action == "window_end":
            self.window_load = {
                "cpu_s": time.process_time() - self.window_load["cpu_s"],
                "lock_wait_s": self.lock_wait_s
                - self.window_load["lock_wait_s"],
                "posts": self.posts - self.window_load["posts"],
            }
            self.say({"window_end": due})

    def say(self, obj: dict) -> None:
        """One line to the parent (any thread)."""
        with self.out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    def submit_wave(self, due: float) -> None:
        """One wave: delete the oldest running pods, create new ones."""
        n = int(self.traffic["wave_pods"])
        gone = 0
        while gone < n and self.running_fifo:
            key = self.running_fifo.popleft()
            if key in self.pods:
                self.delete_pod(key)
                gone += 1
        w = self.wave_no
        self.wave_no += 1
        if self.t0 is not None and due >= self.t0:
            self.waves_in_window += 1
        self.wave_left[w] = n
        for pod in self.built_wave(w):
            self.insert(pod, due)
        self.build_wave(w + 1)

    def build_wave(self, w: int) -> None:
        """Build wave ``w``'s pods on a thread of their own: one job, or
        ``wave_jobs`` jobs of consecutive pods."""
        out: list[Pod] = []
        n = int(self.traffic["wave_pods"])
        parts = int(self.traffic.get("wave_jobs", 1))

        def job(k: int) -> str:
            return f"wave-{w:04d}" if parts == 1 else \
                f"wave-{w:04d}-j{k * parts // n:03d}"

        def build():
            out.extend(Pod(self.wave_factory.make(f"{job(k)}-{k:05d}",
                                                  job(k)), wave=w)
                       for k in range(n))
        th = threading.Thread(target=build, daemon=True)
        th.start()
        self.wave_builder = (w, th, out)

    def built_wave(self, w: int) -> list[Pod]:
        if self.wave_builder is None or self.wave_builder[0] != w:
            self.build_wave(w)
        _w, th, out = self.wave_builder
        th.join()
        self.wave_builder = None
        return out

    # ---- phases (driven by the parent) --------------------------------------

    def populate(self) -> None:
        """The pods running at the start, in memory (not over HTTP)."""
        n = int(self.traffic.get("initial_running", 0))
        where = traffic_gen.initial_placement(self.nodes, n, self.seed)
        built = [Pod(self.factory.make(f"init-{i:06d}", f"init-{i // 8:05d}",
                                       with_prefs=False), node=node)
                 for i, node in enumerate(where)]
        now = time.monotonic()
        with self.cond:
            for pod in built:
                self.insert(pod, now)
            self.lateness.clear()

    def _wait(self, pred, limit_s: float) -> bool:
        end = time.monotonic() + limit_s
        with self.cond:
            while not pred():
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self.cond.wait(min(left, 0.1))
        return True

    def warm_burst(self, n: int) -> None:
        """``n`` pending pods at once, built first: one job, or jobs of
        ``warmup_job_pods`` pods (so a warm-up can carry several of the
        configuration's constraints, which come one per job)."""
        size = int(self.traffic.get("warmup_job_pods", 0))
        built = [Pod(self.factory.make(
            f"warm-{k:05d}", f"warm-{k // size:05d}" if size else "warm"))
            for k in range(n)]
        with self.cond:
            now = time.monotonic()
            for pod in built:
                self.insert(pod, now)
                self.warm_pods.append(pod.key)

    def warm_up(self, limit_s: float = WARMUP_LIMIT_S) -> dict:
        t = self.traffic
        start = time.monotonic()
        if self.kind == "open":
            self.warm_burst(int(t["warmup_pods"]))
            ok = self._wait(lambda: all(self.pods[k].node
                                        for k in self.warm_pods), limit_s)
            with self.cond:
                for k in self.warm_pods:
                    self.delete_pod(k)
        else:
            ok = True
            for _ in range(int(t.get("warmup_waves", 1))):
                with self.cond:
                    self.submit_wave(time.monotonic())
                ok = ok and self._wait(lambda: not self.wave_left, limit_s)
            # a small burst after the wave: a round that starts from the
            # previous round's state, at the shapes the wave grew
            self.warm_burst(int(t.get("warmup_tail_pods", 0)))
            ok = ok and self._wait(lambda: all(self.pods[k].node
                                               for k in self.warm_pods),
                                   limit_s)
        # let the daemon observe the warm-up's end before the window
        time.sleep(float(t.get("settle_s", 1.0)))
        with self.cond:
            self.lateness.clear()
        return {"warm": ok, "warm_s": time.monotonic() - start}

    def start_window(self) -> dict:
        """Open the window: every action of the mix is scheduled from t0.
        The open mix is drawn and built before t0 is read, so building
        it delays no pod."""
        if self.kind == "open":
            jobs = traffic_gen.open_jobs(self.traffic, self.factory,
                                         self.seconds, self.seed)
            mean = traffic_gen.lifetime_mean_s(self.traffic)
            life_rng = traffic_gen.rng_for(self.seed, 5)
            lives = iter(traffic_gen.exponential_set(
                sum(len(j.pods) for j in jobs), mean, life_rng))
            built = [[Pod(spec, lifetime=float(next(lives)))
                      for spec in j.pods] for j in jobs]
        with self.cond:
            t0 = time.monotonic()
            self.t0, self.t1 = t0, t0 + self.seconds
            self.window_load = {"cpu_s": time.process_time(),
                                "lock_wait_s": self.lock_wait_s,
                                "posts": self.posts}
            if self.kind == "open":
                # the pods running at the start leave at memoryless
                # residual lifetimes of the same mean
                init = [k for k in self.initial if k in self.pods]
                res = traffic_gen.exponential_set(len(init), mean, life_rng)
                for k, r in zip(init, res):
                    self._push(t0 + float(r), "delete", k)
                for j, pods in zip(jobs, built):
                    self._push(t0 + j.offset_s, "job", pods)
            else:
                self.submit_wave(t0)
            self._push(self.t1, "window_end", None)
        return {"t0": self.t0, "t1": self.t1}

    def drain(self, limit_s: float = DRAIN_LIMIT_S) -> dict:
        """Wait for the window's end, then for every pod due in it."""
        self._wait(lambda: time.monotonic() >= self.t1, self.seconds + 5)
        with self.cond:
            unbound = [p for p in list(self.pods.values()) + self.gone
                       if self.t0 <= p.due < self.t1 and not p.node]

        def done():
            unbound[:] = [p for p in unbound if not p.node]
            return not unbound
        ok = self._wait(done, limit_s)
        return {"drained": ok, "t": time.monotonic()}

    def report(self) -> dict:
        with self.cond:
            now = time.monotonic()
            pods = []
            for p in list(self.pods.values()) + self.gone:
                s = p.spec
                pods.append([p.key, p.due, p.created, p.bound, p.node,
                             p.deleted, s.job, s.cpu_milli, s.memory_kb,
                             s.prefs, p.rv_added, p.rv_bound, p.rv_deleted,
                             s.constraint])
            late = np.asarray(self.lateness) if self.lateness else \
                np.zeros(1)
            in_window = sum(1 for p in list(self.pods.values()) + self.gone
                            if p.bound is not None
                            and self.t0 <= p.bound < self.t1)
            return {
                "t0": self.t0, "t1": self.t1, "report_t": now,
                "nodes": [[n.name, n.rack, n.pods, n.all_labels,
                           list(n.taints)] for n in self.nodes],
                "pods": pods,
                "overcommit": self.overcommit,
                "constraint_violations": self.constraint_violations,
                "conflicts": self.conflicts,
                "rejected": self.rejected,
                "posts": self.posts,
                "bound_in_window": in_window,
                "waves_in_window": self.waves_in_window,
                "lateness_max_s": float(late.max()),
                "lateness_p99_s": float(np.percentile(late, 99)),
                "lists": self.lists,
                "window_load": self.window_load,
            }


def make_handler(cl: Cluster):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, code: int, payload: bytes) -> None:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def do_GET(self):
            url = urlparse(self.path)
            query = parse_qs(url.query)
            kind = url.path.rsplit("/", 1)[-1]
            if (query.get("watch", ["false"])[0] == "true"
                    and url.path in ("/api/v1/nodes", "/api/v1/pods")):
                try:
                    self._watch(kind, query)
                except (OSError, ValueError):
                    pass  # the client went away mid-stream
                return
            parts = url.path.strip("/").split("/")
            with cl.locked():
                cl.fire_due()
                if url.path in ("/api/v1/nodes", "/api/v1/pods"):
                    if kind == "pods":
                        cl.apply_pending()
                    try:
                        payload = cl.page(kind, query)
                    except KeyError:
                        payload = None
                elif (len(parts) == 6 and parts[2] == "namespaces"
                      and parts[4] == "pods"):
                    cl.apply_pending()
                    pod = cl.pods.get(f"{parts[3]}/{parts[5]}")
                    if pod is None:
                        self._send(404, b'{"error":"no pod"}')
                        return
                    payload = pod.raw
                else:
                    self._send(404, b'{"error":"not served"}')
                    return
            if payload is None:
                self._send(410, _dumps({"kind": "Status", "code": 410,
                                        "reason": "Expired"}))
                return
            self._send(200, payload)

        def _chunk(self, data: bytes) -> None:
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")

        def _watch(self, kind: str, query: dict) -> None:
            rv = int(query.get("resourceVersion", ["0"])[0] or 0)
            with cl.cond:
                gone = rv < cl.floor[kind]
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            if gone:
                self._chunk(b'{"type":"ERROR","object":{"kind":"Status",'
                            b'"code":410,"reason":"Expired"}}\n')
                self._chunk(b"")
                return
            while True:
                with cl.cond:
                    if cl.closing:
                        break
                    cl.fire_due()
                    if kind == "pods":
                        cl.apply_pending()
                    log = cl.events[kind]
                    i = bisect.bisect_right(log, rv, key=lambda e: e[0])
                    if i == len(log):
                        cl.cond.wait(WATCH_IDLE_CLOSE_S)
                        if cl.closing:
                            break
                        cl.fire_due()
                        if kind == "pods":
                            cl.apply_pending()
                        i = bisect.bisect_right(log, rv, key=lambda e: e[0])
                    batch = log[i:]
                    cur = cl.rv
                if batch:
                    rv = batch[-1][0]
                    self._chunk(b"".join(line for _, line in batch))
                    self.wfile.flush()
                    continue
                self._chunk(b'{"type":"BOOKMARK","object":{"kind":"'
                            + kind.encode()
                            + b'","metadata":{"resourceVersion":"'
                            + str(cur).encode() + b'"}}}\n')
                break
            self._chunk(b"")

        def do_POST(self):
            url = urlparse(self.path)
            parts = url.path.strip("/").split("/")
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
            if len(parts) == 5 and parts[2] == "namespaces" \
                    and parts[4] == "bindings":
                key = f"{parts[3]}/{body['metadata']['name']}"
                with cl.locked():
                    code, doc = cl.bind(key, body["target"]["name"])
                self._send(code, _dumps(doc))
            elif (len(parts) == 7 and parts[2] == "namespaces"
                  and parts[4] == "pods" and parts[6] == "eviction"):
                # the configurations here run place-only (no
                # preemption), so an eviction is a fault of the run
                with cl.cond:
                    cl.rejected += 1
                self._send(405, b'{"error":"evictions are not expected"}')
            else:
                self._send(404, b'{"error":"not served"}')

    return Handler


class _QuietServer(ThreadingHTTPServer):
    daemon_threads = True
    request_queue_size = 256

    def handle_error(self, request, client_address):
        exc = sys.exc_info()[1]
        if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
            return
        super().handle_error(request, client_address)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    # a full collection walks every pod's objects and stalls every
    # thread for up to a second; nothing here makes reference cycles
    gc.disable()
    cfg = traffic_gen.load_json(args.config)
    traffic = traffic_gen.load_json(args.traffic)
    traffic_gen.check_config(cfg)
    cl = Cluster(cfg, traffic, args.seed, args.seconds)
    cl.populate()
    httpd = _QuietServer(("127.0.0.1", 0), make_handler(cl))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    threading.Thread(target=cl.run_generator, daemon=True).start()
    cl.say({"port": httpd.server_address[1]})
    try:
        for line in sys.stdin:
            cmd = line.split()
            if not cmd:
                continue
            if cmd[0] == "go":
                out = cl.warm_up()
            elif cmd[0] == "window":
                cl.say(cl.start_window())
                out = cl.drain()
            elif cmd[0] == "report":
                with open(cmd[1], "w") as fh:
                    json.dump(cl.report(), fh)
                out = {"reported": cmd[1]}
            elif cmd[0] == "exit":
                break
            else:
                out = {"error": f"unknown command {cmd[0]!r}"}
            cl.say(out)
    finally:
        with cl.cond:
            cl.closing = True
            cl.cond.notify_all()
        httpd.shutdown()
        httpd.server_close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
