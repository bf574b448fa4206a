"""Tests for the contract linter (poseidon_tpu/analysis).

Per-rule known-bad/known-good snippet pairs, the suppression contract
(a reason is mandatory), the self-check (the shipped tree is
violation-free), and the acceptance injections: seeding a ``.item()``
into the real ``ops/resident.py`` or an unlocked cross-thread mutation
into the real ``bridge/bridge.py`` must make the analyzer (and so CI)
fail.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

from poseidon_tpu.analysis import DEFAULT_CONTRACTS, analyze_tree
from poseidon_tpu.analysis.contracts import Contracts, ThreadContract

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_on(tmp_path, files, contracts=DEFAULT_CONTRACTS):
    """Write a snippet tree under tmp_path and analyze it."""
    paths = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        if rel.endswith(".py"):
            paths.append(p)
    violations, _ = analyze_tree(tmp_path, paths, contracts)
    return violations


def codes(violations):
    return [v.code for v in violations]


class TestPTA001HostSync:
    # the suffix puts the snippet in a declared whole-file hot scope
    HOT = "poseidon_tpu/ops/resident.py"

    def test_bad_syncs_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            import jax
            import jax.numpy as jnp
            import numpy as np

            def round_step(x):
                v = x.item()
                h = np.asarray(x)
                g = jax.device_get(x)
                x.block_until_ready()
                cost = jnp.add(x, 1)
                s = int(cost)
                return v, h, g, s
        """})
        assert codes(vs) == ["PTA001"] * 5

    def test_good_host_code_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            import numpy as np

            def round_step(asg_np, T):
                # int()/np ops on host data do not sync
                asg = np.where(asg_np >= 0, asg_np, -1)
                return int(T), asg
        """})
        assert vs == []

    def test_device_get_is_a_taint_barrier(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            import jax
            import jax.numpy as jnp

            def round_step(x):
                cost = jnp.add(x, 1)
                host = jax.device_get(cost)  # noqa: PTA001 -- test fixture: the sanctioned fetch
                return int(host)             # host data: no second sync
        """})
        assert vs == []

    def test_out_of_scope_file_not_checked(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/somewhere_else.py": """\
            def f(x):
                return x.item()
        """})
        assert vs == []


class TestPTA002ClusterLoops:
    BRIDGE = "poseidon_tpu/bridge/bridge.py"

    def test_loop_in_scope_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.BRIDGE: """\
            class SchedulerBridge:
                def begin_round(self):
                    n = 0
                    for t in self.tasks:
                        n += 1
                    return n
        """})
        assert codes(vs) == ["PTA002"]

    def test_genexp_over_cluster_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.BRIDGE: """\
            class SchedulerBridge:
                def begin_round(self, cluster):
                    return any(t.live for t in cluster.tasks)
        """})
        assert codes(vs) == ["PTA002"]

    def test_churn_loop_and_out_of_scope_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.BRIDGE: """\
            class SchedulerBridge:
                def begin_round(self, dset):
                    for d in dset.place:   # O(churn): this round's deltas
                        self.apply(d)

                def observe_nodes(self, nodes):
                    for n in nodes:        # the poll path is O(cluster) by design
                        self.upsert(n)
        """})
        assert vs == []


class TestPTA003JitHygiene:
    def test_inline_jit_flagged(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax

            def price(model, x):
                return jax.jit(model)(x)
        """})
        assert codes(vs) == ["PTA003"]

    def test_mutable_static_default_and_unknown_name(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("opts", "zzz"))
            def f(x, opts=[]):
                return x
        """})
        assert sorted(codes(vs)) == ["PTA003", "PTA003"]
        msgs = " | ".join(v.message for v in vs)
        assert "mutable default" in msgs and "'zzz'" in msgs

    def test_nested_jit_closure_capture(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax

            def outer(k):
                @jax.jit
                def inner(x):
                    return x + k
                return inner
        """})
        msgs = " | ".join(v.message for v in vs)
        assert codes(vs) == ["PTA003", "PTA003"]
        assert "defined inside a function" in msgs
        assert "closes over 'k'" in msgs

    def test_module_level_jit_clean(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("n",))
            def f(x, n=4):
                return x * n

            _g = jax.jit(lambda x: x + 1)
        """})
        assert vs == []


class TestPTA004LockDiscipline:
    # SchedulerBridge is a declared thread class in the default contracts
    ANY = "poseidon_tpu/bridge/bridge.py"

    BAD = """\
        class SchedulerBridge:
            def __init__(self):
                self.round_num = 0

            def bump(self):
                self.round_num += 1

            def poll(self):  # pta: background-thread
                self.round_num += 1
    """

    def test_unlocked_cross_thread_write_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: self.BAD})
        # PTA004 flags both unlocked sites; the whole-program lockset
        # pass (PTA006) independently reports the attribute race
        assert set(codes(vs)) == {"PTA004", "PTA006"}
        assert codes(vs).count("PTA004") == 2
        assert codes(vs).count("PTA006") == 1

    def test_locked_sites_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: """\
            class SchedulerBridge:
                def __init__(self):
                    self.round_num = 0

                def bump(self):
                    with self._lock:
                        self.round_num += 1

                def poll(self):  # pta: background-thread
                    with self._lock:
                        self.round_num += 1
        """})
        assert vs == []

    def test_declared_handoff_clean(self, tmp_path):
        contracts = Contracts(
            thread_classes={
                "SchedulerBridge": ThreadContract(
                    handoffs={"round_num": "test: monotonic counter"}
                ),
            },
        )
        vs = run_on(tmp_path, {self.ANY: self.BAD}, contracts)
        assert vs == []

    def test_single_thread_class_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: """\
            class SchedulerBridge:
                def __init__(self):
                    self.round_num = 0

                def bump(self):
                    self.round_num += 1   # main thread only: fine
        """})
        assert vs == []


class TestPTA005Surface:
    def test_undeclared_and_dynamic_event_flagged(self, tmp_path):
        vs = run_on(tmp_path, {
            "poseidon_tpu/trace.py": """\
                EVENT_TYPES = frozenset({"ROUND", "SCHEDULE"})
            """,
            "poseidon_tpu/other.py": """\
                class T:
                    def go(self, name):
                        self.trace.emit("ROUND")
                        self.trace.emit("BOGUS")
                        self.trace.emit(name)
            """,
        })
        assert codes(vs) == ["PTA005", "PTA005"]
        msgs = " | ".join(v.message for v in vs)
        assert "BOGUS" in msgs and "dynamic" in msgs

    def test_undeclared_batch_event_flagged(self, tmp_path):
        vs = run_on(tmp_path, {
            "poseidon_tpu/trace.py": """\
                EVENT_TYPES = frozenset({"SUBMIT", "SCHEDULE"})
            """,
            "poseidon_tpu/other.py": """\
                class T:
                    def go(self, rows, name):
                        self.trace.emit_many("SCHEDULE", rows, 1)
                        self.trace.emit_many("PLACED", rows, 1)
                        self.trace.emit_many(name, rows, 1)
            """,
        })
        assert codes(vs) == ["PTA005", "PTA005"]
        msgs = " | ".join(v.message for v in vs)
        assert "PLACED" in msgs and "dynamic" in msgs

    def test_missing_vocab_flagged(self, tmp_path):
        vs = run_on(tmp_path, {"poseidon_tpu/trace.py": """\
            def emit(x):
                pass
        """})
        assert codes(vs) == ["PTA005"]

    def test_undocumented_flag_flagged(self, tmp_path):
        files = {
            "poseidon_tpu/cli.py": """\
                import argparse

                def build_parser():
                    p = argparse.ArgumentParser()
                    p.add_argument("--alpha", type=int)
                    p.add_argument("--hidden", help=argparse.SUPPRESS)
                    return p
            """,
            "README.md": "docs mention --alpha here\n",
            "deploy/poseidon-tpu.cfg": "# no flags here\n",
        }
        vs = run_on(tmp_path, files)
        assert codes(vs) == ["PTA005"]
        assert "--alpha" in vs[0].message
        assert "deploy/poseidon-tpu.cfg" in vs[0].message
        # hidden (SUPPRESS) flags are exempt; documenting --alpha fixes it
        files["deploy/poseidon-tpu.cfg"] = "--alpha=3\n"
        assert run_on(tmp_path, files) == []

    def test_flag_name_prefix_does_not_count(self, tmp_path):
        # "--watch_max_lag" in a doc must NOT satisfy "--watch"
        vs = run_on(tmp_path, {
            "poseidon_tpu/cli.py": """\
                import argparse

                def build_parser():
                    p = argparse.ArgumentParser()
                    p.add_argument("--watch")
                    return p
            """,
            "README.md": "only --watch_max_lag is named\n",
            "deploy/poseidon-tpu.cfg": "--watch=false\n",
        })
        assert codes(vs) == ["PTA005"]
        assert "README.md" in vs[0].message


class TestPTA006LocksetRaces:
    """The whole-program lockset race detector (analysis/threads.py)."""

    ANY = "poseidon_tpu/pkg/mod.py"  # outside every PTA001/002 scope

    def test_spawn_site_inference_without_marker(self, tmp_path):
        """A Thread(target=self.m) spawn makes m background even with
        NO marker — the case PTA004's marker discipline cannot see."""
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class Pump:
                def start(self):
                    self._t = threading.Thread(target=self._drain)
                    self._t.start()

                def _drain(self):
                    self.pending += 1

                def feed(self):
                    self.pending += 1
        """})
        assert codes(vs) == ["PTA006"]
        assert "Pump.pending" in vs[0].message
        assert "ThreadContract" in vs[0].message  # undeclared class

    def test_thread_subclass_run_is_background(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class Stream(threading.Thread):
                def run(self):
                    self.beat = 1.0

                def lag(self):
                    return self.beat
        """})
        # write on the reader thread, read on main, no lock, no handoff
        assert "beat" not in "".join(
            v.message for v in vs if v.code != "PTA006"
        )
        assert [v.code for v in vs] == ["PTA006"]

    def test_call_graph_closure_from_root(self, tmp_path):
        """An unmarked helper reached via self-calls from a background
        root inherits the background domain."""
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class W:
                def go(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    self._step()

                def _step(self):
                    self.count += 1

                def snapshot(self):
                    return self.count

                def reset(self):
                    self.count = 0
        """})
        assert [v.code for v in vs] == ["PTA006"]
        assert "W.count" in vs[0].message

    def test_wrapper_lambda_is_background(self, tmp_path):
        """A lambda handed to a declared spawn wrapper runs on its
        thread: touching self state from it is a cross-thread access."""
        vs = run_on(tmp_path, {self.ANY: """\
            from poseidon_tpu.ops.resident import _AsyncFetch

            class Solver:
                def dispatch(self):
                    self._warm = object()
                    return _AsyncFetch(lambda: self._warm)
        """})
        assert [v.code for v in vs] == ["PTA006"]
        assert "Solver._warm" in vs[0].message

    def test_cross_class_typed_access_seen(self, tmp_path):
        """The _WatchStream pattern: the owning class reads a stream
        attribute on the main thread through a typed container while
        the reader thread writes it."""
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class Stream(threading.Thread):
                def run(self):
                    self.beat = 1.0

            class Owner:
                def __init__(self):
                    self._streams: dict[str, Stream] = {}

                def tick(self):
                    for name, s in self._streams.items():
                        if s.beat > 3:
                            return name
        """})
        assert [v.code for v in vs] == ["PTA006"]
        assert "Stream.beat" in vs[0].message

    def test_wrapper_lambda_in_init_not_exempt(self, tmp_path):
        """__init__'s construction exemption must not cover a
        background context __init__ itself creates: a state-touching
        lambda handed to a spawn wrapper races every later main-thread
        access (review regression)."""
        vs = run_on(tmp_path, {self.ANY: """\
            from poseidon_tpu.ops.resident import _AsyncFetch

            class Solver:
                def __init__(self):
                    self._warm = None
                    self._f = _AsyncFetch(lambda: self._warm)

                def finish(self):
                    self._warm = object()
        """})
        assert [v.code for v in vs] == ["PTA006"]
        assert "Solver._warm" in vs[0].message

    def test_common_lock_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class Pump:
                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._lock:
                        self.pending += 1

                def feed(self):
                    with self._lock:
                        self.pending += 1
        """})
        assert vs == []

    def test_subscript_store_counts_as_write(self, tmp_path):
        """``self.d[k] = v`` mutates the mapping: a write for race
        purposes (the metrics-registry pattern)."""
        vs = run_on(tmp_path, {self.ANY: """\
            import threading

            class Registry:
                def register(self, k, v):
                    self._metrics[k] = v

                def render(self):  # pta: background-thread
                    return list(self._metrics)
        """})
        assert [v.code for v in vs] == ["PTA006"]
        assert "Registry._metrics" in vs[0].message

    def test_stale_handoff_flagged(self, tmp_path):
        from poseidon_tpu.analysis.contracts import (
            Contracts,
            ThreadContract,
        )

        contracts = Contracts(
            thread_classes={
                "Pump": ThreadContract(handoffs={
                    "ghost": "supposedly cross-thread",
                }),
            },
        )
        vs = run_on(tmp_path, {self.ANY: """\
            class Pump:
                def feed(self):
                    self.pending = 1
        """}, contracts)
        assert [v.code for v in vs] == ["PTA006"]
        assert "stale handoff" in vs[0].message
        assert "ghost" in vs[0].message

    def test_tests_dir_is_not_race_evidence(self, tmp_path):
        """Evidence scoping: a test poking privates on the main thread
        must neither fabricate a race nor keep a stale handoff alive —
        tests/ files are excluded from the access map entirely."""
        from poseidon_tpu.analysis.contracts import (
            Contracts,
            ThreadContract,
        )

        files = {
            self.ANY: """\
                import threading

                class Pump(threading.Thread):
                    def run(self):
                        self.beat = 1.0
            """,
            # the ONLY main-thread accessor lives in a test file
            "tests/test_pump.py": """\
                def test_poke(p: "Pump"):
                    assert p.beat > 0
            """,
        }
        contracts = Contracts(
            thread_classes={
                "Pump": ThreadContract(handoffs={
                    "beat": "claimed cross-thread (only a test reads it)",
                }),
            },
            path_rules=(("tests/", ("PTA000", "PTA003", "PTA005")),),
        )
        vs = run_on(tmp_path, files, contracts)
        # the handoff is STALE: production code never reads beat on
        # the main thread, and the test's read is not evidence
        assert [v.code for v in vs] == ["PTA006"]
        assert "stale handoff" in vs[0].message

    def test_live_handoff_not_stale(self, tmp_path):
        from poseidon_tpu.analysis.contracts import (
            Contracts,
            ThreadContract,
        )

        contracts = Contracts(
            thread_classes={
                "Pump": ThreadContract(handoffs={
                    "value": "written before the Event set",
                }),
            },
        )
        vs = run_on(tmp_path, {self.ANY: """\
            class Pump:
                def run(self):  # pta: background-thread
                    self.value = 42

                def result(self):
                    return self.value
        """}, contracts)
        assert vs == []


class TestPTA006Acceptance:
    """Negative injections against the REAL tree: removing any declared
    handoff or lock acquisition must make the linter fire (mirrors
    PR 5's .item()-injection acceptance)."""

    @staticmethod
    def _without_handoff(cls, attr):
        import dataclasses

        from poseidon_tpu.analysis.contracts import (
            DEFAULT_CONTRACTS,
            ThreadContract,
        )

        tc = DEFAULT_CONTRACTS.thread_classes[cls]
        h = dict(tc.handoffs)
        h.pop(attr)
        classes = dict(DEFAULT_CONTRACTS.thread_classes)
        classes[cls] = ThreadContract(lock_attr=tc.lock_attr, handoffs=h)
        return dataclasses.replace(
            DEFAULT_CONTRACTS, thread_classes=classes
        )

    def test_every_declared_handoff_is_load_bearing(self):
        """Removing ANY handoff entry from contracts.py fires PTA006 on
        the shipped tree — the allowlist holds no dead weight."""
        from poseidon_tpu.analysis.contracts import DEFAULT_CONTRACTS

        checked = 0
        for cls, tc in DEFAULT_CONTRACTS.thread_classes.items():
            for attr in tc.handoffs:
                vs, _ = analyze_tree(
                    REPO, contracts=self._without_handoff(cls, attr)
                )
                hits = [
                    v for v in vs
                    if v.code == "PTA006" and f"{cls}.{attr}" in v.message
                ]
                assert hits, f"dropping {cls}.{attr} went undetected"
                checked += 1
        assert checked >= 5  # _AsyncFetch x2 + _WatchStream x3

    def test_removing_lock_acquisition_in_obs_fires(self, tmp_path):
        """Stripping the registry lock from render() (the metrics
        server's handler-thread entry) fires PTA006."""
        src = (REPO / "poseidon_tpu/obs/metrics.py").read_text()
        anchor = "        out: list[str] = []\n        with self._lock:"
        assert anchor in src
        bad = src.replace(
            anchor,
            "        out: list[str] = []\n        if True:",
            1,
        )
        vs = run_on(tmp_path, {"poseidon_tpu/obs/metrics.py": bad})
        assert any(
            v.code == "PTA006" and "MetricsRegistry._metrics" in v.message
            for v in vs
        ), [v.message for v in vs]

    def test_removing_lock_acquisition_in_health_latch_fires(
        self, tmp_path
    ):
        src = (REPO / "poseidon_tpu/obs/server.py").read_text()
        anchor = "        with self._lock:\n            self._round_done"
        assert anchor in src
        bad = src.replace(
            anchor,
            "        if True:\n            self._round_done",
            1,
        )
        vs = run_on(tmp_path, {"poseidon_tpu/obs/server.py": bad})
        assert any(
            v.code == "PTA006" and "HealthState._round_done" in v.message
            for v in vs
        ), [v.message for v in vs]

    def test_unmarked_spawn_injection_in_bridge_fails(self, tmp_path):
        """An UNMARKED background mutation — spawn-site inference only,
        PTA004's marker discipline is blind to it — still fails CI."""
        src = (REPO / "poseidon_tpu/bridge/bridge.py").read_text()
        anchor = "    def cancel_round("
        assert anchor in src
        bad = src.replace(anchor, (
            "    def _spawn_refresher(self):\n"
            "        threading.Thread(target=self._bg_refresh).start()\n"
            "\n"
            "    def _bg_refresh(self):\n"
            "        self.round_num += 1\n\n"
        ) + anchor, 1)
        vs = run_on(tmp_path, {"poseidon_tpu/bridge/bridge.py": bad})
        assert not any(
            v.code == "PTA004" and "round_num" in v.message for v in vs
        )  # no marker: the file-local rule cannot see it
        assert any(
            v.code == "PTA006" and "round_num" in v.message for v in vs
        ), [v.message for v in vs]

    def test_wrapper_lambda_injection_in_resident_fails(self, tmp_path):
        """A lambda smuggled into _AsyncFetch that touches solver state
        is a background access and fails CI."""
        src = (REPO / "poseidon_tpu/ops/resident.py").read_text()
        anchor = "        self._inflight = True"
        assert anchor in src
        bad = src.replace(
            anchor,
            "        _probe = _AsyncFetch(lambda: self._warm)\n"
            + anchor, 1,
        )
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": bad})
        assert any(
            v.code == "PTA006" and "ResidentSolver._warm" in v.message
            for v in vs
        ), [v.message for v in vs]

    def test_wrapper_lambda_injection_in_service_fails(self, tmp_path):
        """The service lane: a chunk-fetch lambda reaching back into
        dispatcher state races the pump thread's bookkeeping."""
        src = (REPO / "poseidon_tpu/service/dispatch.py").read_text()
        anchor = "        chunk.future = _AsyncFetch(_fetch)"
        assert anchor in src
        bad = src.replace(
            anchor,
            "        chunk.future = _AsyncFetch("
            "lambda: (_fetch(), self.dispatches))",
            1,
        )
        vs = run_on(tmp_path, {"poseidon_tpu/service/dispatch.py": bad})
        assert any(
            v.code == "PTA006"
            and "BatchDispatcher.dispatches" in v.message
            for v in vs
        ), [v.message for v in vs]


class TestPTA007RecompileHazard:
    ANY = "poseidon_tpu/pkg/mod.py"

    # closing quotes at column 0: an indented close would leave
    # trailing spaces that merge into the appended snippet's first line
    KERNEL = """\
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("smax", "n_prefs"))
        def kern(x, smax, n_prefs):
            return x

"""

    def test_unfloored_static_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: self.KERNEL + """\
        def round(dev, topo):
            smax = max(int(topo.slots_max), 1)
            return kern(dev, smax=smax, n_prefs=2)
        """})
        assert codes(vs) == ["PTA007"]
        assert "'smax'" in vs[0].message

    def test_floored_static_clean(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: self.KERNEL + """\
        def round(self, dev, topo):
            self._s_floor = max(int(topo.slots_max), self._s_floor)
            smax = self._s_floor
            return kern(dev, smax=smax, n_prefs=2)
        """})
        assert vs == []

    def test_reassignment_clears_taint_flow_ordered(self, tmp_path):
        """A sink BETWEEN the hazard and the floored re-binding fires;
        the same sink after the re-binding is clean."""
        vs = run_on(tmp_path, {self.ANY: self.KERNEL + """\
        def round(self, dev, topo):
            p = topo.max_prefs
            early = kern(dev, smax=4, n_prefs=p)
            p = self._p_floor
            late = kern(dev, smax=4, n_prefs=p)
            return early, late
        """})
        assert codes(vs) == ["PTA007"]
        assert "'n_prefs'" in vs[0].message

    def test_pad_sink_flagged(self, tmp_path):
        vs = run_on(tmp_path, {self.ANY: """\
        from poseidon_tpu.graph.network import pad_bucket

        def prep(E, meta, build_cost_inputs_host):
            t = pad_bucket(max(len(meta.task_uids), 1))
            return build_cost_inputs_host(E, meta, t_min=t)
        """})
        assert codes(vs) == ["PTA007"]
        assert "'t_min'" in vs[0].message

    def test_same_name_jit_defs_do_not_shadow(self, tmp_path):
        """A tests/ (or any second) jitted def reusing a production
        kernel's name must not replace its static-param signature in
        the registry: ambiguous names are dropped, and tests/ never
        feeds the registry at all (review regression)."""
        vs = run_on(tmp_path, {
            self.ANY: self.KERNEL + """\
        def round(dev, topo):
            smax = max(int(topo.slots_max), 1)
            return kern(dev, smax=smax, n_prefs=2)
        """,
            # same name, different statics — in a NON-enforcing dir
            "tests/test_shadow.py": """\
                import jax
                from functools import partial

                @partial(jax.jit, static_argnames=("other",))
                def kern(x, other):
                    return x
            """,
        })
        # the production hazard still fires against the REAL signature
        assert codes(vs) == ["PTA007"]
        assert "'smax'" in vs[0].message

    def test_acceptance_reverted_pr8_smax_floor(self, tmp_path):
        """Reverting PR 8's smax grow-only floor in the REAL resident
        solver (static smax follows shrinking max-free-seats again)
        fails CI."""
        src = (REPO / "poseidon_tpu/ops/resident.py").read_text()
        floored = (
            "        self._s_floor = pad_bucket(\n"
            "            max(int(topo.slots.max(initial=1)), 1),\n"
            "            minimum=self._s_floor,\n"
            "        )\n"
            "        smax = min(self._s_floor, "
            "dt_host.arc_unsched.shape[0])"
        )
        assert floored in src
        bad = src.replace(
            floored,
            "        smax = max(int(topo.slots.max(initial=1)), 1)",
            1,
        )
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": bad})
        hits = [
            v for v in vs
            if v.code == "PTA007" and "'smax'" in v.message
            and "_resident_chain" in v.message
        ]
        assert hits, [v.message for v in vs]

    def test_unfloored_pref_width_reverted(self, tmp_path):
        """Reverting the pref-width floor (n_prefs follows the live
        max_prefs again) fails CI — PR 8's second recompile source."""
        src = (REPO / "poseidon_tpu/ops/resident.py").read_text()
        floored = "        self._p_floor = max(topo.max_prefs, " \
                  "self._p_floor)\n        P = self._p_floor"
        assert floored in src
        bad = src.replace(
            floored, "        P = topo.max_prefs", 1
        )
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": bad})
        hits = [
            v for v in vs
            if v.code == "PTA007" and "'n_prefs'" in v.message
        ]
        assert hits, [v.message for v in vs]


class TestSuppressions:
    HOT = "poseidon_tpu/ops/resident.py"

    def test_suppression_without_reason_fails(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            def f(x):
                return x.item()  # noqa: PTA001
        """})
        # the bare suppression is PTA000 AND suppresses nothing
        assert codes(vs) == ["PTA000", "PTA001"]

    def test_suppression_with_reason_suppresses(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            def f(x):
                return x.item()  # noqa: PTA001 -- test fixture: sanctioned
        """})
        assert vs == []

    def test_suppression_only_covers_named_code(self, tmp_path):
        vs = run_on(tmp_path, {self.HOT: """\
            def f(x):
                return x.item()  # noqa: PTA002 -- wrong code named
        """})
        assert codes(vs) == ["PTA001"]


class TestSuppressionSpans:
    """Satellite fix: a suppression covers its whole statement-header
    span, not just its literal line (regression: a noqa on a decorated
    def did not cover violations reported on the decorator line, and
    vice versa)."""

    def test_noqa_on_def_covers_decorator_violation(self, tmp_path):
        # the unknown-static-name violation anchors on the decorator's
        # tuple element line, one line ABOVE the def carrying the noqa
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("zzz",))
            def f(x):  # noqa: PTA003 -- test fixture: span regression
                return x
        """})
        assert vs == []

    def test_noqa_on_decorator_covers_def_violation(self, tmp_path):
        # nested-jit violations anchor on the DEF line; the noqa sits
        # on the decorator line above it
        vs = run_on(tmp_path, {"poseidon_tpu/x.py": """\
            import jax

            def outer(k):
                @jax.jit  # noqa: PTA003 -- test fixture: span regression
                def inner(x):
                    return x + k
                return inner
        """})
        assert vs == []

    def test_noqa_covers_multiline_statement(self, tmp_path):
        # violation anchors on the call's first line; the noqa sits on
        # a LATER line of the same multi-line statement
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": """\
            def f(x, g):
                v = g(
                    x.item(),
                )  # noqa: PTA001 -- test fixture: same-statement span
                return v
        """})
        assert vs == []

    def test_noqa_on_with_header_does_not_blanket_body(self, tmp_path):
        # compound statements expose only their HEADER as the span: a
        # noqa on the with-line must not suppress the block under it
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": """\
            def f(x, lock):
                with lock:  # noqa: PTA001 -- test fixture: header only
                    return x.item()
        """})
        assert codes(vs) == ["PTA001"]


class TestSuppressionAudit:
    """Satellite: --audit-suppressions reports dead noqas (a reasoned
    suppression whose rule no longer fires on that statement)."""

    HOT = "poseidon_tpu/ops/resident.py"

    def run_audit(self, tmp_path, files):
        import poseidon_tpu.analysis.core as core

        paths = []
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
            paths.append(p)
        vs, _ = core.audit_suppressions(tmp_path, paths)
        return vs

    def test_dead_suppression_reported(self, tmp_path):
        vs = self.run_audit(tmp_path, {self.HOT: """\
            def f(x):
                return x + 1  # noqa: PTA001 -- nothing syncs here any more
        """})
        assert [v.rule for v in vs] == ["dead-suppression"]
        assert "PTA001" in vs[0].message

    def test_live_suppression_not_reported(self, tmp_path):
        vs = self.run_audit(tmp_path, {self.HOT: """\
            def f(x):
                return x.item()  # noqa: PTA001 -- sanctioned fixture
        """})
        assert vs == []

    def test_partially_dead_multi_code_noqa(self, tmp_path):
        # PTA001 fires (live) but PTA002 never can here (dead half)
        vs = self.run_audit(tmp_path, {self.HOT: """\
            def f(x):
                return x.item()  # noqa: PTA001,PTA002 -- half-stale fixture
        """})
        assert [v.rule for v in vs] == ["dead-suppression"]
        assert "PTA002" in vs[0].message

    def test_bare_noqa_not_audited(self, tmp_path):
        # a reasonless suppression is already PTA000 in the main pass
        # and suppresses nothing — the audit does not double-report it
        vs = self.run_audit(tmp_path, {self.HOT: """\
            def f(x):
                return x + 1  # noqa: PTA001
        """})
        assert vs == []

    def test_shipped_tree_audit_clean(self):
        from poseidon_tpu.analysis.core import audit_suppressions

        vs, files = audit_suppressions(REPO)
        assert files > 30
        assert vs == [], "\n".join(
            f"{v.path}:{v.line} {v.message}" for v in vs
        )

    def test_cli_flag_fails_on_dead_noqa(self, tmp_path):
        bad = tmp_path / "poseidon_tpu" / "ops" / "resident.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(
            "def f(x):\n"
            "    return x + 1  # noqa: PTA001 -- stale reason\n"
        )
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--format=json", "--audit-suppressions",
             "--root", str(tmp_path), str(bad)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["count"] == 1
        assert doc["violations"][0]["rule"] == "dead-suppression"


class TestWidenedTargets:
    """Satellite: tests/ is scanned under a per-rule scope — jit
    hygiene (PTA003) and surface vocabulary (PTA005) apply there, the
    hot-path/thread rules do not (test files deliberately contain
    seeded violations as data)."""

    def test_default_targets_include_tests(self):
        from poseidon_tpu.analysis import default_targets

        rels = {
            p.relative_to(REPO).as_posix() for p in default_targets(REPO)
        }
        assert "tests/test_analysis.py" in rels
        assert "scripts/adversarial_sweep.py" in rels

    def test_jit_hygiene_applies_in_tests_dir(self, tmp_path):
        vs = run_on(tmp_path, {"tests/test_x.py": """\
            import jax

            def test_something(model, x):
                return jax.jit(model)(x)
        """})
        assert codes(vs) == ["PTA003"]
        assert vs[0].path == "tests/test_x.py"

    def test_hot_path_rules_do_not_apply_in_tests_dir(self, tmp_path):
        # the same .item() that fails in ops/resident.py is test data
        # under tests/ — only the scoped rules run there
        vs = run_on(tmp_path, {"tests/test_x.py": """\
            def test_something(x):
                return x.item()
        """})
        assert vs == []

    def test_suppression_hygiene_still_applies_in_tests_dir(
        self, tmp_path
    ):
        vs = run_on(tmp_path, {"tests/test_x.py": """\
            import jax

            def test_something(model, x):
                return jax.jit(model)(x)  # noqa: PTA003
        """})
        # the bare suppression is PTA000 AND suppresses nothing
        assert codes(vs) == ["PTA000", "PTA003"]


class TestJsonSchema:
    """Satellite: the CLI's JSON document is load-bearing for CI and
    downstream tooling — field names, violation ordering, and exit
    codes are locked here."""

    VIOLATION_KEYS = ["code", "rule", "path", "line", "col", "message"]

    def run_cli(self, tmp_path, files, *extra):
        paths = []
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
            paths.append(str(p))
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--format=json", "--root", str(tmp_path), *extra, *paths],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        return proc, json.loads(proc.stdout) if proc.stdout else None

    def test_clean_tree_schema_and_exit_zero(self, tmp_path):
        proc, doc = self.run_cli(
            tmp_path, {"poseidon_tpu/x.py": "A = 1\n"}
        )
        assert proc.returncode == 0
        assert sorted(doc) == ["count", "files_scanned", "violations"]
        assert doc == {
            "violations": [], "count": 0, "files_scanned": 1,
        }

    def test_dirty_tree_schema_ordering_and_exit_one(self, tmp_path):
        proc, doc = self.run_cli(tmp_path, {
            "poseidon_tpu/ops/resident.py": """\
                def b(x):
                    return x.item()

                def a(x):
                    h = x.item()
                    return int(h), x.block_until_ready()
            """,
            "poseidon_tpu/a_first.py": """\
                import jax

                def f(model, x):
                    return jax.jit(model)(x)
            """,
        })
        assert proc.returncode == 1
        assert doc["count"] == len(doc["violations"]) == 4
        for v in doc["violations"]:
            assert list(v) == self.VIOLATION_KEYS
            assert isinstance(v["line"], int)
            assert isinstance(v["col"], int)
        keys = [
            (v["path"], v["line"], v["col"], v["code"])
            for v in doc["violations"]
        ]
        assert keys == sorted(keys), "violations must be sorted"
        # path ordering puts a_first.py's PTA003 before resident.py
        assert doc["violations"][0]["path"].endswith("a_first.py")

    def test_fully_suppressed_tree_counts_zero_exit_zero(self, tmp_path):
        proc, doc = self.run_cli(tmp_path, {
            "poseidon_tpu/ops/resident.py": """\
                def f(x):
                    return x.item()  # noqa: PTA001 -- schema fixture
            """,
        })
        assert proc.returncode == 0
        assert doc == {
            "violations": [], "count": 0, "files_scanned": 1,
        }

    def test_kernels_audited_key_only_with_jaxpr(self, tmp_path):
        # without --jaxpr the key is absent (checked via clean run
        # above); the jaxpr lane's schema is asserted in
        # tests/test_jaxpr_check.py where the trace cost is paid once
        proc, doc = self.run_cli(
            tmp_path, {"poseidon_tpu/x.py": "A = 1\n"}
        )
        assert "kernels_audited" not in doc


class TestSelfCheck:
    def test_shipped_tree_is_violation_free(self):
        violations, files_scanned = analyze_tree(REPO)
        assert files_scanned > 30
        assert violations == [], "\n".join(
            f"{v.path}:{v.line} {v.code} {v.message}" for v in violations
        )

    def test_injected_item_in_resident_fused_round_fails(self, tmp_path):
        """Acceptance: a stray .item() in the resident round fails CI."""
        src = (REPO / "poseidon_tpu/ops/resident.py").read_text()
        anchor = "        self._warm = state"
        assert anchor in src
        bad = src.replace(
            anchor, "        leak = primal.item()\n" + anchor, 1
        )
        vs = run_on(tmp_path, {"poseidon_tpu/ops/resident.py": bad})
        assert any(
            v.code == "PTA001" and ".item()" in v.message for v in vs
        )

    def test_injected_unlocked_mutation_in_bridge_fails(self, tmp_path):
        """Acceptance: an unlocked cross-thread mutation in the bridge
        fails CI."""
        src = (REPO / "poseidon_tpu/bridge/bridge.py").read_text()
        anchor = "    def cancel_round("
        assert anchor in src
        bad = src.replace(anchor, (
            "    def _bg_refresh(self):  # pta: background-thread\n"
            "        self.round_num += 1\n\n"
        ) + anchor, 1)
        vs = run_on(tmp_path, {"poseidon_tpu/bridge/bridge.py": bad})
        assert any(
            v.code == "PTA004" and "round_num" in v.message for v in vs
        )

    def test_unmodified_copies_stay_clean(self, tmp_path):
        """The injection tests prove the analyzer reacts to the SEED,
        not to analyzing a file in isolation."""
        vs = run_on(tmp_path, {
            "poseidon_tpu/ops/resident.py":
                (REPO / "poseidon_tpu/ops/resident.py").read_text(),
            "poseidon_tpu/bridge/bridge.py":
                (REPO / "poseidon_tpu/bridge/bridge.py").read_text(),
        })
        assert vs == []


class TestCli:
    def test_json_output_clean_exit_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--format=json"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["count"] == 0
        assert doc["violations"] == []
        assert doc["files_scanned"] > 30

    def test_analyze_file_api_in_fresh_interpreter(self, tmp_path):
        """Regression: the public analyze_file must load the rule
        registry itself — a fresh interpreter using only analyze_file
        must not report a violating file as clean."""
        bad = tmp_path / "poseidon_tpu" / "ops" / "resident.py"
        bad.parent.mkdir(parents=True)
        bad.write_text("def f(x):\n    return x.item()\n")
        proc = subprocess.run(
            [sys.executable, "-c", (
                "import pathlib, sys\n"
                "from poseidon_tpu.analysis import analyze_file\n"
                f"vs = analyze_file(pathlib.Path({str(bad)!r}), "
                f"pathlib.Path({str(tmp_path)!r}))\n"
                "assert [v.code for v in vs] == ['PTA001'], vs\n"
                "print('ok')\n"
            )],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_path_outside_root_exits_two(self, tmp_path):
        stray = tmp_path / "stray.py"
        stray.write_text("x = 1\n")
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--root", str(REPO / "poseidon_tpu"), str(stray)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "outside --root" in proc.stderr

    def test_violations_exit_one(self, tmp_path):
        bad = tmp_path / "poseidon_tpu" / "ops" / "resident.py"
        bad.parent.mkdir(parents=True)
        bad.write_text("def f(x):\n    return x.item()\n")
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--format=json", "--root", str(tmp_path), str(bad)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["count"] == 1
        assert doc["violations"][0]["code"] == "PTA001"


class TestRuleFilter:
    """Satellite: ``--rule PTA0NN[,PTA0MM]`` isolates one analysis —
    the CI lanes run PTA010 and PTA008,PTA009 in isolation, and
    bisecting a red full run needs per-rule reruns."""

    DIRTY = {
        # one PTA001 (hot-path sync) + one PTA003 (inline jit)
        "poseidon_tpu/ops/resident.py": """\
            def f(x):
                return x.item()
        """,
        "poseidon_tpu/misc.py": """\
            import jax

            def g(model, x):
                return jax.jit(model)(x)
        """,
    }

    def run_cli(self, tmp_path, files, *extra):
        paths = []
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
            paths.append(str(p))
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--format=json", "--root", str(tmp_path), *extra, *paths],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        return proc, json.loads(proc.stdout) if proc.stdout else None

    def test_single_rule_filters_other_findings(self, tmp_path):
        proc, doc = self.run_cli(tmp_path, self.DIRTY)
        assert proc.returncode == 1
        assert sorted(v["code"] for v in doc["violations"]) == \
            ["PTA001", "PTA003"]
        proc, doc = self.run_cli(
            tmp_path, self.DIRTY, "--rule", "PTA001"
        )
        assert proc.returncode == 1
        assert [v["code"] for v in doc["violations"]] == ["PTA001"]

    def test_comma_list_selects_both(self, tmp_path):
        proc, doc = self.run_cli(
            tmp_path, self.DIRTY, "--rule", "PTA001,PTA003"
        )
        assert proc.returncode == 1
        assert sorted(v["code"] for v in doc["violations"]) == \
            ["PTA001", "PTA003"]

    def test_selected_rule_clean_exits_zero(self, tmp_path):
        proc, doc = self.run_cli(
            tmp_path, self.DIRTY, "--rule", "PTA010"
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert doc["violations"] == []

    def test_unknown_rule_exits_two(self, tmp_path):
        proc, _ = self.run_cli(
            tmp_path, self.DIRTY, "--rule", "PTA099"
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "unknown rule id" in proc.stderr
        assert "PTA099" in proc.stderr

    def test_no_python_targets_exits_two(self, tmp_path):
        sub = tmp_path / "poseidon_tpu" / "empty"
        sub.mkdir(parents=True)
        (sub / "notes.md").write_text("no code here\n")
        proc = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis",
             "--root", str(tmp_path), str(sub)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "no Python targets" in proc.stderr
