"""Runtime-teeth tests (poseidon_tpu/guards.py + their wiring).

The static pass (tests/test_analysis.py) proves the PATTERNS are
caught; these tests prove the contracts hold at runtime:

- the resident round executes under ``jax.transfer_guard("disallow")``
  and performs EXACTLY ONE sanctioned placement fetch;
- steady-state churned rounds stay at the recorded compile budget of
  ZERO (a recompile regression fails tier-1);
- the pipelined round's background fetch has a deadline
  (``--max_solver_runtime``) that degrades loudly — FetchTimeout +
  FETCH_TIMEOUT trace event + ``SchedulerStats.fetch_timeouts`` —
  instead of blocking a round forever.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.bridge import SchedulerBridge
from poseidon_tpu.cluster import Machine, Task
from poseidon_tpu.guards import (
    CompileCounter,
    FetchTimeout,
    no_implicit_transfers,
    sanctioned_transfer,
)
from poseidon_tpu.ops.resident import _AsyncFetch



def _nodes(n=4):
    return [
        Machine(
            name=f"m{i}", cpu_capacity=8.0, cpu_allocatable=8.0,
            memory_capacity_kb=1 << 20, memory_allocatable_kb=1 << 20,
            rack=f"r{i % 2}", max_tasks=4,
        )
        for i in range(n)
    ]


def _pod(i: int) -> Task:
    return Task(
        uid=f"pod-{i:03d}", job=f"j{i % 2}", cpu_request=0.25,
        memory_request_kb=1024,
    )


class TestTransferGuard:
    def test_implicit_transfer_blocked(self):
        x = jnp.arange(4)
        with pytest.raises(Exception, match="[Dd]isallowed"):
            with no_implicit_transfers():
                # dispatching on a host numpy operand is an implicit
                # host->device transfer
                jnp.add(x, np.arange(4)).block_until_ready()

    def test_sanctioned_block_allows(self):
        with no_implicit_transfers():
            with sanctioned_transfer():
                out = jax.device_put(np.arange(4))
            host = jax.device_get(out)  # explicit: always permitted
        assert list(host) == [0, 1, 2, 3]


class TestCompileCounter:
    def test_counts_fresh_compiles_only(self):
        with CompileCounter() as cc:
            if not cc.supported:
                pytest.skip("jax.monitoring unavailable")
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))  # noqa: PTA003 -- the fresh wrapper IS the fixture: this test counts backend compiles of brand-new computations
        first = cc.count
        assert first >= 1
        with CompileCounter() as cc2:
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))  # noqa: PTA003 -- deliberate second fresh wrapper: proves re-tracing an identical computation does not re-COMPILE
        # the lambda re-traces (new function object) but the counter
        # only grows for actual backend compiles of NEW computations
        assert cc2.count <= first


class TestAsyncFetch:
    def test_result_roundtrip(self):
        f = _AsyncFetch(lambda: 41 + 1)
        assert f.result(timeout_s=5.0) == 42

    def test_exception_propagates(self):
        def boom():
            raise ValueError("boom")
        f = _AsyncFetch(boom)
        with pytest.raises(ValueError, match="boom"):
            f.result(timeout_s=5.0)

    def test_deadline_miss_raises_fetch_timeout(self):
        f = _AsyncFetch(lambda: time.sleep(3.0))
        t0 = time.perf_counter()
        with pytest.raises(FetchTimeout):
            f.result(timeout_s=0.05)
        assert time.perf_counter() - t0 < 1.0  # did not block 3 s


def _steady_bridge():
    """A bridge driven to the dense path's warm steady state."""
    bridge = SchedulerBridge(small_to_oracle=False)
    pods = [_pod(i) for i in range(8)]
    bridge.observe_nodes(_nodes())
    bridge.observe_pods(pods)
    # warm-up: cold-variant compile, then the warm-variant compile.
    # Placements are NOT confirmed, so the same pending set re-offers
    # each round (stable shapes) while churn below swaps members.
    for _ in range(3):
        result = bridge.run_scheduler()
        assert result.stats.backend == "dense_auction", result.stats
    return bridge, pods


class TestResidentRoundContracts:
    def test_steady_state_compile_budget_is_zero(self):
        """The recorded budget: churned warm rounds recompile NOTHING.

        Shapes are padding-bucketed, the chain's static arguments are
        stable, and the warm handle persists — so after warm-up, a
        round that churns pods (within the bucket) must hit the jit
        cache every time. A recompile here is a regression tier-1
        catches (the reason this test exists, ISSUE 5)."""
        bridge, pods = _steady_bridge()
        next_uid = len(pods)
        with CompileCounter() as cc:
            if not cc.supported:
                pytest.skip("jax.monitoring unavailable")
            for r in range(3):
                # churn: one pod leaves the snapshot, a new one arrives
                # (same shape class: no prefs, existing job ids)
                pods = pods[1:] + [_pod(next_uid)]
                next_uid += 1
                bridge.observe_pods(pods)
                result = bridge.run_scheduler()
                assert result.stats.backend == "dense_auction"
        assert cc.count == 0, (
            f"steady-state round recompiled {cc.count} time(s); "
            "the recorded budget is 0"
        )

    def test_exactly_one_sanctioned_fetch_per_round(self):
        bridge, _pods = _steady_bridge()
        result = bridge.run_scheduler()
        assert result.stats.backend == "dense_auction"
        assert bridge.solver.last_round_fetches == 1

    def test_fetch_timeout_degrades_loudly(self):
        bridge, _pods = _steady_bridge()
        bridge.solver.fetch_timeout_s = 0.05
        ir = bridge.begin_round()
        assert ir.solve is not None and ir.solve.outcome is None
        # wedge the fetch: a handle that cannot meet the deadline
        ir.solve.future = _AsyncFetch(lambda: time.sleep(3.0))
        with pytest.raises(FetchTimeout):
            bridge.finish_round(ir)
        assert bridge.solver.fetch_timeouts == 1
        assert bridge.warm_state is None  # device health unknown
        assert "FETCH_TIMEOUT" in [e.event for e in bridge.trace.events]
        # the loop recovers: the next round runs and surfaces the count
        bridge.solver.fetch_timeout_s = None
        result = bridge.run_scheduler()
        assert result.stats.fetch_timeouts == 1
        assert result.stats.backend == "dense_auction"
        # and the counter does not stick
        assert bridge.run_scheduler().stats.fetch_timeouts == 0

    def test_discard_round_bounded_join(self):
        bridge, _pods = _steady_bridge()
        ir = bridge.begin_round()
        ir.solve.future = _AsyncFetch(lambda: time.sleep(3.0))
        bridge.solver.fetch_timeout_s = 0.05
        t0 = time.perf_counter()
        bridge.cancel_round(ir)
        assert time.perf_counter() - t0 < 1.0
        assert bridge.solver.fetch_timeouts == 1
        # a cancel-path deadline miss is surfaced like a finish-path
        # one: traced, and counted in the next round's stats
        assert "FETCH_TIMEOUT" in [e.event for e in bridge.trace.events]
        bridge.solver.fetch_timeout_s = None
        result = bridge.run_scheduler()
        assert result.stats.backend == "dense_auction"
        assert result.stats.fetch_timeouts == 1


class TestCompileCache:
    """``configure_compile_cache``: placed by JAX_COMPILATION_CACHE_DIR,
    else a fixed path in the checkout. Runs in a child process: the
    cache is process-global and tests must not turn it on."""

    _CHILD = (
        "import jax, sys; sys.path.insert(0, {repo!r});"
        "from poseidon_tpu.guards import configure_compile_cache as c;"
        "p = c(); print(p); print(jax.config.jax_compilation_cache_dir);"
        "{extra}"
    )

    def _run(self, tmp_path, env_dir, extra=""):
        repo = str(pathlib.Path(__file__).resolve().parents[1])
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        out = subprocess.run(
            [sys.executable, "-c",
             self._CHILD.format(repo=repo, extra=extra)],
            env=env, cwd=tmp_path, capture_output=True, text=True,
            check=True, timeout=120,
        )
        return repo, out.stdout.split()

    def test_env_var_places_the_cache_and_programs_land_there(self, tmp_path):
        where = tmp_path / "cache"
        _, (path, cfg) = self._run(
            tmp_path, str(where),
            extra=(
                "jax.config.update("
                "'jax_persistent_cache_min_compile_time_secs', 0);"
                "jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(8))"
                ".block_until_ready()"
            ),
        )
        assert path == cfg == str(where)
        assert any(where.iterdir())

    def test_unset_uses_the_fixed_checkout_path(self, tmp_path):
        repo, (path, cfg) = self._run(tmp_path, None)
        assert path == cfg == f"{repo}/.jax_cache"
        gitignore = pathlib.Path(repo, ".gitignore").read_text()
        assert ".jax_cache/" in gitignore.split()
