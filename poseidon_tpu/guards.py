"""Runtime enforcement of the contracts the static pass (analysis/)
checks at review time.

Three guards, all cheap enough to stay on in production:

- ``no_implicit_transfers()``: ``jax.transfer_guard("disallow")`` as a
  context manager. The resident round runs under it — any implicit
  host sync (``float()`` on a device array, a numpy coercion of a
  traced result, a stray dispatch on host operands) raises instead of
  silently re-adding the per-round host sync PR 1 removed.
  Explicit ``jax.device_put`` / ``jax.device_get`` stay permitted;
  pairing with the PTA001 lint keeps those to the sanctioned sites.
- ``sanctioned_transfer()``: ``jax.transfer_guard("allow")`` for the
  round's one blessed fetch (and the degrade paths), making the
  allow-list visible in the code instead of implied.
- ``CompileCounter``: counts XLA backend compiles via
  ``jax.monitoring`` so tests can assert the steady-state recompile
  budget (zero) — a recompile regression fails tier-1.

``FetchTimeout`` is raised by the resident solver when the pipelined
round's background placement fetch exceeds its deadline
(``--max_solver_runtime``); the bridge turns it into a FETCH_TIMEOUT
trace event + ``SchedulerStats.fetch_timeouts`` so the degradation is
loud, then the driver skips the tick like any other failed round.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import threading

import jax

# the persistent compile cache's fixed home when nothing places it:
# inside the checkout (git-ignored), never a temporary or per-process
# name — the path is part of the cache key, so a moving directory
# never hits
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"


class FetchTimeout(RuntimeError):
    """The background placement fetch missed its deadline."""


@contextlib.contextmanager
def no_implicit_transfers():
    """Disallow implicit device<->host transfers inside the block."""
    with jax.transfer_guard("disallow"):
        yield


@contextlib.contextmanager
def sanctioned_transfer():
    """Explicitly allow transfers: the round's blessed fetch sites."""
    with jax.transfer_guard("allow"):
        yield


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it (JAX reads the
    variable itself, so no path is set here); otherwise it lives at
    ``<repo>/.jax_cache``. Called by the entry points a user runs
    (``cli.main``, ``chip_smoke.py``, ``benchmark/run.py``) — never by
    ``run_loop`` or the tests, which must not write a cache.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


# ---- compile counting --------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counter_lock = threading.Lock()
_active_counters: list["CompileCounter"] = []
_listener_installed = False
# optional duration sink (obs satellite): the same monitoring event
# carries the compile's duration in seconds — a daemon wires this to
# SchedulerMetrics.record_compile so every XLA compile lands in the
# poseidon_xla_compile_ms histogram. One process-global slot (the
# listener itself is process-global and cannot be unregistered).
_duration_sink = None


def set_compile_duration_sink(sink) -> bool:
    """Install a ``fn(duration_ms: float)`` receiving every XLA
    backend compile's latency (None to clear). Always True (kept for
    callers that record whether the sink is set)."""
    global _duration_sink
    if not _install_listener():
        return False
    with _counter_lock:
        _duration_sink = sink
    return True


def _on_event(name: str, *args, **_kw) -> None:
    if name != _COMPILE_EVENT:
        return
    with _counter_lock:
        for c in _active_counters:
            c.count += 1
        sink = _duration_sink
    if sink is not None and args:
        try:
            sink(float(args[0]) * 1000.0)
        except Exception:  # a metrics failure must not break compiles
            pass


def _install_listener() -> bool:
    """Register the monitoring listener once per process. jax has no
    unregister (only clear-all, which would drop other listeners), so
    the hook stays installed and counters activate/deactivate."""
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True
    return True


class CompileCounter:
    """Context manager counting XLA backend compiles in the block.

    ``supported`` is False when this jax exposes no monitoring hook —
    callers (the budget tests) skip rather than pass vacuously.
    """

    def __init__(self) -> None:
        self.count = 0
        self.supported = False

    def __enter__(self) -> "CompileCounter":
        self.supported = _install_listener()
        with _counter_lock:
            _active_counters.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _counter_lock:
            _active_counters.remove(self)
