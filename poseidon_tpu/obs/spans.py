"""Program spans: the daemon's host phases on the profiler's clock.

``span(name, stats, field, **ids)`` is the one way the daemon times a
host phase. It opens a ``jax.profiler.TraceAnnotation`` named
``poseidon/<name>`` and reads ``time.perf_counter()`` at entry and at
exit; given ``stats`` (a ``SchedulerStats``, any object with the
attribute, or a dict such as the solver's ``timings``) it adds the
duration in ms to ``field``. The stats timer and the profiler event
therefore come from the same entry and exit.

The annotation is a no-op unless a profiler session is active
(``jax.profiler.start_trace``, or ``GET /debug/profile`` on the obs
server): no flag turns it on. During a session the event lands in the
trace's host plane on the same clock as the device's ``XLA Ops``, so a
device idle gap can be named by the host phase that covers it. A span's
parent is the span that encloses it on the same thread; ``ids`` (the
annotation's metadata, e.g. ``round=n``) carry the round the work
serves, so one round's spans share it. Spans opened inside the solver
(``upload``, ``fetch_wait``) carry no ids: their enclosing bridge span
does.

The vocabulary (README "Program spans" has the table of which
``SchedulerStats`` field each one feeds): ``tick`` (the root, one per
loop iteration), ``list``/``list_page``, ``observe``, ``begin`` >
``build``, ``dispatch`` > ``upload``, ``finish`` > ``fetch_wait``,
``apply``, ``journal``, ``confirm``, ``round_done``, ``post_bindings`` >
``bind_post`` (pool threads), ``outbox_pump``, ``checkpoint``,
``tick_sleep``, ``express_window``, ``audit`` (the shadow auditor's
thread).

``span`` runs inside the round window and the express fast path, so it
is a registered PTA001/PTA002 hot scope: a clock read, a float add and
the annotation, never a device sync.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "poseidon/"


class span:
    """Context manager: a ``poseidon/<name>`` profiler span that adds
    its duration (ms) to ``stats.<field>`` (or ``stats[field]``).
    After exit ``ms`` holds the duration."""

    __slots__ = ("_ann", "_stats", "_field", "_t0", "ms")

    def __init__(self, name: str, stats=None, field: str | None = None,
                 **ids):
        self._ann = TraceAnnotation(PREFIX + name, **ids)
        self._stats = stats
        self._field = field
        self.ms = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1000
        stats, field = self._stats, self._field
        if isinstance(stats, dict):
            stats[field] = stats.get(field, 0.0) + self.ms
        elif stats is not None:
            setattr(stats, field, getattr(stats, field) + self.ms)
        self._ann.__exit__(*exc)
