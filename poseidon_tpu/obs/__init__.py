"""Observability: metrics, health endpoints, program spans, reports.

The reference collects ``SchedulerStats`` per round and drops them
(scheduler_bridge.cc:170-172); seven PRs in, this reproduction has five
latency-critical lanes and a dozen loud degradation paths, and tailing
a JSONL file is not an operational surface. This package is that
surface, in three pieces:

- ``metrics``: a dependency-free metrics registry (counters, gauges,
  fixed-bucket histograms) rendered in Prometheus text exposition
  format, plus ``SchedulerMetrics`` — the one place every instrument
  the scheduler feeds is declared. Recording happens exclusively from
  values the hot path already holds on the host (stats fields,
  perf-counter durations, outcome counters) at finish/actuate time:
  no new device syncs, PTA001-clean by construction and registered
  with the contract linter from day one.
- ``server``: a daemon-thread HTTP server exposing ``/metrics``,
  ``/healthz`` (process liveness) and ``/readyz`` (ready = the seed
  LIST applied AND the first certified round completed), with
  ``HealthState`` as the readiness latch the loop feeds, and
  ``/debug/profile?seconds=N``, a ``jax.profiler`` capture under
  ``--flight_dir``.
- ``spans``: ``span(name, stats, field, **ids)``, the ``poseidon/``
  profiler span around every host phase of the daemon (tick, list,
  observe, begin/build/dispatch/upload, finish/fetch_wait/apply,
  journal, confirm, round_done, post_bindings/bind_post, outbox_pump,
  checkpoint, tick_sleep, express_window, audit), each adding its
  duration to the ``SchedulerStats`` field it times; during a
  profiler capture the spans share the device ops' clock.
- ``report``: the trace analysis backend behind
  ``python -m poseidon_tpu.trace report`` — round-latency percentiles
  by lane/build-mode, express event-to-bind percentiles,
  degrade/resync/timeout tallies with reasons, placement-churn
  summaries.
- ``explain`` + ``flightrec`` + ``replay``: the decision-evidence
  layer (README "Explain & replay"). ``explain`` decomposes any
  decision's cost into named model terms that sum bit-exactly to the
  solver's arc cost and diagnoses unscheduled pods with a validated
  minimal relaxation; ``flightrec`` keeps a bounded ring of the last K
  rounds' full solve inputs and dumps it on anomalies (DEGRADE,
  EXPRESS_DEGRADE, FETCH_TIMEOUT, resync storms) or on demand;
  ``python -m poseidon_tpu.obs.replay`` re-runs a dump through the
  real solve path offline and asserts bit-identity with the recorded
  assignment/cost, reporting divergence instead of crashing.
- ``lifecycle`` + ``audit`` + ``slo``: the quality observatory
  (README "Quality & SLOs"). ``lifecycle`` stamps bounded per-pod
  timelines across the tick/express/service/restart lanes and closes
  them into true event-to-confirmed latency histograms plus a
  standing-unscheduled wait-age distribution; ``audit`` re-solves a
  sampled cluster snapshot on a background thread (CPU-pinned
  pricing + the subprocess oracle — never the accelerator) and
  publishes placement regret vs the certified optimum, a
  fragmentation index per SKU class, and drift counts; ``slo``
  evaluates declarative objectives (``e2b_p99_ms < 10 by
  lane=express``, ``regret == 0``, ``ready``) with multi-window
  burn rates, latched ``SLO_BREACH`` trace events, and the ``/slo``
  endpoint.
"""

from poseidon_tpu.obs.audit import ShadowAuditor
from poseidon_tpu.obs.flightrec import FlightRecorder
from poseidon_tpu.obs.lifecycle import LifecycleTracker
from poseidon_tpu.obs.metrics import (
    MetricsRegistry,
    SchedulerMetrics,
    build_info,
)
from poseidon_tpu.obs.server import HealthState, ObsServer
from poseidon_tpu.obs.slo import SloEngine

__all__ = [
    "FlightRecorder",
    "HealthState",
    "LifecycleTracker",
    "MetricsRegistry",
    "ObsServer",
    "SchedulerMetrics",
    "ShadowAuditor",
    "SloEngine",
    "build_info",
]
