"""Observability: tracing + typed metrics + the failure-path black box.

Four pillars, wired through every tier of the stack (client, fleet
router, serving server, scheduler, engine, prefix cache, parameter
servers):

- ``tracing``: a Dapper-style :class:`TraceContext` propagated in an
  optional ``trace`` field of the DKT1 frame header, with
  :class:`Span` records collected process-wide and (opt-in per
  request) assembled into a per-request timeline on the reply. See
  docs/ARCHITECTURE.md "Observability" for the span hierarchy.
- ``metrics``: Prometheus-style :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` in a :class:`MetricsRegistry`, replacing the
  hand-rolled per-component counter dicts (:class:`CounterGroup` keeps
  the ``counters["key"] += 1`` call sites working verbatim); exposed
  by the ``metrics`` DKT1 verb and renderable as the Prometheus text
  exposition format (``render_prometheus`` / ``parse_prometheus``).
- ``recorder``: the always-on :class:`FlightRecorder` ring of
  component events (scheduler iterations, blame/quarantine, watchdog
  trips, router ejections, PS replication/promotion, armed fault-seam
  firings) plus :func:`dump_postmortem` — the one bundle writer every
  self-healing seam dumps through on a terminal event, retrieved by
  the ``postmortem`` DKT1 verb and rendered by
  ``tools/dkt_postmortem.py``.
- ``slo``: declarative :class:`SloSpec` objectives evaluated from the
  registries (:func:`evaluate_slos` / :class:`SloEvaluator`); verdicts
  (``ok``/``warn``/``breach``) ride the ``health`` verb, breaches land
  in the recorder and a registry counter, and the fleet health sweep
  can eject on sustained breach.
- ``timeseries``: :class:`MetricsHistory` — a bounded ring of
  periodic registry snapshots answering WINDOWED queries (reset-aware
  counter rates, windowed histogram quantiles, EWMA/trend) and
  multi-window burn-rate SLO verdicts (fast 1m / slow 10m); served by
  the ``timeseries`` DKT1 verb and rendered as sparkline/trend
  columns by ``tools/dkt_top.py``.
- ``compile_ledger``: :class:`CompileLedger` — every runtime XLA
  program mint recorded (key, wall seconds, warmup|serving trigger,
  in-flight requests) at the ``DecodeStepper._jit`` chokepoint, with
  compile-STORM detection (a post-warmup serving-path mint of a
  never-seen program trips an ``xla.compile.storm`` event + gauge)
  and per-request ``xla.compile`` trace spans.
- ``overlap``: :class:`OverlapLedger` — per-scheduler-iteration
  dispatch/ready/collect stamps turning the decode loop's host bubble
  (iteration wall minus device wall) into the
  ``serving_step_bubble_seconds`` histogram and the
  ``serving_overlap_efficiency`` gauge, the zero-bubble numbers
  ``dkt_top`` reads.
"""

from distkeras_tpu.obs.compile_ledger import CompileLedger
from distkeras_tpu.obs.overlap import OverlapLedger
from distkeras_tpu.obs.recorder import (
    POSTMORTEM_SCHEMA,
    FlightRecorder,
    build_postmortem,
    dump_postmortem,
    latest_postmortem,
)
from distkeras_tpu.obs.timeseries import (
    FAST_WINDOW,
    SLOW_WINDOW,
    MetricsHistory,
    worst_burn,
)
from distkeras_tpu.obs.slo import (
    SloEvaluator,
    SloSpec,
    default_serving_slos,
    default_training_slos,
    evaluate_slos,
)
from distkeras_tpu.obs.metrics import (
    Counter,
    CounterGroup,
    Gauge,
    Histogram,
    MetricsRegistry,
    label_samples,
    parse_prometheus,
    render_prometheus,
)
from distkeras_tpu.obs.tracing import (
    COLLECTOR,
    Span,
    TraceCollector,
    TraceContext,
    new_id,
    request_spans,
    span_record,
    stamp_error_trace,
    start_span,
    timeline_complete,
)

__all__ = [
    "COLLECTOR",
    "FAST_WINDOW",
    "POSTMORTEM_SCHEMA",
    "SLOW_WINDOW",
    "CompileLedger",
    "Counter",
    "CounterGroup",
    "FlightRecorder",
    "MetricsHistory",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "OverlapLedger",
    "SloEvaluator",
    "SloSpec",
    "Span",
    "TraceCollector",
    "TraceContext",
    "build_postmortem",
    "default_serving_slos",
    "default_training_slos",
    "dump_postmortem",
    "evaluate_slos",
    "latest_postmortem",
    "label_samples",
    "new_id",
    "parse_prometheus",
    "render_prometheus",
    "request_spans",
    "span_record",
    "stamp_error_trace",
    "start_span",
    "timeline_complete",
    "worst_burn",
]
