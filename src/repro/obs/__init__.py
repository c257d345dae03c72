"""Observability layer (``repro.obs``): tracing + metrics for the whole
TAG pipeline.

  * ``trace``        — Chrome/Perfetto trace export of predicted
                       schedule ``Timeline``s and executed event
                       streams, plus the per-(stage, mb, kind)
                       predicted-vs-executed ``diff_report``;
  * ``spans``        — low-overhead thread-safe span API (planner:
                       plan -> store lookup -> policy resolve -> MCTS
                       playouts; pipeline engine: step -> F/B/W events,
                       transfers, host syncs, optimizer), recorded in
                       memory and exported in the same trace format,
                       and on the device trace's clock during a
                       ``jax.profiler`` session;
  * ``metrics``      — counters/gauges/histograms with Prometheus-text
                       and JSON dumps (planner hit rates, plan-latency
                       histograms, bubble fractions, drift state);
  * ``xla_profiler`` — optional ``jax.profiler`` hook parsing real
                       per-collective samples into
                       ``StepRecord.collectives`` (no-op off a TPU
                       when the profiler is unavailable).

The live plane (PR 7) crosses process boundaries:

  * ``collector``    — cross-process span/event spool (fcntl-locked
                       JSONL shards with wall<->monotonic anchors) and
                       the incremental merge into one Chrome trace;
  * ``server``       — stdlib HTTP daemon serving /metrics (Prometheus
                       text), /healthz, /traces/<run_id> (chunked past
                       a size threshold), /plans (+ verify detail),
                       /runs, /runs/<run_id>/health, /alerts;
  * ``health``       — ``RunHealthAnalyzer``: continuous executed-vs-
                       predicted residual attribution per stage/link,
                       straggler ranking with hysteresis, and replan
                       prioritization for the recalibration loop;
  * ``alerts``       — step-time SLO tracking with multi-window
                       burn-rate ``AlertRule`` evaluation (page/warn).

Every surface is consumed by ``repro-plan trace`` / ``repro-plan
metrics`` / ``repro-plan serve-metrics`` and ``launch.train
--trace-dir`` / ``--spool-dir``.
"""
from repro.obs.alerts import (
    AlertEvaluator, AlertRule, AlertState, SLOTracker, default_rules,
    load_rules, parse_rules)
from repro.obs.collector import SpoolWriter, TraceCollector, shard_path
from repro.obs.health import RunHealthAnalyzer
from repro.obs.metrics import (
    Counter, Gauge, Histogram, Metric, MetricsRegistry,
    escape_label_value, parse_prometheus_text)
from repro.obs.server import PROM_CONTENT_TYPE, ObsServer
from repro.obs.spans import (
    Span, Tracer, export_tracer_metrics, get_tracer, set_tracer, span)
from repro.obs.trace import (
    aggregate_events, chrome_trace, diff_report, event_name,
    executed_events_of, executed_trace_events, format_diff,
    timeline_trace_events, validate_chrome_trace, write_chrome_trace)
from repro.obs.xla_profiler import (
    attach_collectives, classify_op, find_trace_files,
    parse_trace_collectives, profile_step, profiler_available)

__all__ = [
    "Counter", "Gauge", "Histogram", "Metric", "MetricsRegistry",
    "escape_label_value", "parse_prometheus_text",
    "Span", "Tracer", "export_tracer_metrics", "get_tracer",
    "set_tracer", "span",
    "SpoolWriter", "TraceCollector", "shard_path",
    "ObsServer", "PROM_CONTENT_TYPE",
    "AlertEvaluator", "AlertRule", "AlertState", "SLOTracker",
    "default_rules", "load_rules", "parse_rules",
    "RunHealthAnalyzer",
    "aggregate_events", "chrome_trace", "diff_report", "event_name",
    "executed_events_of", "executed_trace_events", "format_diff",
    "timeline_trace_events", "validate_chrome_trace",
    "write_chrome_trace",
    "attach_collectives", "classify_op", "find_trace_files",
    "parse_trace_collectives", "profile_step", "profiler_available",
]
