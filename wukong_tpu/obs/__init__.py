"""Observability subsystem: tracing, metrics, flight recorder, exporters.

What the reference never had (SURVEY §5: "no pervasive tracing framework")
and every perf PR after this one stands on:

- trace.py    — per-query :class:`QueryTrace` (trace id + span stack),
  thread-ambient activation for deep layers, sampling knobs, the ``span``
  helper every layer boundary opens its span (and ``wk:`` annotation) through
- metrics.py  — process-wide :class:`MetricsRegistry` (labeled counters /
  gauges / histograms; Prometheus-text + JSON snapshot exporters)
- recorder.py — :class:`FlightRecorder` ring of recent traces with
  auto-dump on resilience failures and slow queries
- export.py   — Chrome trace-event JSON (Perfetto) + JAX device profiler
- slo.py      — tenant-aware SLO plane: per-tenant accounting + error
  budgets + burn-rate sentinels, and the overload signal bus
  (``ADMISSION_INPUTS``) item 4's admission controller consumes
- tsdb.py     — bounded metrics time-series ring: windowed counter rates
  and histogram percentiles (/history; the advisor's trend reads)
- events.py   — structured cluster-event journal with shard/tenant/qid
  correlation keys (/events)
- placement.py— ShardLineage ledger + the observe-only PlacementAdvisor
  emitting literal ``MigrationPlan`` artifacts (/plan) — ROADMAP item
  3's decision substrate
- reuse.py    — serving-cache observatory: template popularity ledger,
  observe-only shadow cache, and invalidation telemetry
  (``CACHE_INPUTS``, /cache) — ROADMAP item 7's decision substrate

Config knobs (all runtime-mutable, config.py): ``enable_tracing`` (default
off — the hot path pays one getattr), ``trace_sample_every``,
``trace_ring``, ``trace_slow_ms``, ``trace_dump_dir``.
"""

from __future__ import annotations

from wukong_tpu.obs.export import (
    chrome_trace_events,
    device_trace,
    maybe_device_trace,
    write_chrome_trace,
)
from wukong_tpu.obs.events import (
    ClusterEvent,
    EventJournal,
    emit_event,
    get_journal,
    render_events,
)
from wukong_tpu.obs.httpd import (
    MetricsSnapshotter,
    health_report,
    maybe_start_metrics_http,
    maybe_start_snapshotter,
    register_health_source,
    stop_metrics_http,
)
from wukong_tpu.obs.placement import (
    MIGRATION_PLAN_FIELDS,
    MigrationPlan,
    PlacementAdvisor,
    ShardLineage,
    get_advisor,
    get_lineage,
    maybe_start_advisor,
    render_plan,
)
from wukong_tpu.obs.tsdb import (
    MetricsTSDB,
    get_tsdb,
    maybe_start_tsdb,
    render_history,
    stop_tsdb,
)
from wukong_tpu.obs.metrics import MetricsRegistry, get_registry
from wukong_tpu.obs.recorder import DUMP_CODES, FlightRecorder, get_recorder
from wukong_tpu.obs.slo import (
    ADMISSION_INPUTS,
    SLOSpec,
    get_overload,
    get_slo,
    render_slo,
)
from wukong_tpu.obs.trace import (
    QueryTrace,
    Span,
    activate,
    current,
    maybe_start_trace,
    trace_event,
)

__all__ = [
    "ADMISSION_INPUTS", "ClusterEvent", "DUMP_CODES", "EventJournal",
    "FlightRecorder", "MIGRATION_PLAN_FIELDS", "MetricsRegistry",
    "MetricsSnapshotter", "MetricsTSDB", "MigrationPlan",
    "PlacementAdvisor", "QueryTrace", "SLOSpec", "ShardLineage", "Span",
    "activate", "chrome_trace_events", "current",
    "device_trace", "emit_event", "get_advisor", "get_journal",
    "get_lineage", "get_overload", "get_recorder", "get_registry",
    "get_slo", "get_tsdb", "health_report", "maybe_device_trace",
    "maybe_start_advisor", "maybe_start_metrics_http",
    "maybe_start_snapshotter", "maybe_start_trace", "maybe_start_tsdb",
    "register_health_source", "render_events", "render_history",
    "render_plan", "render_slo", "stop_metrics_http", "stop_tsdb",
    "trace_event", "write_chrome_trace",
]
