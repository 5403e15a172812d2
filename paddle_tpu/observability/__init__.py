"""Unified telemetry layer (ISSUE 13): one registry, three exports.

The stack's five ad-hoc instrumentation vocabularies — profiler stage
counters, the serving engine's stats dict, watchdog stdout dumps,
guardrail events, tuner provenance — all migrate onto the typed
thread-safe registry here. `registry.py` is the spine (counters, gauges,
streaming-percentile histograms, labeled series, events, spans,
atomic `snapshot(reset=True)`), `schema.py` declares every permitted
metric name (tests/test_observability.py lints drift), `exporters.py` ships it
(rotating atomic JSONL, Prometheus text, /metrics endpoint) and `slo.py`
watches it (rolling-window thresholds -> warn/alert callbacks).

Usage is module-level against the process-wide default registry:

    from paddle_tpu import observability as obs
    obs.counter_inc("serving.prefills")
    obs.histogram_observe("serving.ttft_s", 0.042)
    with obs.span("serving.decode", rows=8):
        ...                         # TraceAnnotation + histogram + JSONL
    snap = obs.snapshot()           # everything, atomically
"""
from __future__ import annotations

from . import schema  # noqa: F401
from .exporters import (  # noqa: F401
    JsonlWriter, jsonl_line, parse_prometheus, prometheus_text,
    start_http_exporter, write_prometheus)
from .registry import (  # noqa: F401
    MetricsRegistry, attach_sink, base_name, counter_defer, counter_inc,
    detach_sink,
    enabled, event, gauge_set, gc_pause_seconds, histogram_observe,
    note_import, registry, reset, snapshot, span, spanned, stage_counters,
    stage_record)
from .slo import SloMonitor, SloRule, default_serving_monitor  # noqa: F401


def export_prometheus(path: str | None = None) -> str | None:
    """Write the default registry's snapshot as a Prometheus text file to
    `path` (default FLAGS_obs_prometheus_path; no-op when unset). Returns
    the rendered text."""
    from .. import flags as _flags

    p = path or str(_flags.get_flag("obs_prometheus_path")).strip()
    if not p:
        return None
    return write_prometheus(p, snapshot())

__all__ = [
    "MetricsRegistry", "registry", "enabled", "counter_inc", "counter_defer",
    "gauge_set",
    "histogram_observe", "event", "span", "spanned", "snapshot",
    "stage_record",
    "stage_counters", "reset", "attach_sink", "detach_sink", "base_name",
    "gc_pause_seconds",
    "schema", "JsonlWriter", "jsonl_line", "prometheus_text",
    "write_prometheus", "parse_prometheus", "start_http_exporter",
    "SloMonitor", "SloRule", "default_serving_monitor",
    "export_prometheus",
]
