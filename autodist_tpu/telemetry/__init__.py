"""Unified telemetry: spans, metrics registry, per-step records, drift.

The single observability surface for the framework (the reference's
chrome-trace timelines + ``TimeHistory`` meter tier, SURVEY.md §5.1,
rebuilt process-wide).  Typical use::

    from autodist_tpu import telemetry

    telemetry.configure(out_dir="/tmp/run1")
    with telemetry.span("compile"):
        ...
    telemetry.counter("asyncps/push").inc()
    telemetry.record_step(step=3, duration_s=0.012, examples=32)
    telemetry.flush()        # trace.json / metrics.jsonl / manifest.json
    telemetry.startup()      # the process's compile and start-up account
    telemetry.drift_report(strategy, cost_model, measured,
                           trainable=trainable)

Disabled entirely with ``AUTODIST_TPU_TELEMETRY=0`` (no files, shared
no-op span/instrument singletons).  See ``docs/usage/observability.md``.
"""
from autodist_tpu.telemetry import tracing
from autodist_tpu.telemetry.account import startup, watch_compiles
from autodist_tpu.telemetry.aggregate import (RollingWindow,
                                              TelemetryAggregator)
from autodist_tpu.telemetry.core import (NULL_SPAN, Telemetry, configure,
                                         get, reset)
from autodist_tpu.telemetry.drift import DriftMonitor, drift_report
from autodist_tpu.telemetry.metrics import (NULL_INSTRUMENT, Counter, Gauge,
                                            Histogram, MetricsRegistry)
from autodist_tpu.telemetry.records import build_manifest, provenance
from autodist_tpu.telemetry.scopes import SCOPES, scope
from autodist_tpu.telemetry.tracing import (current_trace_id, mint_trace_id,
                                            request_timeline, stitch_trace,
                                            trace_context)

__all__ = [
    "Telemetry", "get", "configure", "reset", "enabled", "span", "counter",
    "gauge", "histogram", "record_step", "record_event", "annotate",
    "flush", "manifest", "SCOPES", "scope", "startup", "watch_compiles",
    "summary", "drift_report", "provenance", "build_manifest",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_SPAN", "NULL_INSTRUMENT",
    "tracing", "mint_trace_id", "current_trace_id", "trace_context",
    "stitch_trace", "request_timeline",
    "RollingWindow", "TelemetryAggregator", "DriftMonitor",
]


def enabled() -> bool:
    return get().enabled


def span(name: str, **args):
    return get().span(name, **args)


def counter(name: str):
    return get().counter(name)


def gauge(name: str):
    return get().gauge(name)


def histogram(name: str):
    return get().histogram(name)


def record_step(step: int, duration_s: float, **kw) -> bool:
    return get().record_step(step, duration_s, **kw)


def record_event(kind: str, **fields) -> bool:
    return get().record_event(kind, **fields)


def annotate(**kv):
    return get().annotate(**kv)


def flush(out_dir=None) -> dict:
    return get().flush(out_dir)


def manifest() -> dict:
    return get().manifest()


def summary() -> str:
    return get().summary()
