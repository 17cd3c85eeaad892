"""repro.obs — unified telemetry: metrics registry, span tracing,
device-resident counters (DESIGN.md section 9), and the request-scoped
layer (section 12): trace context, per-tenant SLOs, flight recorder,
Perfetto/OpenMetrics exporters.

Quickstart::

    import os; os.environ["REPRO_TRACE"] = "1"
    import repro.obs as obs
    obs.configure()                    # pick up the knob (or pass mode=)
    ... run queries / session steps ...
    print(obs.summary())               # unified text table
    obs.export_jsonl("telemetry.jsonl")  # spans + metrics, one JSON/line
    obs.export_perfetto("trace.json")  # open in ui.perfetto.dev
    print(obs.export_openmetrics())    # Prometheus-style scrape text
"""
from .registry import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                       MetricSet, Registry)
from .tracing import (configure, current_trace, export_jsonl,  # noqa: F401
                      recent_spans, record_span, span, timeline,
                      trace_enabled, trace_mode, trace_path, trace_scope)
from .device import (TELEM_HEADER, level_occupancy,  # noqa: F401
                     pack_step_telemetry, unpack_step_telemetry)
from .lifecycle import on_reset, run_reset_hooks  # noqa: F401
from .perfetto import export_perfetto, to_trace_events  # noqa: F401
from .openmetrics import export_openmetrics  # noqa: F401
from . import slo, flight  # noqa: F401  (registers their reset hooks)
from . import compiles as _compiles
from .compiles import thread_compiles  # noqa: F401

_compiles.install()


def metric_set(component: str) -> MetricSet:
    """New instance-scoped MetricSet registered with the global registry."""
    return REGISTRY.metric_set(component)


def summary() -> str:
    """Text table of every metric in the global registry."""
    return REGISTRY.summary()


def metrics_dict() -> dict:
    """The unified metric schema ({"schema": "repro.obs/v1", "metrics":
    [...]}) consumed by benchmarks/ and scripts/check_bench.py."""
    return REGISTRY.metrics_dict()


def reset() -> None:
    """Clear the global registry, the span ring buffer, and every
    component-local state registered via :func:`on_reset` (SLO windows,
    flight ring) — so back-to-back test scenarios start clean."""
    from . import tracing
    REGISTRY.reset()
    tracing.reset()
    run_reset_hooks()
