"""Observability for the dpow stack: metrics registry, tracing, /metrics.

The reference hub exposes two ad-hoc Redis counters and nothing else
(SURVEY §state); this package gives every layer — transport broker, server
orchestrator, worker client, TPU/native engines — a shared, dependency-free
telemetry surface:

  registry  — process-local Counter / Gauge / Histogram with label sets and
              fixed log2 latency buckets, safe from executor threads;
  trace     — span API stamping one WorkRequest through the whole pipeline
              (receive → accept → queue → publish → dispatch → pack →
              device → result → result_in → winner → resolve → reply),
              each stage timed from the stage that caused it, trace id
              riding the existing MQTT payloads; ``span`` puts engine
              spans into the JAX profiler's trace;
  prom      — Prometheus text-format v0.0.4 renderer + parser and the
              aiohttp GET /metrics route (server upcheck port, client
              metrics port).

Entry points:
  obs.get_registry()  — the process-wide Registry
  obs.get_tracer()    — the process-wide Tracer
  obs.span(name)      — a profiler span where jax is imported, else a no-op
  obs.snapshot()      — machine-readable dump of every metric (what
                        bench.py and the harness scripts consume instead
                        of parsing logs)
  obs.render()        — the Prometheus text page as a string
  obs.reset()         — clear all series + traces (test isolation)
"""

from .registry import (  # noqa: F401
    LOG2_BUCKETS,
    MAX_SERIES,
    Counter,
    Gauge,
    Histogram,
    MetricError,
    Registry,
    get_registry,
)
from .ledger import LEDGER, LeakLedger, get_ledger  # noqa: F401
from .trace import (  # noqa: F401
    STAGES,
    Tracer,
    get_tracer,
    is_trace_id,
    new_trace_id,
    span,
)
from .prom import add_metrics_route, histogram_quantile, parse_text, render  # noqa: F401


def snapshot() -> dict:
    """Machine-readable dump of the default registry."""
    return get_registry().snapshot()


def reset() -> None:
    """Clear every metric series and all traces (test isolation)."""
    get_registry().reset()
    get_tracer().reset()
