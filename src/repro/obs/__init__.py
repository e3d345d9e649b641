"""repro.obs — dependency-free tracing + metrics for the sort pipeline.

Three layers, one import:

* **Spans** (``obs.trace()`` / ``SortLimits(trace=True)``): wall-time
  phase breakdown of a sort — plan, encode, stage, the fused sort
  program (dispatch, overflow check), decode, D2H — with per-processor
  counts and measured imbalance, exportable as Chrome trace-event JSON.
  A traced sort runs the same compiled program as an untraced one. See
  ``tracing``.
* **Metrics** (``obs.counter/gauge/histogram``, ``obs.render_prometheus``):
  process-wide registry the serve tier, program cache, and overflow
  ladder publish into; rendered as Prometheus text exposition. See
  ``metrics``.
* **Profiler** (``jax.profiler``): every span site is also a
  ``TraceAnnotation`` named ``repro.<span>``, and the in-core sort
  programs scope each paper step with ``jax.named_scope`` (names in
  ``tracing.PHASES``), so a captured profile splits the device time by
  phase on the same clock as the host spans. Nothing to switch on.
* **Flight recorder** (``obs.flight``): always-on bounded rings of
  recent request/flush summaries with per-request ``trace_id``s, dumped
  as structured incident snapshots to ``$REPRO_FLIGHT_DIR`` on anomaly
  triggers. See ``flight`` and ``python -m repro.obsctl``.
* **SLOs** (``obs.slo``): declarative latency / error-budget objectives
  with burn-rate gauges in the registry (``SortServer(slo=...)``).

``obs.disabled()`` switches the whole subsystem off for a block — the
``trace_overhead`` benchmark gate uses it to price the instrumentation.
"""
from __future__ import annotations

import contextlib

from repro.obs import flight, metrics, slo, tracing
from repro.obs.flight import RECORDER, FlightRecorder, new_trace_id
from repro.obs.metrics import (
    REGISTRY,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    render_prometheus,
)
from repro.obs.slo import SLOConfig, SLOTracker
from repro.obs.tracing import PHASES, Span, Trace, current_trace, maybe_span, trace

__all__ = [
    "metrics",
    "tracing",
    "flight",
    "slo",
    "RECORDER",
    "FlightRecorder",
    "new_trace_id",
    "SLOConfig",
    "SLOTracker",
    "REGISTRY",
    "MetricsRegistry",
    "counter",
    "gauge",
    "histogram",
    "render_prometheus",
    "PHASES",
    "Span",
    "Trace",
    "current_trace",
    "maybe_span",
    "trace",
    "disabled",
    "set_enabled",
]


def set_enabled(flag: bool) -> None:
    """Master switch for spans, metric mutation, and flight recording."""
    tracing.set_enabled(flag)
    metrics.set_enabled(flag)
    flight.set_enabled(flag)


@contextlib.contextmanager
def disabled():
    """Run a block with all observability off (spans skipped, metric
    mutations dropped). Not reentrancy-counted — intended for benchmark
    gates and tests, not nested production use."""
    set_enabled(False)
    try:
        yield
    finally:
        set_enabled(True)
