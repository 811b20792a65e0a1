"""repro.obs — unified telemetry: metrics registry, spans, plan events.

The observability layer every subsystem reports through (DESIGN.md
§11). Three pieces, one on/off switch (``REPRO_TELEMETRY=0`` disables
everything; :func:`set_enabled` toggles at runtime):

* :mod:`.metrics` — a low-overhead, thread-safe registry of counters /
  gauges / log2-bucket histograms. Serving caches, compile trackers,
  pack-build counters and the benchmark rows all register here, so one
  :func:`snapshot` describes a whole run.
* :mod:`.spans` — ``with span("compute") as sp: ...; sp.fence(out)``
  wall-time tracing with ``block_until_ready`` fencing at span exit
  (device work is attributed to the span that launched it). Each span
  is also a ``jax.profiler.TraceAnnotation``, so it lands in a profiler
  trace beside the device ops, and keeps its raw ``perf_counter_ns``
  bounds, its id and its parent's; JAX's compile phases become spans
  through one monitoring listener, installed here. The buffer exports
  as Chrome-trace JSON (:func:`export_chrome_trace`, loadable in
  Perfetto / chrome://tracing).
* :mod:`.events` — the structured plan-event stream: every planner
  decision row records the cost model's *predicted* cost.

``repro.obs`` sits below every other repro package (it imports only
jax/numpy/stdlib), so core/data/models/launch can all report here
without import cycles.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      REGISTRY, counter, gauge, histogram, snapshot,
                      reset_metrics, enabled, set_enabled,
                      percentile_nearest_rank)
from .spans import (Span, span, export_chrome_trace, trace_events,
                    clear_trace, span_coverage, install_compile_listener)
from .events import (PLAN_EVENT_FIELDS, plan_event, plan_events,
                     clear_events, family_of)
from .signatures import SignatureTracker

install_compile_listener()

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "snapshot", "reset_metrics",
    "enabled", "set_enabled", "percentile_nearest_rank",
    "Span", "span", "export_chrome_trace", "trace_events",
    "clear_trace", "span_coverage", "install_compile_listener",
    "PLAN_EVENT_FIELDS", "plan_event", "plan_events", "clear_events",
    "family_of",
    "SignatureTracker",
]
