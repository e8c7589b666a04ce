"""``repro.obs`` — dispatch-level tracing and overhead attribution.

The observability subsystem the serving stack instruments against:

* :mod:`repro.obs.tracer` — span tracer (ring buffer when enabled,
  zero-allocation no-op when disabled) recording every scheduler phase
  and every backend dispatch lane; each live span is also a
  ``repro.<name>`` event on the ``jax.profiler`` trace;
* :mod:`repro.obs.scopes` — the named scopes of the paged step
  (``kv_gather``, ``attn``, ``mlp``, ``unembed``, ``kv_write``) read back
  from compiled HLO, so a device trace's ops say which part they are;
* :mod:`repro.obs.perfetto` — trace-event JSON export for
  ui.perfetto.dev / chrome://tracing, plus the schema validator CI runs;
* :mod:`repro.obs.metrics` — counters/gauges/histograms with p50/p99
  quantiles (TTFT, TPOT, queue wait, dispatches/token);
* :mod:`repro.obs.overhead` — the paper's naive vs sequential-dispatch
  timing methodology as a reusable per-backend
  {host Python, dispatch submit, device compute} report.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile, write_metrics)
from repro.obs.overhead import (OverheadReport, measure_overhead,
                                overhead_table)
from repro.obs.perfetto import to_trace_events, validate_trace, write_trace
from repro.obs.tracer import NULL_TRACER, SpanEvent, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "percentile",
    "write_metrics", "OverheadReport", "measure_overhead", "overhead_table",
    "to_trace_events", "validate_trace", "write_trace",
    "NULL_TRACER", "SpanEvent", "Tracer",
]
