"""repro.obs — unified observability: metrics, structured events, spans.

Three pillars (full guide: docs/OBSERVABILITY.md):

* **Metrics** (``obs.metrics`` / ``obs.registry``) — typed
  ``Counter``/``Gauge``/``Histogram`` series in a ``MetricsRegistry`` with
  one schema-versioned export (``repro.obs/1``).  Hot-path variants
  (``DeviceCounter``/``DeviceHistogram``) accumulate in device-resident
  int32 arrays and drain only at existing flush boundaries.
* **Events** (``obs.events``) — a bounded ring of schema-versioned records
  (scheduler admits/retires/rejects, supervisor health transitions,
  checkpoint saves/restores, fault sightings/recoveries).
* **Spans** (``obs.trace``) — host-walltime timelines exportable as Chrome
  trace-event JSON for Perfetto (trainer phases, per-stage executor ticks,
  request lifecycles), each also a ``jax.profiler`` annotation.
* **Scopes** (``obs.scopes``) — the named regions of the step program and
  the compiled step's instruction -> ``op_name`` table, which charges a
  device trace's operations to them.

Consumption: ``launch/loadgen.py`` (open-loop Poisson load against the
serve engine, SLOs into ``results/BENCH_9.json``) and
``launch/metrics.py`` (dump / summary / schema check).
"""
from repro.obs.events import (EVENT_KINDS, Event, EventLog, default_log,
                              set_default_log)
from repro.obs.metrics import (DEPTH_BUCKETS, LOSS_BUCKETS, TTFT_MS_BUCKETS,
                               Counter, DeviceCounter, DeviceHistogram,
                               Gauge, Histogram)
from repro.obs.registry import (SCHEMA, MetricsRegistry, default_registry,
                                set_default_registry)
from repro.obs.trace import (TID_LOOP, TID_REQ0, TID_STAGE0, Span, Tracer)

__all__ = [
    "SCHEMA", "EVENT_KINDS", "TTFT_MS_BUCKETS", "LOSS_BUCKETS",
    "DEPTH_BUCKETS", "TID_LOOP", "TID_STAGE0", "TID_REQ0",
    "Counter", "Gauge", "Histogram", "DeviceCounter", "DeviceHistogram",
    "MetricsRegistry", "default_registry", "set_default_registry",
    "Event", "EventLog", "default_log", "set_default_log",
    "Span", "Tracer",
]
