"""Unified observability layer: metrics, tracing, trace export.

- :mod:`.metrics` — thread-safe Counter/Gauge/Histogram + Registry
  with Prometheus text exposition (stdlib-only, standalone-loadable).
- :mod:`.timing` — OpTimer / PhaseTimer unified over the histogram;
  each span is also a ``jax.profiler.TraceAnnotation`` (where JAX is
  already imported), so it reaches the profiler's trace under a stable
  name: ``build.<phase>``, ``build.pack``, ``serve.op.<op>``,
  ``serve.step.device``, ``serve.step.rescore``, ``serve.batch``,
  ``serve.reply``.  Importing this package never imports JAX.
- :mod:`.tracing` — per-request trace ids, trace ring, slow-query log.
- :mod:`.attribution` — request-scoped cost collector (the EXPLAIN
  surface) and the crash-dump flight recorder.
- :mod:`.chrometrace` — Chrome ``trace_event`` export for builds.
"""

from .chrometrace import TraceEvents
from .metrics import (Counter, Gauge, Histogram, KNOWN_METRICS, Registry,
                      default_registry)
from .timing import OpTimer, PhaseTimer
from .tracing import TraceRing, gen_trace_id

__all__ = [
    "Counter", "Gauge", "Histogram", "KNOWN_METRICS", "OpTimer",
    "PhaseTimer", "Registry", "TraceEvents", "TraceRing",
    "default_registry", "gen_trace_id",
]
