"""Observability: span tracing + process-wide metrics.

The reproduction's counterpart of the reference's observability stack —
OperatorStats/QueryInfo over REST, event/SplitMonitor.java, and the JMX
connector that turns engine metrics into SQL tables (reference
presto-main/.../connector/jmx/) — reshaped for a device runtime:

- ``obs.trace``   context-propagated spans (query -> stage -> task ->
                  operator -> dispatch/device-sync/compile), each also
                  a ``jax.profiler.TraceAnnotation`` while the tracer
                  is on, with a Chrome-trace (Perfetto) JSON exporter
                  and wire-carriable span context for distributed
                  stitching;
- ``obs.metrics`` process-wide counters/gauges/histograms fed by direct
                  instrumentation and by an EventListenerManager sink,
                  queryable as ``system.runtime.metrics``;
- ``obs.exposition`` Prometheus/OpenMetrics text rendering of the
                  registry — the ``GET /v1/metrics`` scrape surface on
                  workers and the coordinator;
- ``obs.history`` bounded persistent query history (+ optional JSONL
                  sink with size-capped rotation), queryable as
                  ``system.runtime.{completed_queries,operator_stats}``;
- ``obs.log``     structured JSON-lines logging correlated by
                  query/task/trace ids from the span context;
- ``obs.profiler`` device profiling & cost attribution: per-executable
                  compile/FLOPs/HBM introspection
                  (``system.runtime.executables``), per-operator
                  device-time attribution under the ``profile`` session
                  property, the one listener on JAX's compile event,
                  and HBM telemetry sampling.

Everything is always importable and safe when idle: the tracer is OFF
by default (a disabled ``span()`` returns a shared no-op and records
nothing), the logger is off by default, and metric updates are single
dict/number operations.
"""
from .trace import TRACER, Span, chrome_trace, write_chrome_trace  # noqa: F401
from .metrics import (  # noqa: F401
    NODES, REGISTRY, TASKS, attach_event_listeners,
)
from .exposition import parse_exposition, render_exposition  # noqa: F401
from .flight import FLIGHTS, FlightRecorder, current_flight  # noqa: F401
from .history import HISTORY, attach_history  # noqa: F401
from .log import LOG  # noqa: F401
from .profiler import EXECUTABLES, profiled, sample_hbm  # noqa: F401
