"""repro.obs — unified observability for the serving runtime.

Three facilities behind one :class:`~repro.obs.observe.Observability`
bundle a :class:`~repro.runtime.serve.Server` accepts via ``obs=``:

* :mod:`~repro.obs.trace` — per-request lifecycle spans and per-tick
  engine events on the tick clock + wall clock, exported as a single
  JSON document that is both a schema'd artifact and a
  Perfetto/``chrome://tracing``-loadable timeline.  Its
  :func:`~repro.obs.trace.span` is the engine's host span: always a
  ``jax.profiler.TraceAnnotation`` (so ``Server.tick``'s ``serve.*``
  phases sit on a profiler trace's device clock), and also a recorder
  span when a bundle with a recorder is attached;
* :mod:`~repro.obs.metrics` — counters / gauges / log-bucket
  histograms with a snapshot API and Prometheus text exposition;
* :mod:`~repro.obs.monitor` — the online direction-2 model-conformance
  check: the live paged allocator's op stream continuously validated
  against the verified abstract model (:mod:`repro.verify`), dumping a
  replayable counterexample trail on violation.

``python -m repro.obs`` summarizes, schema-checks, and re-exports
recorded traces.
"""

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .monitor import ConformanceMonitor
from .observe import Observability
from .trace import (TRACE_KIND, TRACE_SCHEMA, Span, TraceRecorder,
                    export_trace, parse_trace, span, spans_from_events,
                    validate_trace)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "ConformanceMonitor", "Observability", "span",
           "TRACE_KIND", "TRACE_SCHEMA", "Span", "TraceRecorder",
           "export_trace", "parse_trace", "spans_from_events",
           "validate_trace"]
