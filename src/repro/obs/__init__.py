"""Observability layer: device-side engine telemetry, host-side tracing
spans, and export pipelines (Chrome ``trace_event`` JSON, metrics
registry).

Three pieces, layered from device to host:

* :mod:`.telemetry` — :class:`EngineTelemetry`, the host-facing view of
  the fixed-shape counter arrays the sweep loop
  (:mod:`repro.engine.sweep`) carries through its ``lax.while_loop``:
  gain passes executed, exchanges applied per sweep, tabu-masked pairs,
  aspiration fires, downhill escapes, matching rounds, and the objective
  trajectory.  Collection is a *runtime* toggle that masks rather than
  retraces — the same no-retrace discipline as the tabu knobs — and the
  off path is bit-identical to the untelemetered engine.  It is on when
  a caller asks (``telemetry=True`` on ``refine``/``execute``/``map``)
  and whenever the global tracer records: ``MappingPlan.execute``,
  ``execute_batch`` and ``execute_warm`` then attach it to
  ``search_stats.telemetry`` and to the ``plan.refine`` span.

* :mod:`.trace` — :class:`Span`/:class:`Tracer`, a lightweight
  context-manager tracing API with a bounded in-memory ring buffer.
  Spans always measure wall-time (callers read ``span.dur`` for result
  accounting) but are only *recorded* when the tracer is enabled, so
  the disabled hot path costs one ``perf_counter`` pair.  A span carries
  its ``id``, its ``parent`` (the span open on its thread) and the
  request tickets it serves (``req``, set by
  :meth:`Tracer.request`); while enabled it also enters a
  ``jax.profiler.TraceAnnotation``, so profiler traces show it on the
  device trace's clock.  The spans, by layer:

  - service: ``service.tick`` per worker tick, ``service.queue`` per
    request (submit to the start of its tick; recorded after the fact);
  - plan: ``plan.lower``, ``plan.execute`` (``_batch``/``_warm``),
    ``plan.construct``, ``plan.refine`` and, inside it, ``plan.pairs``
    (candidate-pair generation, with the pair count and the LRU hit);
    ``plan.vcycle`` with per-level ``vcycle.construct`` and
    ``vcycle.refine``; ``monitor.*`` in the closed remap loop;
  - engine, inside ``plan.refine``: ``engine.upload`` (device graph,
    pairs and toggles, with cache hits), ``engine.dispatch``,
    ``engine.wait`` (until the outputs are ready) and
    ``engine.readback`` (transfers and the host float64 objective).

* :mod:`.export` / :mod:`.metrics` — ``write_chrome_trace`` emits
  Perfetto/``chrome://tracing``-loadable ``trace_event`` JSON (spans as
  complete events, per-sweep engine counters as counter tracks);
  ``span_breakdown`` aggregates spans by name;
  :class:`MetricsRegistry` holds counters/gauges/histograms behind one
  lock with atomic deep-copied snapshots (the backing store of
  ``MappingService.stats()`` and its Prometheus exposition).

Surfaces: ``viem --profile out.trace.json`` / ``viem --telemetry``,
``plan.describe()["timings"]``, ``MappingService.stats()`` engine
aggregates, and the span breakdowns stamped into every ``BENCH_*.json``.
"""

from .export import chrome_trace_events, span_breakdown, write_chrome_trace
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      parse_prometheus)
from .telemetry import EngineTelemetry
from .trace import Span, Tracer, get_tracer

__all__ = [
    "Counter", "EngineTelemetry", "Gauge", "Histogram", "MetricsRegistry",
    "Span", "Tracer", "chrome_trace_events", "get_tracer",
    "parse_prometheus", "span_breakdown", "write_chrome_trace",
]
