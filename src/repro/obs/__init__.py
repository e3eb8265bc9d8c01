"""Process-wide telemetry plane: metrics, spans, timelines, JIT stats.

The observability substrate every subsystem reports through:

- ``metrics``  — a labeled, thread-safe :class:`MetricsRegistry` of
  counters / gauges / streaming histograms (exact quantiles while the
  sample count is small, log-bucketed beyond that), near-zero-cost
  when the plane is disabled (``obs.disable()``);
- ``trace``    — span-based tracing with an *injectable clock*, so the
  ``IngestionDaemon`` virtual-clock ``run()`` and the wall-clock
  ``serve()`` both produce honest timelines, with explicit span
  categories separating host work from device dispatch. Context-managed
  spans also enter a ``jax.profiler.TraceAnnotation``, so a profiler
  trace shows them on its own clock beside the device operations;
  every event carries an ``id`` and its ``parent`` (the span open on
  the same thread when it started);
- ``timeline`` — export of recorded spans to Chrome trace-event JSON
  (loadable in Perfetto / ``chrome://tracing``) plus the schema
  validator shared by tests and the CI smoke step;
- ``jaxstat``  — consolidated JIT accounting (:class:`JitSite`:
  tracings, dispatches, per-program compile/run wall seconds) behind
  the registry, replacing the per-module ad-hoc trace counters while
  keeping their public ``count`` / ``trace_count`` reads. At an
  asynchronous site the dispatch span and its run seconds are the
  launch; the wait for the outputs is the site's own span
  (``replay.wait``);
- ``regress``  — noise-aware perf-regression detection over benchmark
  history series (EWMA baselines sharing fleet drift's fold, noise
  floors calibrated from series scatter + A/A null rows, per-metric
  direction policies, telemetry-snapshot attribution). Imported
  explicitly (``from repro.obs import regress``) because it leans on
  ``repro.fleet`` — the rest of the plane stays dependency-light.

Everything hangs off one process-wide registry (:func:`registry`) and
one process-wide tracer (:func:`tracer`); components that need their
own clock domain (the ingestion daemon's virtual clock) own a private
:class:`Tracer` instead of stamping wall-clock times into the shared
one.
"""

from repro.obs.jaxstat import JitSite, instance_site
from repro.obs.metrics import (Counter, Gauge, Histogram,
                               MetricsRegistry, StatsDict, disable,
                               disabled, enable, enabled, parse_key,
                               registry)
from repro.obs.trace import (CAT_DEVICE, CAT_HOST, CAT_LADDER,
                             CAT_PLANE, SpanEvent, Tracer, span,
                             tracer)
from repro.obs.timeline import (chrome_trace, validate_chrome_trace,
                                validate_chrome_trace_file,
                                write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatsDict",
    "registry", "enable", "disable", "enabled", "disabled",
    "parse_key",
    "Tracer", "SpanEvent", "tracer", "span",
    "CAT_HOST", "CAT_DEVICE", "CAT_LADDER", "CAT_PLANE",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "validate_chrome_trace_file",
    "JitSite", "instance_site",
]
