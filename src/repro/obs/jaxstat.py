"""Consolidated JIT accounting behind the metrics registry.

:class:`JitSite` replaces the repo's ad-hoc trace counters (the old
``core.trainer.TraceCount``, ``serving.FingerprintEngine``'s inline
``_trace_count`` and ``fleet.shard.ShardedScorer``'s copy) with one
registry-backed object per dispatch site. Each site owns four labeled
instruments — ``jax.traces`` / ``jax.dispatches`` / ``jax.compile_s``
/ ``jax.run_s`` — so a registry snapshot answers "what retraced, how
often does it dispatch, and where did the compile wall time go" across
the whole process.

The public reads the old counters exposed stay intact: ``tick()``
increments at trace time (call it from inside the traced function, the
established pattern), ``count`` and ``trace_count`` read the tracing
counter, so ``tests/_trace_utils.expect_traces`` works on a
:class:`JitSite` unchanged.

:meth:`JitSite.dispatch` wraps one compiled call: it books the wall
time as *compile* when the site's trace counter advanced inside the
call (first call per program signature) and as *run* otherwise, ticks
the dispatch counter, and records a ``CAT_DEVICE`` span through
:meth:`repro.obs.trace.Tracer.span` on the current thread — on the
profiler's clock, with the ``traced`` flag in its args — which is how
per-program compile/run splits and worker-thread device spans reach
the timeline. On an asynchronous backend a call returns once the
program is launched, so at a site that fetches its outputs later
(``optimizer.replay.replay_async``) the span and ``jax.run_s`` measure
the *launch*; the device time shows in the site's own wait span
(``replay.wait``).
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Dict, Iterator, Optional

from repro.obs import metrics, trace

_SITE_SEQ = itertools.count()


def instance_site(prefix: str) -> str:
    """Unique site label for per-instance accounting (``engine/3``) —
    instances of the same class keep distinct registry rows."""
    return f"{prefix}/{next(_SITE_SEQ)}"


class JitSite:
    """Trace/dispatch/compile-time accounting for one jit call site."""

    def __init__(self, site: str,
                 registry: Optional[metrics.MetricsRegistry] = None,
                 tracer: Optional[trace.Tracer] = None):
        reg = registry if registry is not None else metrics.registry()
        self.site = site
        self._tracer = tracer
        self._traces = reg.counter("jax.traces", site=site)
        self._dispatches = reg.counter("jax.dispatches", site=site)
        self._compile_s = reg.counter("jax.compile_s", site=site)
        self._run_s = reg.counter("jax.run_s", site=site)

    # ------------------------------------------------- trace counting
    def tick(self) -> None:
        """Tick the tracing counter — call inside the traced function
        so it fires at trace time only."""
        self._traces.inc()

    @property
    def count(self) -> int:
        return int(self._traces.value)

    @property
    def trace_count(self) -> int:
        return int(self._traces.value)

    # ------------------------------------------------------ dispatch
    @property
    def dispatches(self) -> int:
        return int(self._dispatches.value)

    @property
    def compile_seconds(self) -> float:
        return float(self._compile_s.value)

    @property
    def run_seconds(self) -> float:
        return float(self._run_s.value)

    @contextlib.contextmanager
    def dispatch(self, name: Optional[str] = None,
                 args: Optional[Dict[str, object]] = None
                 ) -> Iterator[None]:
        """Account one compiled call (see module doc). No-op when the
        plane is disabled."""
        if not metrics.enabled():
            yield
            return
        tr = self._tracer if self._tracer is not None \
            else trace.tracer()
        span_args = dict(args) if args else {}
        before = self._traces.value
        with tr.span(name if name is not None else self.site,
                     trace.CAT_DEVICE, args=span_args):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                traced = self._traces.value > before
                (self._compile_s if traced else self._run_s).add(dt)
                self._dispatches.inc()
                span_args["traced"] = traced

    def stats(self) -> metrics.StatsDict:
        return {
            "traces": self.count,
            "dispatches": self.dispatches,
            "compile_s": self.compile_seconds,
            "run_s": self.run_seconds,
        }
