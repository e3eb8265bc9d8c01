"""The program's own spans (``repro.obs``), as recorded in a run's
measured window, for the per-layer readers under ``bench/metrics``.

The window runs from ``run.t_process + run.setup_s`` for
``run.window_s`` seconds, on ``time.perf_counter``, which is also the
process tracer's clock; the warm-up's spans lie before it.
"""

from __future__ import annotations

from typing import List, Optional


def in_window(run, name: str) -> List:
    """The events named ``name`` that start and end inside the
    measured window, in start order; none where the run has no
    window."""
    if run.setup_s is None or run.window_s is None:
        return []
    from repro import obs

    lo = run.t_process + run.setup_s
    hi = lo + run.window_s
    return sorted((e for e in obs.tracer().events()
                   if e.name == name and lo <= e.ts
                   and e.ts + e.dur <= hi), key=lambda e: e.ts)


def mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans in the window, in ms."""
    spans = in_window(run, name)
    if not spans:
        return None
    return sum(e.dur for e in spans) / len(spans) * 1e3
