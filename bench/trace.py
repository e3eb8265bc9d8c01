"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

A traced run wraps its measured window in the host annotation
``bench.window`` and each call into a layer in ``bench.<layer>``
(``jax.profiler.TraceAnnotation``), so the host spans and the device
operations sit on the profiler's one clock. From them:

- busy: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices used;
- idle share: 1 - busy / window;
- top device operations by summed device time;
- the longest idle gaps, each labelled with the innermost ``bench.*``
  span the host was in at the gap's midpoint ("outside" where none).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
#: The device plane's line of operation events.
OP_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    """Device operations per device and host spans, in seconds."""

    device_ops: Dict[str, List[Tuple[str, float, float]]]
    host_spans: List[Tuple[str, float, float]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str) -> Trace:
    """Device operation events and ``bench.*`` host spans of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OP_LINE not in lines:
                continue
            ops[plane.name] = [
                (op_name(e.name), e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for e in lines[OP_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return Trace(device_ops=ops, host_spans=spans)


def op_name(hlo: str) -> str:
    """``fusion.12`` of an event named by its HLO text
    (``%fusion.12 = f32[...] fusion(...)``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """Innermost (shortest) host span other than the window covering t."""
    inside = [(e - s, name) for name, s, e in spans
              if name != WINDOW and s <= t <= e]
    return min(inside)[1] if inside else "outside"


def window_of(trace: Trace) -> Optional[Interval]:
    wins = [(s, e) for name, s, e in trace.host_spans if name == WINDOW]
    return max(wins, key=lambda w: w[1] - w[0]) if wins else None


def reduce(trace: Trace, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, device_ops and idle_gaps of a
    trace. Raises where the window or the device operations are
    missing: a traced run without them measured nothing."""
    win = window_of(trace)
    if win is None:
        raise ValueError(f"trace has no {WINDOW} span")
    lo, hi = win
    used = {d: ev for d, ev in trace.device_ops.items()
            if any(s < hi and e > lo for _, s, e in ev)}
    if not used:
        raise ValueError("no device operation ran inside the window")
    busy_each, op_time, idle = [], {}, []
    for dev, ev in sorted(used.items()):
        busy = union([(s, e) for _, s, e in ev], lo, hi)
        busy_each.append(sum(e - s for s, e in busy))
        for name, s, e in ev:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d / len(used)
        idle.extend(gaps(busy, lo, hi))
    busy_s = sum(busy_each) / len(busy_each)
    window_s = hi - lo
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": sorted(([n, t] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[label_at(trace.host_spans, (s + e) / 2), e - s]
                      for s, e in idle[:top]],
        "idle_gap_offsets": [s - lo for s, _ in idle[:top]],
        "devices": len(used),
    }
