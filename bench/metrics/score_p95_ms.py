"""score_p95_ms: 95th percentile, over every event due in the window,
of the time from its scheduled creation to the return of the flush that
attached its rows' scores in the store. An event never scored counts as
infinitely late; a run whose tail is then infinite reports nothing."""

import math

import numpy as np


def read(run):
    lat = run.values.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    with np.errstate(invalid="ignore"):
        p95 = float(np.percentile(np.asarray(lat), 95))
    return p95 * 1e3 if math.isfinite(p95) else None
