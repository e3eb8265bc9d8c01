"""staging_wait_ms (intake and staging): mean time from an event's due
time to the start of the FleetScoringService.flush that took it."""

import numpy as np


def read(run):
    w = run.values.get("staging_wait_s")
    if w is None:
        return None
    w = np.asarray(w)
    w = w[np.isfinite(w)]
    return float(w.mean() * 1e3) if len(w) else None
