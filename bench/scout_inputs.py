"""Inputs of the §IV-D configuration-search cells.

``profile_scores`` and ``conditions`` are copied from
``benchmarks/bench_optimizer.py`` (``_profile_scores``, ``_conditions``)
so that the yardstick stays with the benchmark; the program keeps its
own copies. ``search_data`` reads the Scout-shaped dataset (the
program's simulator, ``tuning.scout``) into plain arrays for the
reference, and :func:`digest` pins them: a change to the simulator
fails ``tests/bench`` instead of moving the benchmark in silence.
"""

from __future__ import annotations

import hashlib
import json

import numpy as np

def profile_scores(vm_types):
    """Deterministic fingerprint-score stand-in: per-aspect capability
    scaled off the machine profiles (ordered like real scores)."""
    from repro.fingerprint.machines import MACHINE_PROFILES

    scores = {}
    for vm in vm_types:
        p = MACHINE_PROFILES[vm]
        scores[vm] = {
            "cpu": p.cpu / 1000.0,
            "memory": p.memory / 10000.0,
            "disk": p.disk_iops / 5000.0,
            "network": p.net_gbps,
        }
    return scores


def conditions(seed: int = 0):
    """Healthy plus one degraded fleet derived through the program's
    fleet-drift path (store + EWMA analytics on a simulated fleet whose
    c4 nodes lose cpu quality), resolved in set-up."""
    from repro.optimizer import HEALTHY, drifted_condition

    degraded = drifted_condition(
        ("c4.large", "c4.xlarge", "c4.2xlarge"),
        name="c4-cpu-degraded", seed=seed)
    return (HEALTHY, degraded)


def search_data(ds, workloads, scores, conds) -> dict:
    """Plain arrays of everything a search reads: per workload and
    configuration the runtime, cost and utilization metrics; per
    configuration its features, machine type and price; per condition
    the machine scores after its drops."""
    from repro.tuning.scout import PRICES

    configs = list(ds.configs)
    return {
        "workloads": list(workloads),
        "keys": [c.key for c in configs],
        "vm": [c.vm_type for c in configs],
        "features": np.stack([ds.config_features(c) for c in configs]),
        "price": np.asarray([PRICES[c.vm_type] for c in configs]),
        "runtime": np.asarray([[ds.runtime_s(w, c) for c in configs]
                               for w in workloads]),
        "cost": np.asarray([[ds.cost_usd(w, c) for c in configs]
                            for w in workloads]),
        "lows": np.asarray([[ds.low_level_metrics(w, c) for c in configs]
                            for w in workloads]),
        "scores": {cond.name: {vm: {a: per[a] * (1.0 - dict(
            cond.score_drop.get(vm, {})).get(a, 0.0)) for a in per}
            for vm, per in scores.items()} for cond in conds},
    }


def digest(data: dict) -> str:
    h = hashlib.sha256()
    for k in ("features", "price", "runtime", "cost", "lows"):
        h.update(np.ascontiguousarray(data[k]).tobytes())
    h.update(json.dumps([data["workloads"], data["keys"], data["scores"]],
                        sort_keys=True).encode())
    return h.hexdigest()
