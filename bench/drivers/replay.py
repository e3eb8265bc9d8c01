"""Configuration-search driver: closed-loop scenario matrices through
the program's entry ``optimizer.replay_scenarios`` (lane lowering
``lane_tables``, the scanned float64 replay on the device, the fetch
and the per-lane traces).

Set-up builds the Scout-shaped dataset, the machine scores and the
fleet conditions, and runs one warm-up matrix of the cell's size, so
the one lane shape is compiled (or loaded) before the window. The
window issues matrices back to back; it closes when the matrix in
flight at ``--seconds`` has its traces, so every second of it holds
counted work.

The check, after the window: a sample of the finished lanes, drawn
from the seed, against the plain sequential reference
(``bench/reference/search.py``) on the same data: evaluated
configurations, costs, runtimes, running best and search cost must be
identical. With ``check.control`` on, the reference in float32 takes
the program's place in that comparison (``bench/control.py``), so the
control reads through the same check and limit as the program.
"""

from __future__ import annotations

import time

import numpy as np


def run(run) -> None:
    from bench import scout_inputs, traffic
    from bench.reference import search as ref
    from repro.optimizer import build_scenarios, replay_scenarios
    from repro.optimizer import scenarios as lowering
    from repro.tuning.scout import VM_TYPES, ScoutDataset

    cfg = run.config
    with run.span("setup.dataset"):
        ds = ScoutDataset(seed=cfg["dataset_seed"])
        workloads = list(ds.workloads)[:cfg["workloads"]]
        scores = scout_inputs.profile_scores(VM_TYPES)
    with run.span("setup.conditions"):
        conds = scout_inputs.conditions(cfg["condition_seed"])
    variants = tuple(cfg["variants"])

    def matrix(k):
        return build_scenarios(
            ds, workloads=workloads,
            seeds=traffic.matrix_seeds(run.mix, run.seed, k),
            variants=variants, conditions=conds,
            limit_percentile=cfg["limit_percentile"])

    with run.span("setup.warm_up"):
        replay_scenarios(ds, matrix(-1), scores)  # compile or load

    originals = {name: getattr(lowering, name)
                 for name in ("lane_tables", "replay")}
    layer = {"lane_tables": "lane_tables", "replay": "replay_dispatch"}

    def spanned(name):
        fn = originals[name]

        def call(*a, **k):
            with run.span(layer[name]):
                return fn(*a, **k)
        return call

    done = []  # (scenarios, traces) of every finished matrix
    for name in originals:
        setattr(lowering, name, spanned(name))
    try:
        with run.window() as t0:
            k, end = 0, t0 + run.seconds
            while time.perf_counter() < end:
                scens = matrix(k)
                with run.span("matrix"):
                    traces = replay_scenarios(ds, scens, scores)
                done.append((scens, traces))
                k += 1
            run.window_s = time.perf_counter() - t0
    finally:
        for name, fn in originals.items():
            setattr(lowering, name, fn)

    lanes = [(sc, tr) for scens, traces in done
             for sc, tr in zip(scens, traces)]
    run.counts.update(lanes_done=len(lanes), matrices=len(done))
    run.attempted = len(lanes)

    data = scout_inputs.search_data(ds, workloads, scores, conds)
    rng = np.random.default_rng([run.seed, 6])
    pick = rng.choice(len(lanes), min(cfg["check"]["lanes"], len(lanes)),
                      replace=False)
    control = cfg["check"].get("control")
    mismatched = 0
    for i in pick:
        sc, got = lanes[i]
        w = workloads.index(sc.workload)
        limit = ref.runtime_limit(data["runtime"][w],
                                  cfg["limit_percentile"])
        want = ref.search(data, w, sc.seed, sc.variant, sc.condition.name,
                          limit, cfg["replay"])
        if control:
            low = ref.search(data, w, sc.seed, sc.variant,
                             sc.condition.name, limit, cfg["replay"],
                             dtype=np.float32)
            mismatched += not same_trace(low, want)
        else:
            mismatched += not same(got, want, data["keys"])
    run.failed = mismatched
    run.check("lanes_unlike_reference", mismatched, 0)
    run.values.update(checked_lanes=len(pick))


def same(got, want, keys) -> bool:
    """A program trace (``SearchTrace``) equals a reference trace."""
    return ([c.key for c in got.evaluated]
            == [keys[i] for i in want.evaluated]
            and got.costs == want.costs and got.runtimes == want.runtimes
            and got.best_valid_cost == want.best_valid_cost
            and got.search_cost == want.search_cost)


def same_trace(a, b) -> bool:
    return (a.evaluated == b.evaluated and a.costs == b.costs
            and a.runtimes == b.runtimes
            and a.best_valid_cost == b.best_valid_cost
            and a.search_cost == b.search_cost)
