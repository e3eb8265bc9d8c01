"""Collaborative-search driver (Karasu, arXiv 2308.11792): closed-loop
scenario matrices of Karasu lanes through the program's entry
``optimizer.replay_scenarios``, the same lowering, staging, scanned
float64 replay and trace materialization as the CherryPick lanes,
with each lane's RGPE ensemble of support models in the scan.

Set-up builds the Scout-shaped dataset, the machine scores, the fleet
conditions and the support history (``optimizer.scenarios.
support_history``: past CherryPick searches of every workload,
replayed through the program's 256-lane program, and their support
models), then one warm-up matrix of the cell's size. The window
issues matrices back to back; it closes when the matrix in flight at
``--seconds`` has its traces.

The check, after the window: a sample of the finished lanes, drawn
from the seed, against the plain reference ``bench/reference/
karasu.py`` (which builds its own support history from
``bench/reference/search.py``): evaluated configurations, costs,
runtimes, running best and search cost must be identical
(``lanes_unlike_reference``, limit 0), and each round's peak expected
improvement, before the float32 selection grid, must agree to
``check.ei_peak_rtol`` (``ei_peak_gap``, the largest relative
difference). The traces alone would not tell float32 from float64:
the ensemble's picks hardly move with the precision, its EI values
do. With ``check.control`` on, the reference in float32 takes the
program's place in both comparisons.
"""

from __future__ import annotations

import time

import numpy as np


def run(run) -> None:
    # first: a program without Karasu lanes fails here, at once
    from repro.optimizer.scenarios import support_history

    from bench import scout_inputs, traffic
    from bench.drivers.replay import same, same_trace
    from bench.reference import karasu as ref
    from repro.optimizer import ReplayConfig, build_scenarios, \
        replay_scenarios
    from repro.optimizer import scenarios as lowering
    from repro.tuning.scout import VM_TYPES, ScoutDataset

    cfg = run.config
    replay_cfg = ReplayConfig(**{k: v for k, v in cfg["replay"].items()
                                 if k != "dtype"})
    with run.span("setup.dataset"):
        ds = ScoutDataset(seed=cfg["dataset_seed"])
        workloads = list(ds.workloads)[:cfg["workloads"]]
        scores = scout_inputs.profile_scores(VM_TYPES)
    with run.span("setup.conditions"):
        conds = scout_inputs.conditions(cfg["condition_seed"])
    with run.span("setup.support_history"):
        support = support_history(
            ds, scores, workloads=workloads,
            searches=cfg["support_history"], cfg=replay_cfg,
            limit_percentile=cfg["limit_percentile"])
    if support.n_support != min(cfg["support_models"], len(workloads) - 1):
        raise ValueError(f"{support.n_support} support models per lane, "
                         f"the configuration states "
                         f"{cfg['support_models']}")
    variants = tuple(cfg["variants"])

    def matrix(k):
        return build_scenarios(
            ds, workloads=workloads,
            seeds=traffic.matrix_seeds(run.mix, run.seed, k),
            variants=variants, conditions=conds,
            limit_percentile=cfg["limit_percentile"])

    def replay_matrix(scens):
        return replay_scenarios(ds, scens, scores, replay_cfg, True,
                                support=support)

    with run.span("setup.warm_up"):
        replay_matrix(matrix(-1))  # compile or load

    originals = {name: getattr(lowering, name)
                 for name in ("lane_tables", "replay")}
    layer = {"lane_tables": "lane_tables", "replay": "replay_dispatch"}

    def spanned(name):
        fn = originals[name]

        def call(*a, **k):
            with run.span(layer[name]):
                return fn(*a, **k)
        return call

    done = []  # (scenarios, traces, result) of every finished matrix
    for name in originals:
        setattr(lowering, name, spanned(name))
    try:
        with run.window() as t0:
            k, end = 0, t0 + run.seconds
            while time.perf_counter() < end:
                scens = matrix(k)
                with run.span("matrix"):
                    traces, result = replay_matrix(scens)
                done.append((scens, traces, result.peaks))
                k += 1
            run.window_s = time.perf_counter() - t0
    finally:
        for name, fn in originals.items():
            setattr(lowering, name, fn)

    lanes = [lane for scens, traces, peaks in done
             for lane in zip(scens, traces, peaks)]
    run.counts.update(lanes_done=len(lanes), matrices=len(done))
    run.attempted = len(lanes)

    data = scout_inputs.search_data(ds, workloads, scores, conds)
    search_cfg = dict(cfg["replay"])
    hist = ref.history(data, cfg["support_history"], cfg["dataset_seed"],
                       cfg["limit_percentile"], search_cfg)
    rng = np.random.default_rng([run.seed, 6])
    pick = rng.choice(len(lanes), min(cfg["check"]["lanes"], len(lanes)),
                      replace=False)
    control = cfg["check"].get("control")
    mismatched, gap = 0, 0.0
    for i in pick:
        sc, got, got_peaks = lanes[i]
        w = workloads.index(sc.workload)
        limit = ref.cherrypick.runtime_limit(data["runtime"][w],
                                             cfg["limit_percentile"])
        want = ref.search(data, hist, w, sc.seed, sc.variant,
                          sc.condition.name, limit, search_cfg)
        if control:
            low = ref.search(data, hist, w, sc.seed, sc.variant,
                             sc.condition.name, limit, search_cfg,
                             dtype=np.float32)
            mismatched += not same_trace(low, want)
            gap = max(gap, peak_gap(low.peaks, want.peaks))
        else:
            mismatched += not same(got, want, data["keys"])
            gap = max(gap, peak_gap(got_peaks, want.peaks))
    run.failed = mismatched
    run.check("lanes_unlike_reference", mismatched, 0)
    run.check("ei_peak_gap", gap, cfg["check"]["ei_peak_rtol"])
    run.values.update(checked_lanes=len(pick))


def peak_gap(got, want) -> float:
    """Largest relative difference of two searches' per-round peak EI
    over the reference's rounds (absolute below 1e-12); a round the
    other search did not run counts as infinitely far."""
    got = np.asarray(got, np.float64)
    if len(got) < len(want) or np.isnan(got[:len(want)]).any():
        return float("inf")
    want = np.asarray(want, np.float64)
    diff = np.abs(got[:len(want)] - want)
    return float(np.max(diff / np.maximum(np.abs(want), 1e-12),
                        initial=0.0))
