"""Batched BO replay vs sequential numpy search (paper §IV-D grid),
written to ``BENCH_optimizer.json``.

Both paths run the *same* scenario matrix — workload x seed x tuner
variant (CherryPick/Arrow, +-Perona weighting) x fleet condition
(healthy + a drift-derived degraded fleet) — over one shared scout
dataset, so every lane must reproduce its sequential trace exactly
(asserted here and in tests/test_optimizer.py):

- ``sequential`` — one ``CherryPick.search``/``Arrow.search`` per
  scenario (scipy GP per BO round, Python loops);
- ``batched``    — ``optimizer.replay``: all lanes advanced per round
  inside one scanned, vmapped, donated-carry device dispatch. The warm
  row is measured with compile caches populated (one prior replay of
  the same shapes), matching the steady state the trace-count tests
  assert; compile time is reported separately;
- ``sharded``    — the same dispatch with the lane axis partitioned
  over the 1-D device mesh (``common.mesh``); bit-identical picks;
- ``pipelined``  — ``optimizer.replay_scenarios`` on the *large*
  fleet-sweep matrix (12 seeds x 4 fleet conditions, the degraded ones
  derived through the real store path and DEFERRED so the drift
  simulation runs inside the overlap window). A matrix that wide runs
  as lane blocks of ``scenarios.REPLAY_BLOCK_LANES``, two in flight on
  one thread: block N+1's tables are built on the host while block N
  scans. XLA on the CPU executes synchronously, so here the blocks
  run one after the other. The wall clock *includes* all host work;
  the baseline ``large.unpipelined.wall_s`` is the same matrix as one
  dispatch (``replay_scenarios(devices=...)``). Both are measured
  rep-interleaved and reported as medians (ambient load hits both
  paths equally);
- ``seeded``     — the same scanned program fed the compact
  ``SeededLaneSpec`` instead of host-materialized lane tables: every
  stochastic table cell is re-derived *inside* the compiled program
  from counter-based fold-in keys (``common.rng``), bit-identical
  picks asserted against the host-table replay. ``huge.*`` scales the
  fleet sweep to a 10^4-lane matrix where host table construction
  dominates: ``huge.lane_tables_s`` vs ``huge.spec_s`` is the
  O(L*C*D) -> O(W*C + L) host-work drop, and the seeded block path's
  end-to-end wall clock is compared against the host-table block path
  on the identical matrix (rep-interleaved).

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (or
``benchmarks/run.py --devices N``) to exercise the multi-device rows
on a CPU-only machine.

Machine scores come from a deterministic profile-derived stand-in
(scoring inputs, not model quality, are under test — the fingerprint
training path is benchmarked by bench_tuning/bench_fingerprint).
"""

from __future__ import annotations

import os
import time

import numpy as np

#: Regression-gate policy for this module's tracked rows (see
#: ``benchmarks.gate`` / bench_fleet.POLICIES for the rationale).
#: Trace-parity rows gate at threshold 0: ANY drop below the all-lanes
#: ratio of 1.0 is a correctness regression, not noise. Compile-time
#: rows gate loosely (compiles are one-off and XLA-version dependent);
#: the pipelined-speedup ratio is informational because it is
#: hardware-ceiling-bound on small CI runners (see README
#: "Multi-device replay").
POLICIES = {
    "optimizer.sequential.searches_per_s": ("higher", 10.0),
    "optimizer.batched.searches_per_s": ("higher", 10.0),
    "optimizer.sharded.searches_per_s": ("higher", 10.0),
    "optimizer.seeded.searches_per_s": ("higher", 10.0),
    "optimizer.large.pipelined.searches_per_s": ("higher", 10.0),
    "optimizer.speedup": ("higher", 15.0),
    "optimizer.trace_parity": ("higher", 0.0),
    "optimizer.seeded.trace_parity": ("higher", 0.0),
    "optimizer.batched.compile_s": ("lower", 25.0),
    "optimizer.seeded.compile_s": ("lower", 25.0),
    "optimizer.seeded.spec_s": ("lower", 50.0),
    "optimizer.lane_tables_s": "info",  # ~0 on quick matrices
    "optimizer.large.unpipelined.wall_s": ("lower", 15.0),
    "optimizer.large.pipelined.wall_s": ("lower", 15.0),
    "optimizer.large.pipelined.speedup": "info",
    "optimizer.large.pipelined.seeded.wall_s": ("lower", 15.0),
    "optimizer.large.pipelined.seeded.speedup": "info",
    "optimizer.mean_runs_per_search": "info",
    "optimizer.wall_s": "info",
}


def _profile_scores(vm_types):
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


def _conditions(seed: int = 0):
    """Healthy plus one degraded fleet derived through the real
    fleet-drift path (store + EWMA analytics on a simulated fleet
    whose c4 nodes lose cpu quality)."""
    from repro.optimizer import HEALTHY, drifted_condition

    degraded = drifted_condition(
        ("c4.large", "c4.xlarge", "c4.2xlarge"),
        name="c4-cpu-degraded", seed=seed)
    return (HEALTHY, degraded)


def _best_of(fn, reps: int = 3):
    """Min wall clock over ``reps`` runs (the 2-core CI boxes are
    noisy); returns (seconds, last result)."""
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _assert_parity(ref_traces, got_traces):
    """Lane-for-lane trace equality (evaluated keys + best-cost curve)."""
    assert len(ref_traces) == len(got_traces)
    for a, b in zip(ref_traces, got_traces):
        assert [c.key for c in a.evaluated] == \
            [c.key for c in b.evaluated], "seeded lane diverged"
        assert a.best_valid_cost == b.best_valid_cost


def _interleaved_medians(fns, reps: int = 5):
    """Median wall clock per callable, measured round-robin so ambient
    load hits every path equally; returns (medians, last results)."""
    import statistics

    times = [[] for _ in fns]
    outs = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            outs[i] = fn()
            times[i].append(time.perf_counter() - t0)
    return [statistics.median(t) for t in times], outs


def _large_matrix(ds, n_seeds: int, workloads=None):
    """The scaled fleet-sweep matrix: every seed replayed under the
    healthy fleet plus drift-derived degraded fleets whose conditions
    are DEFERRED — the store-path simulation runs during lane-table
    construction, i.e. inside the pipelined overlap window.
    Condition-major order keeps each lane block on few conditions."""
    from repro.optimizer import HEALTHY, build_scenarios, \
        drifted_condition

    conds = tuple(
        drifted_condition((vm,), aspects=(aspect,), seed=i,
                          name=f"sweep-{vm}-{aspect}", deferred=True)
        for i, (vm, aspect) in enumerate(
            (("c4.large", "cpu"), ("m4.xlarge", "memory"),
             ("r4.large", "disk"))))
    return build_scenarios(ds, workloads=workloads,
                           seeds=tuple(range(n_seeds)),
                           conditions=(HEALTHY,) + conds,
                           condition_major=True)


def run(rows, n_workloads: int = 18, n_seeds: int = 3,
        quick: bool = False):
    import jax

    from repro.common.mesh import pow2_devices, shard_size
    from repro.optimizer import (build_scenarios, lane_spec,
                                 lane_tables, reference_search, replay,
                                 replay_scenarios,
                                 replay_seeded, traces_from_result,
                                 traces_from_spec, REPLAY_TRACES,
                                 ReplayConfig)
    from repro.tuning.scout import (ScoutDataset, VM_TYPES,
                                    WORKLOAD_NAMES)

    if quick:
        n_workloads, n_seeds = 3, 1
    cfg = ReplayConfig()
    ds = ScoutDataset(seed=0)
    scores = _profile_scores(VM_TYPES)
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:n_workloads],
                            seeds=tuple(range(n_seeds)),
                            conditions=_conditions())
    devices = pow2_devices(jax.devices())
    n_dev = len(devices)

    # --- batched replay: compile, then the warm steady state ---------
    t0 = time.perf_counter()
    tab = lane_tables(ds, scens, scores, cfg)
    t_tables = time.perf_counter() - t0
    t0 = time.perf_counter()
    replay(tab, cfg)
    t_compile = time.perf_counter() - t0
    traces0 = REPLAY_TRACES.count
    t_bat, result = _best_of(lambda: replay(tab, cfg))
    batched = traces_from_result(tab, result, ds.configs)
    assert REPLAY_TRACES.count == traces0  # warm: no retracing

    # --- seeded replay: tables generated inside the program ----------
    t0 = time.perf_counter()
    spec = lane_spec(ds, scens, scores, cfg)
    t_spec = time.perf_counter() - t0
    t0 = time.perf_counter()
    replay_seeded(spec, cfg)
    t_seed_compile = time.perf_counter() - t0
    t_seed, seed_result = _best_of(lambda: replay_seeded(spec, cfg))
    assert np.array_equal(seed_result.chosen, result.chosen)
    assert np.array_equal(seed_result.count, result.count)
    seeded_traces = traces_from_spec(spec, seed_result, ds.configs)

    # --- sharded whole-matrix dispatch (lane axis over the mesh) -----
    replay(tab, cfg, devices=devices)  # compile
    t_shard, shard_result = _best_of(
        lambda: replay(tab, cfg, devices=devices))
    assert np.array_equal(shard_result.chosen, result.chosen)
    assert np.array_equal(shard_result.count, result.count)

    # --- large fleet-sweep matrix: lane blocks vs one dispatch -------
    # (the multi-device acceptance measurement; deferred store-path
    # conditions resolve inside the overlap window, so each rep builds
    # a fresh matrix)
    large_seeds = 1 if quick else 12
    large_wls = WORKLOAD_NAMES[:n_workloads] if quick else None

    def large():
        return _large_matrix(ds, large_seeds, workloads=large_wls)

    n_large = len(large())
    replay_scenarios(ds, large(), scores, cfg, devices=devices)  # warm
    replay_scenarios(ds, large(), scores, cfg)  # warm
    replay_scenarios(ds, large(), scores, cfg, seeded=True)  # warm
    ((t_unpipe, t_pipe, t_pipe_seed),
     (large_ref, large_piped, large_seeded)) = _interleaved_medians(
        (lambda: replay_scenarios(ds, large(), scores, cfg,
                                  devices=devices),
         lambda: replay_scenarios(ds, large(), scores, cfg),
         lambda: replay_scenarios(ds, large(), scores, cfg,
                                  seeded=True)),
        reps=2 if quick else 5)

    # --- huge fleet sweep: the matrix host tables can't keep up with -
    # (spec build is O(W*C + L); lane-table build is O(L*C*D) and
    # dominates the host side at this scale)
    huge = {}
    if not quick:
        huge_scens = _large_matrix(ds, 35)  # 18 x 35 x 4 x 4 = 10080
        t0 = time.perf_counter()
        huge_tab = lane_tables(ds, huge_scens, scores, cfg)
        t_huge_tab = time.perf_counter() - t0
        t0 = time.perf_counter()
        huge_spec = lane_spec(ds, huge_scens, scores, cfg)
        t_huge_spec = time.perf_counter() - t0
        del huge_tab, huge_spec
        # warm both lowerings over the full sweep: the huge matrix's
        # condition-boundary blocks hit n_conds signatures the large
        # phase never compiled
        replay_scenarios(ds, huge_scens, scores, cfg)
        replay_scenarios(ds, huge_scens, scores, cfg, seeded=True)
        ((t_huge_host, t_huge_seed),
         (huge_host_traces, huge_seeded_traces)) = _interleaved_medians(
            (lambda: replay_scenarios(ds, huge_scens, scores, cfg),
             lambda: replay_scenarios(ds, huge_scens, scores, cfg,
                                      seeded=True)),
            reps=1)
        _assert_parity(huge_host_traces, huge_seeded_traces)
        huge = {"lanes": len(huge_scens), "lane_tables_s": t_huge_tab,
                "spec_s": t_huge_spec, "host_wall_s": t_huge_host,
                "seeded_wall_s": t_huge_seed}

    # --- sequential reference loop -----------------------------------
    t0 = time.perf_counter()
    sequential = [reference_search(ds, sc, scores, cfg)
                  for sc in scens]
    t_seq = time.perf_counter() - t0

    # --- per-seed trace parity (the acceptance criterion) ------------
    def diverged(ref, got):
        return ([c.key for c in ref.evaluated]
                != [c.key for c in got.evaluated]
                or ref.best_valid_cost != got.best_valid_cost)

    mismatches = sum(1 for st, bt in zip(sequential, batched)
                     if diverged(st, bt))
    assert mismatches == 0, \
        f"{mismatches}/{len(scens)} lanes diverged from sequential"
    seed_mismatches = sum(1 for st, bt in zip(sequential, seeded_traces)
                          if diverged(st, bt))
    assert seed_mismatches == 0, \
        f"{seed_mismatches}/{len(scens)} seeded lanes diverged"
    assert not any(diverged(rt, pt)
                   for rt, pt in zip(large_ref, large_piped)), \
        "pipelined large-matrix lanes diverged from unpipelined"
    assert not any(diverged(rt, pt)
                   for rt, pt in zip(large_ref, large_seeded)), \
        "seeded pipelined large-matrix lanes diverged"

    n = len(scens)
    sps_seq = n / max(t_seq, 1e-9)
    sps_bat = n / max(t_bat, 1e-9)
    rows.append(("optimizer.scenarios", "", n))
    rows.append(("optimizer.sequential.searches_per_s",
                 f"{t_seq / n * 1e6:.0f}", f"{sps_seq:.1f}"))
    rows.append(("optimizer.batched.searches_per_s",
                 f"{t_bat / n * 1e6:.0f}", f"{sps_bat:.1f}"))
    rows.append(("optimizer.speedup", "",
                 f"{sps_bat / max(sps_seq, 1e-9):.1f}x"))
    rows.append(("optimizer.batched.compile_s", "", f"{t_compile:.2f}"))
    rows.append(("optimizer.lane_tables_s", "", f"{t_tables:.2f}"))
    rows.append(("optimizer.seeded.searches_per_s",
                 f"{t_seed / n * 1e6:.0f}",
                 f"{n / max(t_seed, 1e-9):.1f}"))
    rows.append(("optimizer.seeded.compile_s", "",
                 f"{t_seed_compile:.2f}"))
    rows.append(("optimizer.seeded.spec_s", "", f"{t_spec:.3f}"))
    rows.append(("optimizer.seeded.trace_parity", "",
                 f"{n - seed_mismatches}/{n}"))
    rows.append(("optimizer.batched.dispatches", "", result.dispatches))
    rows.append(("optimizer.batched.traces", "", REPLAY_TRACES.count))
    rows.append(("optimizer.trace_parity", "",
                 f"{n - mismatches}/{n}"))
    mean_runs = float(np.mean(result.count))
    rows.append(("optimizer.mean_runs_per_search", "",
                 f"{mean_runs:.2f}"))
    # --- multi-device / pipelined rows -------------------------------
    rows.append(("optimizer.device_count", "", n_dev))
    rows.append(("optimizer.lanes_per_device", "",
                 shard_size(n, n_dev) // n_dev))
    rows.append(("optimizer.sharded.searches_per_s",
                 f"{t_shard / n * 1e6:.0f}",
                 f"{n / max(t_shard, 1e-9):.1f}"))
    rows.append(("optimizer.large.lanes", "", n_large))
    rows.append(("optimizer.large.unpipelined.wall_s", "",
                 f"{t_unpipe:.3f}"))
    rows.append(("optimizer.large.pipelined.wall_s", "",
                 f"{t_pipe:.3f}"))
    rows.append(("optimizer.large.pipelined.searches_per_s", "",
                 f"{n_large / max(t_pipe, 1e-9):.1f}"))
    rows.append(("optimizer.large.pipelined.speedup", "",
                 f"{t_unpipe / max(t_pipe, 1e-9):.2f}x"))
    rows.append(("optimizer.large.pipelined.seeded.wall_s", "",
                 f"{t_pipe_seed:.3f}"))
    rows.append(("optimizer.large.pipelined.seeded.speedup", "",
                 f"{t_unpipe / max(t_pipe_seed, 1e-9):.2f}x"))
    if huge:
        rows.append(("optimizer.huge.lanes", "", huge["lanes"]))
        rows.append(("optimizer.huge.lane_tables_s", "",
                     f"{huge['lane_tables_s']:.2f}"))
        rows.append(("optimizer.huge.spec_s", "",
                     f"{huge['spec_s']:.3f}"))
        rows.append(("optimizer.huge.table_build_speedup", "",
                     f"{huge['lane_tables_s'] / max(huge['spec_s'], 1e-9):.0f}x"))
        rows.append(("optimizer.huge.pipelined.wall_s", "",
                     f"{huge['host_wall_s']:.2f}"))
        rows.append(("optimizer.huge.pipelined.seeded.wall_s", "",
                     f"{huge['seeded_wall_s']:.2f}"))
        rows.append(("optimizer.huge.pipelined.seeded.searches_per_s",
                     "",
                     f"{huge['lanes'] / max(huge['seeded_wall_s'], 1e-9):.1f}"))
    return {"n_workloads": n_workloads, "n_seeds": n_seeds,
            "variants": 4, "conditions": 2, "lanes": n,
            "max_runs": cfg.max_runs, "device_count": n_dev,
            "cpu_cores": os.cpu_count(),
            "lanes_per_device": shard_size(n, n_dev) // n_dev,
            "large_lanes": n_large,
            **{f"huge_{k}": v for k, v in huge.items()}}
