"""Order-independent scenario RNG: every stochastic quantity is a pure
function of fold-in keys, so query order, dict insertion order and the
eager/jit/vmap boundary can never change a draw. Covers the scout
simulator grid, the counter-based device draws, the columnar suite
runner and the deferred-condition resolve race."""

import threading

import numpy as np
import pytest

from repro.common.rng import (STREAM_CONTENTION, bounded_uniform_grid,
                              folded_generator, lognormal_noise_grid,
                              lognormal_noise_row, stream_key,
                              x64_streams)
from repro.tuning.scout import (VM_TYPES, WORKLOAD_NAMES, ScoutDataset,
                                all_configs, config_uid)


def _scores():
    rng = np.random.default_rng(3)
    return {vm: {a: float(rng.uniform(0.5, 2.0))
                 for a in ("cpu", "memory", "disk", "network")}
            for vm in VM_TYPES}


# ------------------------------------------------- scout order-independence

def test_scout_dataset_call_order_independent():
    """Two fresh datasets queried in opposite orders produce
    bit-identical tables — the draws are keyed by (seed, workload,
    config), not by a shared stream's consumption order."""
    a = ScoutDataset(seed=0)
    b = ScoutDataset(seed=0)
    configs = a.configs
    # a: canonical order; b: reversed workloads, reversed configs,
    # interleaved with scalar queries
    for wl in WORKLOAD_NAMES:
        a.workload_arrays(wl)
    for wl in reversed(WORKLOAD_NAMES):
        b.runtime_s(wl, configs[-1])
        b.low_level_metrics(wl, configs[0])
        b.workload_arrays(wl)
    for wl in WORKLOAD_NAMES:
        rt_a, cost_a, low_a = a.workload_arrays(wl)
        rt_b, cost_b, low_b = b.workload_arrays(wl)
        np.testing.assert_array_equal(rt_a, rt_b)
        np.testing.assert_array_equal(cost_a, cost_b)
        np.testing.assert_array_equal(low_a, low_b)
        for c in (configs[0], configs[7], configs[-1]):
            assert a.runtime_s(wl, c) == b.runtime_s(wl, c)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=10, deadline=None)
    @given(order=st.permutations(list(range(len(WORKLOAD_NAMES)))),
           interleave=st.lists(
               st.tuples(st.integers(0, len(WORKLOAD_NAMES) - 1),
                         st.integers(0, 68)),
               max_size=6))
    def test_scout_dataset_any_query_order_bit_identical(
            order, interleave):
        """Property form: ANY permutation of workload queries,
        interleaved with arbitrary scalar lookups, yields the same
        tables as canonical-order materialization."""
        ref = ScoutDataset(seed=3)
        for wl in WORKLOAD_NAMES:
            ref.workload_arrays(wl)
        probe = ScoutDataset(seed=3)
        configs = probe.configs
        for w, c in interleave:
            probe.runtime_s(WORKLOAD_NAMES[w], configs[c])
        for i in order:
            probe.workload_arrays(WORKLOAD_NAMES[i])
        for wl in WORKLOAD_NAMES:
            for a, b in zip(ref.workload_arrays(wl),
                            probe.workload_arrays(wl)):
                np.testing.assert_array_equal(a, b)
except ImportError:  # pragma: no cover - hypothesis is optional
    pass


def test_scout_dataset_consumer_order_independent():
    """reference_search-first vs lane_tables-first must see the same
    simulator: the PR 4 parity guarantee no longer needs any shared
    warm-up ordering between the two paths."""
    from repro.optimizer import (HEALTHY, build_scenarios, lane_tables,
                                 reference_search)

    scores = _scores()
    ds_seq = ScoutDataset(seed=0)
    ds_tab = ScoutDataset(seed=0)
    scens = build_scenarios(ds_seq, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0,), conditions=(HEALTHY,))
    # consume ds_seq via the sequential tuner first, ds_tab via the
    # stacked tables first
    ref = reference_search(ds_seq, scens[0], scores)
    scens_tab = build_scenarios(ds_tab, workloads=WORKLOAD_NAMES[:2],
                                seeds=(0,), conditions=(HEALTHY,))
    tab = lane_tables(ds_tab, scens_tab, scores)
    for wl in WORKLOAD_NAMES[:2]:
        rt_a, cost_a, low_a = ds_seq.workload_arrays(wl)
        rt_b, cost_b, low_b = ds_tab.workload_arrays(wl)
        np.testing.assert_array_equal(rt_a, rt_b)
        np.testing.assert_array_equal(cost_a, cost_b)
        np.testing.assert_array_equal(low_a, low_b)
    np.testing.assert_array_equal(
        tab.runtime[0], ds_seq.workload_arrays(WORKLOAD_NAMES[0])[0])
    assert ref.search_cost > 0.0


def test_scout_seeds_differ_and_grid_matches_scalar_path():
    ds0, ds1 = ScoutDataset(seed=0), ScoutDataset(seed=1)
    wl = WORKLOAD_NAMES[0]
    assert not np.array_equal(ds0.workload_arrays(wl)[0],
                              ds1.workload_arrays(wl)[0])
    # scalar accessor returns exactly the grid cell
    for c in (ds0.configs[0], ds0.configs[33]):
        col = [cc.key for cc in ds0.configs].index(c.key)
        assert ds0.runtime_s(wl, c) == ds0.workload_arrays(wl)[0][col]


def test_config_uid_stable_under_grid_extension():
    """uids depend only on (vm_type, count), never on grid position —
    extending the config grid cannot re-key existing draws."""
    configs = all_configs()
    uids = [config_uid(c) for c in configs]
    assert len(set(uids)) == len(uids)
    assert all(u == VM_TYPES.index(c.vm_type) * 256 + c.count
               for u, c in zip(uids, configs))


# --------------------------------------------- counter-based device draws

def test_noise_draws_identical_across_jit_and_vmap():
    """The contention draw for a (workload, config) cell is the same
    number under jit, under jit(vmap), and inside the grid helper —
    the seeded device program's parity rests on this. (The *eager*
    op-by-op path may differ by 1 ulp from the compiled one — erf/exp
    fuse differently — which is why both the host grid and the replay
    program run jitted.)"""
    import jax
    import jax.numpy as jnp

    key = stream_key(0, STREAM_CONTENTION)
    uids = np.asarray([config_uid(c) for c in all_configs()], np.int32)
    grid = lognormal_noise_grid(key, len(WORKLOAD_NAMES), uids, 0.06)
    assert grid.shape == (len(WORKLOAD_NAMES), len(uids))
    assert grid.dtype == np.float64
    with x64_streams():
        k, u = jnp.asarray(key), jnp.asarray(uids)
        row_eager = np.asarray(lognormal_noise_row(k, 3, u, 0.06))
        row_jit = np.asarray(jax.jit(
            lambda k, u: lognormal_noise_row(k, 3, u, 0.06))(k, u))
        rows_vmap_jit = np.asarray(jax.jit(jax.vmap(
            lambda w: lognormal_noise_row(k, w, u, 0.06)))(
            jnp.arange(len(WORKLOAD_NAMES))))
    np.testing.assert_array_equal(row_jit, grid[3])
    np.testing.assert_array_equal(rows_vmap_jit, grid)
    np.testing.assert_allclose(row_eager, grid[3], rtol=1e-15)


def test_bounded_uniform_grid_is_per_cell_keyed():
    key = stream_key(7, 1)
    lo = np.asarray([0.0, 10.0])
    hi = np.asarray([1.0, 20.0])
    g = bounded_uniform_grid(key, 4, lo, hi)
    assert g.shape == (4, 2)
    assert np.all((g >= lo) & (g <= hi))
    # a single row re-derived standalone matches the full grid's row
    np.testing.assert_array_equal(
        bounded_uniform_grid(key, 4, lo, hi)[2], g[2])


def test_folded_generator_path_keyed():
    a = folded_generator(0, 1, "net-slots")
    b = folded_generator(0, 1, "net-slots")
    c = folded_generator(0, 2, "net-slots")
    x = a.uniform(size=5)
    np.testing.assert_array_equal(x, b.uniform(size=5))
    assert not np.array_equal(x, c.uniform(size=5))


# --------------------------------------------------- suite runner frames

def test_run_frame_machine_dict_order_independent():
    """Dict insertion order of the fleet map must not change any draw:
    the per-group generators are keyed by (seed, round, benchmark
    type, machine type) and nodes iterate sorted."""
    from repro.fingerprint.runner import SuiteRunner

    machines = {"b": "n2-standard-4", "a": "e2-medium",
                "c": "n2-standard-4"}
    shuffled = {"a": "e2-medium", "c": "n2-standard-4",
                "b": "n2-standard-4"}
    rec_a = SuiteRunner(seed=0).run(machines, runs_per_type=3,
                                    stress_fraction=0.3)
    rec_b = SuiteRunner(seed=0).run(shuffled, runs_per_type=3,
                                    stress_fraction=0.3)

    def canon(records):
        return sorted((r.machine, r.benchmark_type, r.t, r.stressed,
                       tuple(sorted(r.metrics.items())),
                       tuple(sorted(r.node_metrics.items())))
                      for r in records)

    assert canon(rec_a) == canon(rec_b)


def test_run_frame_rounds_draw_fresh_values():
    from repro.fingerprint.runner import SuiteRunner

    runner = SuiteRunner(seed=0)
    machines = {"a": "e2-medium"}
    f1 = runner.run_frame(machines, runs_per_type=2)
    f2 = runner.run_frame(machines, runs_per_type=2)
    assert not np.array_equal(f1.metrics, f2.metrics)
    # ...but a fresh runner replays round 0 exactly
    g1 = SuiteRunner(seed=0).run_frame(machines, runs_per_type=2)
    np.testing.assert_array_equal(f1.metrics, g1.metrics)


# ------------------------------------------------ deferred-resolve race

def test_deferred_condition_resolves_once_under_concurrency():
    """Concurrent resolvers (the pipelined per-device workers) must
    run the factory exactly once and all observe the same object —
    a second FleetCondition would split the id()-keyed table caches."""
    from repro.optimizer import DeferredFleetCondition, FleetCondition

    calls = []
    gate = threading.Barrier(8)

    def factory():
        calls.append(1)
        return FleetCondition("deg", {"c4.large": {"cpu": 0.4}})

    lazy = DeferredFleetCondition("deg", factory)
    out = [None] * 8

    def resolve(i):
        gate.wait()
        out[i] = lazy.resolve()

    threads = [threading.Thread(target=resolve, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert calls == [1]
    assert all(o is out[0] for o in out)
    assert lazy.resolved


# -------------------------------------------- seeded replay round trips

def test_seeded_spec_is_compact():
    """The seeded spec must stay O(W*C + K*C + L): no array may carry
    both the lane axis and the candidate axis."""
    from repro.optimizer import HEALTHY, build_scenarios, lane_spec

    ds = ScoutDataset(seed=0)
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:4],
                            seeds=(0, 1, 2), conditions=(HEALTHY,))
    spec = lane_spec(ds, scens, _scores())
    n_lanes, n_cand = len(scens), len(ds.configs)
    assert len(spec) == n_lanes
    for name in ("workload_id", "condition_id", "variant_id", "limit"):
        assert getattr(spec, name).shape == (n_lanes,)
    for arr in (spec.base_runtime, spec.low_num, spec.x_base,
                spec.norm_scores, spec.fp_low):
        assert n_lanes not in arr.shape or n_lanes == n_cand
    assert spec.norm_scores.shape == (1, n_cand, 4)


@pytest.mark.slow
def test_seeded_replay_matches_sequential_traces():
    """Acceptance: the in-program-generated tables reproduce the
    sequential scipy searches exactly, across variants, seeds and a
    degraded condition."""
    from repro.optimizer import (HEALTHY, FleetCondition,
                                 build_scenarios, lane_spec,
                                 reference_search, replay_seeded,
                                 traces_from_spec)

    ds = ScoutDataset(seed=0)
    scores = _scores()
    cond = FleetCondition("deg", {"c4.large": {"cpu": 0.3},
                                  "m4.xlarge": {"memory": 0.4}})
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:3],
                            seeds=(0, 1), conditions=(HEALTHY, cond))
    spec = lane_spec(ds, scens, scores)
    traces = traces_from_spec(spec, replay_seeded(spec), ds.configs)
    assert len(traces) == len(scens)
    for sc, bt in zip(scens, traces):
        seq = reference_search(ds, sc, scores)
        assert [c.key for c in seq.evaluated] == \
            [c.key for c in bt.evaluated], sc
        assert seq.best_valid_cost == bt.best_valid_cost, sc
        assert seq.costs == bt.costs and seq.runtimes == bt.runtimes
