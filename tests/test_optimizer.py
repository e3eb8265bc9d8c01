"""Batched BO replay engine: GP pinned against the scipy reference,
per-seed trace parity with CherryPick/Arrow, Perona-weighting
equivalence, degraded-fleet scenarios, compile amortization, sharded
lane-axis bit parity and the block path of wide matrices."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from _trace_utils import expect_traces

from repro.optimizer import (HEALTHY, FleetCondition, ReplayConfig,
                             REPLAY_TRACES, build_scenarios,
                             condition_from_drift, degrade_scores,
                             lane_spec, lane_tables, reference_search,
                             replay, replay_scenarios, replay_seeded,
                             simulate_degraded_fleet,
                             traces_from_result, traces_from_spec)
from repro.tuning.scout import ScoutDataset, VM_TYPES, WORKLOAD_NAMES


@pytest.fixture(scope="module")
def ds():
    return ScoutDataset(seed=0)


@pytest.fixture(scope="module")
def machine_scores():
    """Deterministic fingerprint-score stand-in (scores, not model
    quality, are under test here; the trained path is covered by
    test_tuning)."""
    rng = np.random.default_rng(3)
    return {vm: {a: float(rng.uniform(0.5, 2.0))
                 for a in ("cpu", "memory", "disk", "network")}
            for vm in VM_TYPES}


@pytest.fixture(scope="module")
def degraded_condition():
    report, node_types = simulate_degraded_fleet(
        ("c4.large", "c4.xlarge"), degraded={"c4.large": ("cpu",),
                                             "c4.xlarge": ("cpu",)},
        seed=1)
    return condition_from_drift("c4-cpu", report, node_types)


# ------------------------------------------------------------ GP parity

def test_batched_gp_matches_scipy_reference():
    """Masked padded jnp fit/predict == dense scipy fit/predict."""
    import jax.numpy as jnp

    from repro.optimizer.gp import gp_fit, gp_predict
    from repro.tuning.gp import GP

    rng = np.random.default_rng(0)
    with jax.enable_x64():
        for m in (1, 2, 3, 5, 9):
            X = rng.normal(size=(m, 4))
            y = rng.normal(size=m) * 3.0 + 1.0
            Xs = rng.normal(size=(12, 4))
            ref = GP(noise=1e-3).fit(X, y)
            mu_ref, sd_ref = ref.predict(Xs)

            P = 16
            Xp = np.zeros((P, 4))
            Xp[:m] = X
            yp = np.zeros(P)
            yp[:m] = y
            mask = np.arange(P) < m
            state = gp_fit(jnp.asarray(Xp), jnp.asarray(yp),
                           jnp.asarray(mask), noise=1e-3)
            mu, sd = gp_predict(state, jnp.asarray(Xs))
            np.testing.assert_allclose(np.asarray(mu), mu_ref,
                                       rtol=1e-9, atol=1e-9)
            np.testing.assert_allclose(np.asarray(sd), sd_ref,
                                       rtol=1e-6, atol=1e-8)
            # length scales equal the reference's median heuristic
            np.testing.assert_allclose(np.asarray(state.scales),
                                       ref.scales, rtol=0, atol=0)


def test_gp_cholesky_matches_lapack():
    """The hand-written factorization equals LAPACK's to rounding,
    including a masked (identity-padded) observation block."""
    import jax.numpy as jnp

    from repro.optimizer.gp import cholesky

    rng = np.random.default_rng(2)
    a = rng.normal(size=(16, 16))
    k = a @ a.T + 16.0 * np.eye(16)
    k[10:, :] = 0.0
    k[:, 10:] = 0.0
    k[10:, 10:] = np.eye(6) * 1.001
    with jax.enable_x64():
        got = np.asarray(cholesky(jnp.asarray(k)))
    np.testing.assert_allclose(got, np.linalg.cholesky(k), rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("masked_from", [16, 10, 3, 1])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tril_inverse_matches_numpy(seed, masked_from):
    """The unrolled inverse of the Cholesky factor equals numpy's
    inverse of LAPACK's factor, including matrices whose trailing slots
    are the masked identity block (1 + noise on the diagonal)."""
    import jax.numpy as jnp

    from repro.optimizer.gp import cholesky, tril_inverse

    rng = np.random.default_rng(seed)
    a = rng.normal(size=(16, 16))
    k = a @ a.T + 16.0 * np.eye(16)
    k[masked_from:, :] = 0.0
    k[:, masked_from:] = 0.0
    k[masked_from:, masked_from:] = np.eye(16 - masked_from) * 1.001
    ref = np.linalg.inv(np.linalg.cholesky(k))
    with jax.enable_x64():
        got = np.asarray(tril_inverse(cholesky(jnp.asarray(k))))
    np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-12)
    assert np.all(np.triu(got, 1) == 0.0)
    np.testing.assert_array_equal(
        got[masked_from:, masked_from:],
        np.eye(16 - masked_from) / np.sqrt(1.001))


def test_batched_ei_matches_numpy():
    import jax.numpy as jnp

    from repro.optimizer.acquire import expected_improvement as ei_jnp
    from repro.tuning.gp import expected_improvement as ei_np

    rng = np.random.default_rng(1)
    mu = rng.normal(size=50)
    sigma = np.abs(rng.normal(size=50)) + 1e-3
    with jax.enable_x64():
        got = np.asarray(ei_jnp(jnp.asarray(mu), jnp.asarray(sigma),
                                0.3))
    ref = ei_np(mu, sigma, 0.3)
    np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-15)
    assert np.all(ref >= 0) and np.all(got >= 0)


# --------------------------------------------------------- trace parity

def _assert_trace_equal(seq, bat, scenario):
    label = (scenario.workload, scenario.seed, scenario.variant,
             scenario.condition.name)
    assert [c.key for c in seq.evaluated] == \
        [c.key for c in bat.evaluated], label
    assert seq.best_valid_cost == bat.best_valid_cost, label
    assert seq.costs == bat.costs, label
    assert seq.runtimes == bat.runtimes, label
    assert seq.search_cost == bat.search_cost, label


def test_replay_matches_sequential_traces(ds, machine_scores,
                                          degraded_condition):
    """The acceptance criterion: every lane reproduces its sequential
    numpy search exactly — same evaluated configs, same
    best-valid-cost curve — across variants, seeds and conditions."""
    scens = build_scenarios(
        ds, workloads=WORKLOAD_NAMES[:3], seeds=(0, 1),
        conditions=(HEALTHY, degraded_condition))
    traces = replay_scenarios(ds, scens, machine_scores)
    assert len(traces) == len(scens) == 3 * 2 * 4 * 2
    for sc, bt in zip(scens, traces):
        _assert_trace_equal(reference_search(ds, sc, machine_scores),
                            bt, sc)


def test_perona_lanes_reproduce_weighter_rankings(ds, machine_scores):
    """The pure-array weighting reproduces the sequential
    ``PeronaAcquisitionWeighter`` bit-for-bit on the same inputs."""
    import jax.numpy as jnp

    from repro.core.ranking import machine_score_matrix
    from repro.optimizer.acquire import perona_weight_factors
    from repro.tuning.perona_weights import (PeronaAcquisitionWeighter,
                                             normalized_machine_scores)
    from repro.tuning.scout import PRICES

    weighter = PeronaAcquisitionWeighter(ds, machine_scores)
    wl = WORKLOAD_NAMES[0]
    evaluated = [ds.configs[i] for i in (3, 17, 40)]
    rng = np.random.default_rng(0)
    acq = np.abs(rng.normal(size=len(ds.configs)))
    ref = weighter(ds.configs, acq, workload=wl, evaluated=evaluated,
                   any_valid=True)

    norm = normalized_machine_scores(machine_scores)
    ns = np.stack([norm[c.vm_type] for c in ds.configs])
    prices = np.asarray([PRICES[c.vm_type] for c in ds.configs])
    util = np.mean([ds.low_level_metrics(wl, c) for c in evaluated],
                   axis=0)
    with jax.enable_x64():
        factors = np.asarray(perona_weight_factors(
            jnp.asarray(util), jnp.asarray(ns), jnp.asarray(prices),
            True))
    got = acq * factors
    np.testing.assert_allclose(got, ref, rtol=1e-12)
    np.testing.assert_array_equal(np.argsort(got), np.argsort(ref))
    # the weighter's normalized machine-score vectors are exactly the
    # batched matrix rows (core.ranking batched-input form)
    mats = machine_score_matrix(machine_scores, list(machine_scores))
    assert mats.shape == (len(machine_scores), 4)
    for i, vm in enumerate(machine_scores):
        np.testing.assert_array_equal(
            weighter.norm_scores[vm], norm[vm])


def test_degraded_condition_changes_search(ds, machine_scores,
                                           degraded_condition):
    """Degrading a machine type's fingerprint must actually steer the
    weighted lanes: scores drop for the degraded type and the scenario
    matrix produces at least one different trace vs healthy."""
    degraded = degrade_scores(machine_scores, degraded_condition)
    assert degraded["c4.large"]["cpu"] < machine_scores["c4.large"]["cpu"]
    assert degraded["c4.large"]["memory"] == \
        machine_scores["c4.large"]["memory"]
    healthy = build_scenarios(ds, workloads=WORKLOAD_NAMES[:6],
                              seeds=(0,),
                              variants=("cherrypick+perona",),
                              conditions=(HEALTHY,))
    sick = build_scenarios(ds, workloads=WORKLOAD_NAMES[:6],
                           seeds=(0,),
                           variants=("cherrypick+perona",),
                           conditions=(degraded_condition,))
    t_h = replay_scenarios(ds, healthy, machine_scores)
    t_s = replay_scenarios(ds, sick, machine_scores)
    assert any([c.key for c in a.evaluated] !=
               [c.key for c in b.evaluated]
               for a, b in zip(t_h, t_s))


def test_distinct_conditions_sharing_a_name(ds, machine_scores):
    """Condition tables cache by object, not by name: two different
    conditions named alike must produce different lane tables."""
    cfg = ReplayConfig()
    a = FleetCondition("degraded", {"c4.large": {"cpu": 0.5}})
    b = FleetCondition("degraded", {"r4.large": {"disk": 0.5}})
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:1],
                            seeds=(0,), variants=("cherrypick+perona",),
                            conditions=(a, b))
    tab = lane_tables(ds, scens, machine_scores, cfg)
    assert not np.array_equal(tab.norm_scores[0], tab.norm_scores[1])


def test_replay_compile_amortized(ds, machine_scores):
    """Same lane/slot shapes -> one tracing total (donated-carry scan
    is reused; REPLAY_TRACES is the shared TraceCount pattern)."""
    cfg = ReplayConfig()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    tab = lane_tables(ds, scens, machine_scores, cfg)
    replay(tab, cfg)  # compile (or reuse an earlier test's program)
    with expect_traces(REPLAY_TRACES, 0):
        r1 = replay(tab, cfg)
        r2 = replay(tab, cfg)
    np.testing.assert_array_equal(r1.chosen, r2.chosen)
    assert r1.dispatches == 1


def _assert_same_traces(ref_traces, got_traces):
    assert len(ref_traces) == len(got_traces)
    for a, b in zip(ref_traces, got_traces):
        assert [c.key for c in a.evaluated] == \
            [c.key for c in b.evaluated]
        assert a.best_valid_cost == b.best_valid_cost


def test_pipelined_matches_unpipelined(ds, machine_scores, monkeypatch):
    """A matrix wide enough for two blocks runs as lane blocks, two in
    flight; with ``devices=`` it keeps its one (sharded) dispatch. Both
    give the same traces, picks and counts lane for lane: blocks never
    interact."""
    from repro.optimizer import scenarios

    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    (ref, whole), whole_evs = _replay_spans(lambda: replay_scenarios(
        ds, scens, machine_scores, return_result=True,
        devices=jax.devices()))
    got, split = replay_scenarios(ds, scens, machine_scores,
                                  return_result=True)
    _assert_same_traces(ref, got)
    np.testing.assert_array_equal(whole.chosen, split.chosen)
    np.testing.assert_array_equal(whole.count, split.count)
    assert whole.dispatches == 1 and split.dispatches == 2
    (launch,) = [e.args for e in whole_evs if e.name == "replay.dispatch"]
    assert (launch["block"], launch["blocks"]) == (0, 1)


def test_deferred_condition_resolves_lazily(ds, machine_scores):
    """A DeferredFleetCondition derives its drops on first use inside
    lane_tables (once, cached) and reproduces the eager condition's
    lanes exactly; building the scenario matrix never resolves it."""
    from repro.optimizer import DeferredFleetCondition, resolve_condition

    calls = []
    eager = FleetCondition("deg", {"c4.large": {"cpu": 0.4}})

    def factory():
        calls.append(1)
        return eager

    lazy = DeferredFleetCondition("deg", factory)
    kwargs = dict(workloads=WORKLOAD_NAMES[:1], seeds=(0,),
                  variants=("cherrypick+perona",))
    lazy_scens = build_scenarios(ds, conditions=(lazy,),
                                 condition_major=True, **kwargs)
    assert calls == [] and not lazy.resolved
    cfg = ReplayConfig()
    tab_lazy = lane_tables(ds, lazy_scens, machine_scores, cfg)
    assert calls == [1] and lazy.resolved
    lane_tables(ds, lazy_scens, machine_scores, cfg)
    assert calls == [1]  # cached
    eager_scens = build_scenarios(ds, conditions=(eager,), **kwargs)
    tab_eager = lane_tables(ds, eager_scens, machine_scores, cfg)
    np.testing.assert_array_equal(tab_lazy.norm_scores,
                                  tab_eager.norm_scores)
    assert resolve_condition(lazy).score_drop == eager.score_drop
    assert resolve_condition(eager) is eager


def test_condition_major_order_same_traces(ds, machine_scores):
    """condition_major reorders the matrix but every scenario's trace
    is unchanged (scenario-keyed comparison across orders)."""
    conds = (HEALTHY, FleetCondition("deg", {"r4.large": {"disk": 0.5}}))
    kwargs = dict(workloads=WORKLOAD_NAMES[:2], seeds=(0, 1),
                  conditions=conds)
    a = build_scenarios(ds, **kwargs)
    b = build_scenarios(ds, condition_major=True, **kwargs)
    assert sorted(map(repr, a)) == sorted(map(repr, b)) and a != b
    ta = {repr(s): t for s, t in
          zip(a, replay_scenarios(ds, a, machine_scores))}
    tb = {repr(s): t for s, t in
          zip(b, replay_scenarios(ds, b, machine_scores))}
    for k in ta:
        assert [c.key for c in ta[k].evaluated] == \
            [c.key for c in tb[k].evaluated]
        assert ta[k].best_valid_cost == tb[k].best_valid_cost


@pytest.mark.parametrize("seeded", [False, True],
                         ids=["host", "seeded"])
def test_pipelined_empty_and_partial_block(ds, machine_scores,
                                           monkeypatch, seeded):
    """Both lowerings: an empty matrix gives no traces and no dispatch,
    a one-lane matrix its reference trace, and a matrix one lane past a
    block runs as a full block and a one-lane block with the whole
    matrix's traces."""
    from repro.optimizer import scenarios

    traces, result = replay_scenarios(ds, [], machine_scores,
                                      return_result=True, seeded=seeded)
    assert traces == [] and result.dispatches == 0
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:1],
                            seeds=(0, 1), conditions=(HEALTHY,))
    (got,) = replay_scenarios(ds, scens[:1], machine_scores,
                              seeded=seeded)
    _assert_trace_equal(reference_search(ds, scens[0], machine_scores),
                        got, scens[0])
    scens = scens + scens[:1]
    ref = replay_scenarios(ds, scens, machine_scores, seeded=seeded)
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    got, split = replay_scenarios(ds, scens, machine_scores,
                                  return_result=True, seeded=seeded)
    assert len(scens) == 9 and split.dispatches == 2
    _assert_same_traces(ref, got)


@pytest.mark.slow
def test_trace_amortized_across_lane_counts(ds, machine_scores,
                                            degraded_condition,
                                            monkeypatch):
    """100-, 200- and 432-lane matrices: the one-dispatch path compiles
    one program per pow2 lane bucket (128/256/512) and reuses it, the
    block path reuses ONE 64-lane block program across all three
    matrix sizes."""
    from repro.optimizer import scenarios

    cfg = ReplayConfig()
    scens = build_scenarios(ds, seeds=(0, 1, 2),
                            conditions=(HEALTHY, degraded_condition))
    assert len(scens) == 432
    sizes = (100, 200, 432)
    tabs = {n: lane_tables(ds, scens[:n], machine_scores, cfg)
            for n in sizes}
    results = {}
    for n in sizes:  # warm each pow2 bucket (<= 1 tracing per bucket)
        before = REPLAY_TRACES.count
        results[n] = replay(tabs[n], cfg)
        assert REPLAY_TRACES.count - before <= 1
    with expect_traces(REPLAY_TRACES, 0):  # every bucket amortized
        for n in sizes:
            again = replay(tabs[n], cfg)
            np.testing.assert_array_equal(again.chosen,
                                          results[n].chosen)

    # blocks of 64 lanes -> one program for ALL sizes
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 64)
    replay_scenarios(ds, scens[:100], machine_scores,
                     cfg)  # warm the single block shape
    with expect_traces(REPLAY_TRACES, 0):
        for n in (200, 432):
            got, split = replay_scenarios(ds, scens[:n], machine_scores,
                                          cfg, return_result=True)
            assert split.dispatches == -(-n // 64)
            _assert_same_traces(
                traces_from_result(tabs[n], results[n], ds.configs),
                got)


# ----------------------------------------------------- seeded replay

def test_seeded_replay_bit_identical_to_host_tables(
        ds, machine_scores, degraded_condition):
    """The in-program table generation (seeded spec, counter-based
    noise re-drawn on device) reproduces the host-materialized lane
    tables' replay bit-for-bit: same selections, same counts, same
    traces — across variants and a degraded condition."""
    cfg = ReplayConfig()
    scens = build_scenarios(
        ds, workloads=WORKLOAD_NAMES[:3], seeds=(0, 1),
        conditions=(HEALTHY, degraded_condition))
    tab = lane_tables(ds, scens, machine_scores, cfg)
    host = replay(tab, cfg)
    spec = lane_spec(ds, scens, machine_scores, cfg)
    seeded = replay_seeded(spec, cfg)
    np.testing.assert_array_equal(host.chosen, seeded.chosen)
    np.testing.assert_array_equal(host.count, seeded.count)
    for a, b in zip(traces_from_result(tab, host, ds.configs),
                    traces_from_spec(spec, seeded, ds.configs)):
        assert [c.key for c in a.evaluated] == \
            [c.key for c in b.evaluated]
        assert a.costs == b.costs and a.runtimes == b.runtimes
        assert a.best_valid_cost == b.best_valid_cost
        assert a.search_cost == b.search_cost


def test_seeded_scenarios_end_to_end(ds, machine_scores):
    """replay_scenarios(seeded=True) matches the host-table path and
    the sequential reference lane-for-lane."""
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0,), conditions=(HEALTHY,))
    ref = replay_scenarios(ds, scens, machine_scores)
    got = replay_scenarios(ds, scens, machine_scores, seeded=True)
    _assert_same_traces(ref, got)
    for sc, bt in zip(scens, got):
        _assert_trace_equal(reference_search(ds, sc, machine_scores),
                            bt, sc)


def test_seeded_pipelined_matches_unpipelined(ds, machine_scores,
                                             monkeypatch):
    """Across lowerings: the seeded lowering's blocks give the host
    tables' one-dispatch traces, picks and counts lane for lane."""
    from repro.optimizer import scenarios

    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    ref, host = replay_scenarios(ds, scens, machine_scores,
                                 return_result=True)
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    got, seeded = replay_scenarios(ds, scens, machine_scores,
                                   return_result=True, seeded=True)
    assert host.dispatches == 1 and seeded.dispatches == 2
    for sc, a, b in zip(scens, ref, got):
        _assert_trace_equal(a, b, sc)
    np.testing.assert_array_equal(host.chosen, seeded.chosen)
    np.testing.assert_array_equal(host.count, seeded.count)


def test_seeded_replay_compile_amortized(ds, machine_scores):
    """Replays of equally-shaped seeded specs reuse one program, and
    condition counts pad to pow2 so 1- and 2-condition matrices of the
    same lane shape can differ in program only via that padded axis."""
    cfg = ReplayConfig()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    spec = lane_spec(ds, scens, machine_scores, cfg)
    replay_seeded(spec, cfg)  # compile (or reuse)
    with expect_traces(REPLAY_TRACES, 0):
        r1 = replay_seeded(spec, cfg)
        r2 = replay_seeded(spec, cfg)
    np.testing.assert_array_equal(r1.chosen, r2.chosen)
    assert r1.dispatches == 1


# ------------------------------------------- sharded lane axis (slow)

@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_replay_bit_identical_subprocess():
    """8 virtual CPU devices: shard_map'd lanes must reproduce the
    single-device scanned replay bit-for-bit on the full 432-lane
    matrix, and replay_scenarios over the mesh must match lane for lane
    on both lowerings."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro.optimizer import (HEALTHY, FleetCondition,
                                     ReplayConfig, build_scenarios,
                                     lane_spec, lane_tables, replay,
                                     replay_scenarios, replay_seeded,
                                     traces_from_result)
        from repro.tuning.scout import ScoutDataset, VM_TYPES

        assert jax.device_count() == 8
        rng = np.random.default_rng(3)
        scores = {vm: {a: float(rng.uniform(0.5, 2.0))
                       for a in ("cpu", "memory", "disk", "network")}
                  for vm in VM_TYPES}
        ds = ScoutDataset(seed=0)
        cfg = ReplayConfig()
        cond = FleetCondition("deg", {"c4.large": {"cpu": 0.3},
                                      "m4.xlarge": {"memory": 0.4}})
        scens = build_scenarios(ds, seeds=(0, 1, 2),
                                conditions=(HEALTHY, cond))
        assert len(scens) == 432
        tab = lane_tables(ds, scens, scores, cfg)
        single = replay(tab, cfg)
        sharded = replay(tab, cfg, devices=jax.devices())
        assert np.array_equal(single.chosen, sharded.chosen)
        assert np.array_equal(single.count, sharded.count)

        # seeded spec: tables generated inside the sharded program,
        # noise re-drawn per shard from fold-in keys
        spec = lane_spec(ds, scens, scores, cfg)
        seeded = replay_seeded(spec, cfg, devices=jax.devices())
        assert np.array_equal(single.chosen, seeded.chosen)
        assert np.array_equal(single.count, seeded.count)

        ref = traces_from_result(tab, single, ds.configs)
        mesh = replay_scenarios(ds, scens, scores, cfg,
                                devices=jax.devices())
        mesh_seeded = replay_scenarios(ds, scens, scores, cfg,
                                       seeded=True,
                                       devices=jax.devices())
        for a, b, c in zip(ref, mesh, mesh_seeded):
            assert [x.key for x in a.evaluated] == \\
                [x.key for x in b.evaluated] == \\
                [x.key for x in c.evaluated]
            assert a.best_valid_cost == b.best_valid_cost \\
                == c.best_valid_cost
        print("OK bit-identical across", jax.device_count(), "devices")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK bit-identical" in proc.stdout


def test_traces_from_result_fields(ds, machine_scores):
    """Replayed SearchTrace bookkeeping is self-consistent: costs and
    runtimes come from the lane tables, the best-valid curve is the
    running min over valid runs, search_cost sums the costs."""
    cfg = ReplayConfig()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:1],
                            seeds=(0,), conditions=(HEALTHY,))
    tab = lane_tables(ds, scens, machine_scores, cfg)
    result = replay(tab, cfg)
    traces = traces_from_result(tab, result, ds.configs)
    for sc, tr in zip(scens, traces):
        assert len(tr.evaluated) == len(tr.costs) == len(tr.runtimes) \
            == len(tr.best_valid_cost)
        assert cfg.n_init <= len(tr.evaluated) <= cfg.max_runs
        assert tr.search_cost == float(np.sum(tr.costs))
        running = np.inf
        for cost, rt, best in zip(tr.costs, tr.runtimes,
                                  tr.best_valid_cost):
            if rt <= sc.limit:
                running = min(running, cost)
            assert best == running
        # no config evaluated twice
        keys = [c.key for c in tr.evaluated]
        assert len(keys) == len(set(keys))


# ------------------------------------------- Karasu lanes (RGPE support)

KARASU_VARIANTS = ("cherrypick", "cherrypick+perona", "karasu",
                   "karasu+perona")


@pytest.fixture(scope="module")
def karasu(ds, machine_scores):
    """3 workloads (2 support models a lane) x 2 seeds, 4 past searches
    a workload, 32 posterior samples; Karasu lanes beside the
    CherryPick lanes they extend."""
    from types import SimpleNamespace

    from repro.optimizer import support_history

    cfg = ReplayConfig(samples=32)
    workloads = list(ds.workloads)[:3]
    hist = support_history(ds, machine_scores, workloads=workloads,
                           searches=4, cfg=cfg)
    scens = build_scenarios(ds, workloads=workloads, seeds=(0, 1),
                            variants=KARASU_VARIANTS,
                            conditions=(HEALTHY,))
    tab = lane_tables(ds, scens, machine_scores, cfg, hist)
    result = replay(tab, cfg)
    return SimpleNamespace(cfg=cfg, hist=hist, scens=scens, tab=tab,
                           result=result, workloads=workloads,
                           traces=traces_from_result(tab, result,
                                                     ds.configs))


def _lane(scens, variant, workload, seed):
    return next(i for i, sc in enumerate(scens)
                if (sc.variant, sc.workload, sc.seed)
                == (variant, workload, seed))


def test_karasu_lanes_match_sequential_reference(ds, machine_scores,
                                                 karasu):
    """Every lane, Karasu or not, reproduces ``tuning.karasu`` /
    ``tuning.cherrypick`` exactly, and the support models steer the
    Karasu lanes off the CherryPick ones."""
    k = karasu
    assert k.tab.n_support == 2
    for sc, bt in zip(k.scens, k.traces):
        _assert_trace_equal(reference_search(ds, sc, machine_scores,
                                             k.cfg, support=k.hist),
                            bt, sc)
    moved = [k.traces[_lane(k.scens, "karasu", w, s)].evaluated
             != k.traces[_lane(k.scens, "cherrypick", w, s)].evaluated
             for w in k.workloads for s in (0, 1)]
    assert any(moved)


def test_karasu_lanes_match_bench_reference(ds, machine_scores, karasu):
    """The benchmark's own reference, which builds its own support
    history from its CherryPick copy, gives the same traces."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import scout_inputs
    from bench.reference import karasu as kref

    k = karasu
    data = scout_inputs.search_data(ds, k.workloads, machine_scores,
                                    (HEALTHY,))
    search_cfg = dict(max_runs=9, n_init=3, ei_threshold=0.1,
                      noise=1e-3, xi=0.01, strength=0.3, per_dollar=True,
                      samples=k.cfg.samples)
    hist = kref.history(data, 4, ds.seed, 40.0, search_cfg)
    checked = 0
    for sc, got, peaks in zip(k.scens, k.traces, k.result.peaks):
        if not sc.variant.startswith("karasu"):
            continue
        w = k.workloads.index(sc.workload)
        want = kref.search(data, hist, w, sc.seed, sc.variant, "healthy",
                           sc.limit, search_cfg)
        assert [c.key for c in got.evaluated] == \
            [data["keys"][i] for i in want.evaluated]
        assert got.costs == want.costs
        assert got.best_valid_cost == want.best_valid_cost
        assert got.search_cost == want.search_cost
        # each round's peak EI; NaN once the lane has stopped
        n = len(want.peaks)
        np.testing.assert_allclose(peaks[:n], want.peaks, rtol=1e-8,
                                   atol=1e-12)
        assert np.isnan(peaks[n:]).all()
        checked += 1
    assert checked == 12


def test_karasu_lane_with_no_support_is_cherrypick(ds, machine_scores,
                                                   karasu):
    """With every support slot empty a Karasu lane is its CherryPick
    lane bit for bit, on the program and in the sequential tuner."""
    import dataclasses

    from repro.tuning.cherrypick import CherryPick
    from repro.tuning.karasu import Karasu

    k = karasu
    empty = dataclasses.replace(
        k.tab, support_ids=np.full_like(k.tab.support_ids, -1))
    res = replay(empty, k.cfg)
    for w in k.workloads:
        for s in (0, 1):
            for plain, rgpe in (("cherrypick", "karasu"),
                                ("cherrypick+perona", "karasu+perona")):
                a = _lane(k.scens, plain, w, s)
                b = _lane(k.scens, rgpe, w, s)
                np.testing.assert_array_equal(res.chosen[a],
                                              res.chosen[b])
                assert res.count[a] == res.count[b]
                np.testing.assert_array_equal(res.chosen[a],
                                              k.result.chosen[a])
    sc = k.scens[_lane(k.scens, "cherrypick", k.workloads[0], 1)]
    seq = Karasu(ds, sc.limit, support=[None, None], samples=32,
                 seed=1).search(sc.workload)
    _assert_trace_equal(CherryPick(ds, sc.limit, seed=1)
                        .search(sc.workload), seq, sc)


def test_karasu_needs_host_tables_and_history(ds, machine_scores, karasu):
    scens = [sc for sc in karasu.scens if sc.variant == "karasu"]
    with pytest.raises(ValueError, match="host tables"):
        replay_scenarios(ds, scens, machine_scores, karasu.cfg,
                         seeded=True, support=karasu.hist)
    with pytest.raises(ValueError, match="support history"):
        replay_scenarios(ds, scens, machine_scores, karasu.cfg)


# ------------------------------------------------ blocked wide matrices

def _block_matrix(ds, karasu, kind, n_blocks):
    """A matrix of ``n_blocks`` blocks of 8 lanes, the last one short
    of a lane (so padded): CherryPick/Arrow lanes on the host or the
    seeded lowering, or Karasu lanes beside their CherryPick ones,
    interleaved or after them (so that the first blocks hold none)."""
    if kind.startswith("karasu"):
        scens = build_scenarios(ds, workloads=karasu.workloads[:2],
                                seeds=tuple(range(n_blocks)),
                                variants=KARASU_VARIANTS,
                                conditions=(HEALTHY,))
        if kind == "karasu_last":
            scens.sort(key=lambda sc: sc.variant.startswith("karasu"))
        kw = dict(cfg=karasu.cfg, support=karasu.hist)
    else:
        scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                                seeds=tuple(range(n_blocks)),
                                conditions=(HEALTHY,))
        kw = dict(seeded=kind == "seeded")
    assert len(scens) == 8 * n_blocks
    return scens[:-1], kw


def _replay_spans(run):
    from repro import obs

    tr = obs.tracer()
    tr.clear()
    out = run()
    return out, tr.events()


@pytest.mark.parametrize("n_blocks", [2, 4])
@pytest.mark.parametrize("kind",
                         ["host", "seeded", "karasu", "karasu_last"])
def test_blocked_replay_matches_one_dispatch(ds, machine_scores, karasu,
                                             monkeypatch, kind,
                                             n_blocks):
    """A matrix that reaches two blocks runs as blocks of
    ``REPLAY_BLOCK_LANES`` lanes, one dispatch each, and gives lane for
    lane the traces, picks, counts and Karasu peaks of the one
    dispatch, with no more padded lanes."""
    from repro.optimizer import scenarios

    scens, kw = _block_matrix(ds, karasu, kind, n_blocks)
    launch = "replay.dispatch_seeded" if kind == "seeded" \
        else "replay.dispatch"
    (ref, one), one_evs = _replay_spans(lambda: replay_scenarios(
        ds, scens, machine_scores, return_result=True, **kw))
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    (got, split), split_evs = _replay_spans(lambda: replay_scenarios(
        ds, scens, machine_scores, return_result=True, **kw))

    assert len(got) == len(ref) == len(scens)
    for sc, a, b in zip(scens, ref, got):
        _assert_trace_equal(a, b, sc)
    np.testing.assert_array_equal(split.chosen, one.chosen)
    np.testing.assert_array_equal(split.count, one.count)
    if kind.startswith("karasu"):
        np.testing.assert_array_equal(split.peaks, one.peaks)
    else:
        assert split.peaks is None and one.peaks is None
    assert one.dispatches == 1 and split.dispatches == n_blocks

    (whole,) = [e.args for e in one_evs if e.name == launch]
    assert (whole["block"], whole["blocks"]) == (0, 1)
    blocks = [e.args for e in split_evs if e.name == launch]
    assert [(a["block"], a["blocks"]) for a in blocks] == \
        [(i, n_blocks) for i in range(n_blocks)]
    assert all(a["padded"] == 8 for a in blocks)
    assert sum(a["padded"] for a in blocks) == whole["padded"]
    assert sum(a["lanes"] for a in blocks) == whole["lanes"]


def test_blocked_karasu_places_support_grid_once(ds, machine_scores,
                                                 karasu, monkeypatch):
    """Each block stages its own support ids and seeds; the support
    grid is placed by the first block and reused by the others, so the
    blocks' ``replay.support_tables`` bytes sum to the one dispatch's."""
    from repro.optimizer import scenarios

    scens, kw = _block_matrix(ds, karasu, "karasu", 4)
    _, one_evs = _replay_spans(lambda: replay_scenarios(
        ds, scens, machine_scores, **kw))
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    _, split_evs = _replay_spans(lambda: replay_scenarios(
        ds, scens, machine_scores, **kw))

    grid = karasu.hist.grid
    grid_bytes = grid.mean.nbytes + grid.var.nbytes + grid.cov.nbytes
    (whole,) = [e.args["bytes"] for e in one_evs
                if e.name == "replay.support_tables"]
    staged = [e.args["bytes"] for e in split_evs
              if e.name == "replay.support_tables"]
    assert len(staged) == 4
    assert sum(staged) == whole
    lane_bytes = staged[1]
    assert staged == [lane_bytes + grid_bytes] + [lane_bytes] * 3


def _weights_program(losses, eligible):
    import jax.numpy as jnp

    from repro.optimizer.acquire import rgpe_weights

    with jax.enable_x64():
        return np.asarray(jax.jit(rgpe_weights, static_argnums=2)(
            jnp.asarray(losses, jnp.int32), jnp.asarray(eligible), 9 * 8))


def _weights_tuning(losses, eligible):
    from repro.tuning.karasu import rgpe_weights

    return rgpe_weights(np.asarray(losses), np.asarray(eligible))


def _weights_bench(losses, eligible):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench.reference import karasu as kref

    return kref.weights(np.asarray(losses), np.asarray(eligible))


@pytest.mark.parametrize("weights", [_weights_program, _weights_tuning,
                                     _weights_bench])
def test_rgpe_weights(weights):
    """Weights sum to 1 and are shares of samples; a tie splits a
    sample equally; an empty slot gets nothing; the dilution guard
    drops a support model that disagrees on most samples (median loss
    above the target's 95th percentile) although it would win the
    rest. The program and both references give the same bits."""
    rng = np.random.default_rng(0)
    losses = rng.integers(0, 30, size=(5, 64))
    ok = np.array([True, True, True, False, True])
    w = weights(losses, ok)
    assert w.sum() == pytest.approx(1.0, abs=1e-12) and w[3] == 0.0
    assert np.all(w >= 0)
    np.testing.assert_array_equal(w, _weights_tuning(losses, ok))

    # sample 0: three-way tie (row 3's median 9 is above the target's
    # 95th percentile 3.85: dropped); sample 1: rows 0 and 2 tie
    tie = np.array([[4, 1], [4, 2], [4, 1], [9, 9]])
    np.testing.assert_allclose(weights(tie, np.ones(4, bool)),
                               [(1 / 3 + 1 / 2) / 2, 1 / 6,
                                (1 / 3 + 1 / 2) / 2, 0.0], rtol=1e-15)

    target = np.full(20, 10)  # its 95th percentile: 10
    rogue = np.where(np.arange(20) < 8, 0, 50)  # median 50, wins 8 of 20
    both = np.stack([target, rogue])
    np.testing.assert_array_equal(weights(both, np.ones(2, bool)),
                                  [1.0, 0.0])
    honest = np.where(np.arange(20) < 8, 0, 10)  # median 10: kept
    np.testing.assert_allclose(
        weights(np.stack([target, honest]), np.ones(2, bool)),
        [6 / 20, 14 / 20], rtol=1e-15)


def test_ranking_losses_count_misordered_pairs():
    import jax.numpy as jnp

    from repro.optimizer.acquire import ranking_losses
    from repro.tuning.karasu import ranking_losses as ref_losses

    y = np.array([3.0, 1.0, 2.0, 0.0])
    mask = np.array([True, True, True, False])
    f = np.stack([y[None, :3], -y[None, :3]])  # agrees, reversed
    f = np.concatenate([f, np.zeros((2, 1, 1))], axis=-1)
    with jax.enable_x64():
        got = np.asarray(ranking_losses(jnp.asarray(f), jnp.asarray(y),
                                        jnp.asarray(mask)))
    np.testing.assert_array_equal(got, [[0], [6]])
    np.testing.assert_array_equal(ref_losses(f[..., :3], y[:3]), got)


def test_karasu_sharded_bit_identical_subprocess():
    """8 virtual CPU devices: the Karasu program with its lane axis
    (support ids and seeds with it) sharded and the support grid
    replicated gives the single-device picks bit for bit."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro.optimizer import (HEALTHY, ReplayConfig,
                                     build_scenarios, lane_tables, replay,
                                     support_history)
        from repro.tuning.scout import ScoutDataset, VM_TYPES

        assert jax.device_count() == 8
        rng = np.random.default_rng(3)
        scores = {vm: {a: float(rng.uniform(0.5, 2.0))
                       for a in ("cpu", "memory", "disk", "network")}
                  for vm in VM_TYPES}
        ds = ScoutDataset(seed=0)
        cfg = ReplayConfig(samples=16)
        wls = list(ds.workloads)[:3]
        hist = support_history(ds, scores, workloads=wls, searches=2,
                               cfg=cfg)
        scens = build_scenarios(ds, workloads=wls, seeds=(0, 1, 2),
                                variants=("karasu", "karasu+perona"),
                                conditions=(HEALTHY,))
        tab = lane_tables(ds, scens, scores, cfg, hist)
        single = replay(tab, cfg)
        sharded = replay(tab, cfg, devices=jax.devices())
        assert np.array_equal(single.chosen, sharded.chosen)
        assert np.array_equal(single.count, sharded.count)
        assert np.array_equal(single.peaks, sharded.peaks, equal_nan=True)
        print("OK karasu bit-identical across", jax.device_count())
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK karasu bit-identical" in proc.stdout
