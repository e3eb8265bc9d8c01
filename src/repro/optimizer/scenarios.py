"""§IV-D scenario matrix over the scout simulator.

A *scenario* is one configuration search: (workload, seed, tuner
variant, fleet condition). The matrix spans the paper's evaluation grid
— 18 workloads x seeds x {cherrypick, arrow} x {vanilla,
perona-weighted} — extended with *fleet conditions*: degraded-node
fleets derived from ``fleet.drift`` analytics, so fingerprint-aware
search is exercised under exactly the degradation the paper motivates
(a degraded machine type's fingerprint scores drop, steering the
weighted acquisition away from it).

``lane_tables`` lowers a scenario list to the stacked arrays the replay
engine consumes; ``reference_search`` runs the identically-configured
sequential tuner (the parity baseline). Both paths must share one
``ScoutDataset`` instance: ``build_scenarios`` materializes the
simulator's runtime cache in canonical (workload, config) order while
computing runtime limits, which pins the contention-noise draws for
every later consumer.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.common.bucketing import next_pow2
from repro.common.mesh import shard_size
from repro.common.rng import STREAM_SUPPORT, folded_generator
from repro.core.ranking import machine_score_matrix, \
    machine_score_vector
from repro.obs import trace as obs_trace
from repro.optimizer.replay import (BatchReplayResult, LaneTables,
                                    ReplayConfig, SeededLaneSpec,
                                    SupportGrid, replay, replay_async,
                                    replay_seeded, replay_seeded_async,
                                    traces_from_result,
                                    traces_from_spec)
from repro.tuning.scout import LOW_CAPS, PRICES, ScoutDataset

#: Tuner variants, indexed by ``variant_id``: new ones are appended.
#: ``karasu`` is CherryPick with the RGPE ensemble of support models
#: (Karasu, arXiv 2308.11792), ``karasu+perona`` adds the weighting.
VARIANTS = ("cherrypick", "cherrypick+perona", "arrow", "arrow+perona",
            "karasu", "karasu+perona")
#: The variants that borrow nothing from other tenants' searches.
SOLO_VARIANTS = VARIANTS[:4]

#: Lanes per block of a wide matrix in ``replay_scenarios``: a matrix
#: whose padded lane count reaches two blocks is dispatched as blocks
#: of this many lanes, so the host lowers the next block and
#: materializes the last one while a block scans.
REPLAY_BLOCK_LANES = 512


@dataclasses.dataclass(frozen=True)
class FleetCondition:
    """A fleet health state: relative fingerprint-score drops per
    (machine type, resource aspect). The healthy fleet has none."""

    name: str
    score_drop: Mapping[str, Mapping[str, float]] = \
        dataclasses.field(default_factory=dict)


HEALTHY = FleetCondition("healthy")


class DeferredFleetCondition:
    """A fleet condition whose score drops are derived on first use —
    typically through the real store path (``simulate_degraded_fleet``
    -> ``fleet.drift`` EWMAs -> ``condition_from_drift``), which costs
    real host time. Under the block path of ``replay_scenarios`` the
    laziness pays: with a condition-major scenario order
    (``build_scenarios(condition_major=True)``) each block's conditions
    are derived on the host while the previous block scans on the
    device. Resolving is thread-safe."""

    def __init__(self, name: str, factory):
        self.name = name
        self._factory = factory
        self._resolved: Optional[FleetCondition] = None
        self._lock = threading.Lock()

    @property
    def resolved(self) -> bool:
        return self._resolved is not None

    def resolve(self) -> FleetCondition:
        # double-checked: concurrent resolvers of a shared condition
        # must not run the factory twice — beyond the wasted
        # store-path simulation, two FleetCondition objects would
        # split the replay engine's id()-keyed condition caches
        if self._resolved is None:
            with self._lock:
                if self._resolved is None:
                    cond = self._factory()
                    self._resolved = FleetCondition(self.name,
                                                    cond.score_drop)
        return self._resolved


def resolve_condition(condition) -> FleetCondition:
    """An eager :class:`FleetCondition` as-is; a deferred one derived
    (cached on the deferred object)."""
    if isinstance(condition, DeferredFleetCondition):
        return condition.resolve()
    return condition


def degrade_scores(machine_scores: Dict[str, Dict[str, float]],
                   condition: FleetCondition
                   ) -> Dict[str, Dict[str, float]]:
    """Apply a condition's relative drops to a machine-score dict."""
    condition = resolve_condition(condition)
    out = {m: dict(per) for m, per in machine_scores.items()}
    for vm, aspects in condition.score_drop.items():
        if vm not in out:
            continue
        for aspect, drop in aspects.items():
            if aspect in out[vm]:
                out[vm][aspect] *= (1.0 - drop)
    return out


def condition_from_drift(name: str, report: Dict[str, "NodeDrift"],
                         node_types: Mapping[str, str],
                         rel_drop: float = 0.2) -> FleetCondition:
    """Build a condition from ``fleet.drift.drift_report`` output:
    every drop ``fleet.drift.degradation_factors`` reports for a node
    votes for its machine type; drops average per type."""
    from repro.fleet.drift import degradation_factors

    acc: Dict[str, Dict[str, List[float]]] = {}
    for node, drops in degradation_factors(report, rel_drop).items():
        vm = node_types.get(node)
        if vm is None:
            continue
        for aspect, frac in drops.items():
            acc.setdefault(vm, {}).setdefault(aspect, []).append(frac)
    return FleetCondition(name, {
        vm: {a: float(np.mean(v)) for a, v in per.items()}
        for vm, per in acc.items()})


def simulate_degraded_fleet(machine_types: Sequence[str],
                            degraded: Mapping[str, Sequence[str]],
                            *, severity: float = 0.9, rounds: int = 10,
                            healthy_rounds: int = 3, seed: int = 0):
    """Run one simulated node per machine type through streaming
    benchmark rounds, attach synthetic quality scores that decay on the
    ``degraded`` types' aspects over the later rounds, and return the
    resulting ``fleet.drift`` report plus the node->type map.

    This exercises the real fleet path (store appends, chain views,
    EWMA analytics) without model training: attached codes are unit
    vectors scaled so ``core.ranking.code_scores`` equals the intended
    quality directly."""
    from repro.core.ranking import ASPECT_OF_TYPE
    from repro.fingerprint.runner import SuiteRunner
    from repro.fleet.drift import drift_report
    from repro.fleet.store import FingerprintStore

    day = 86400.0
    runner = SuiteRunner(seed=seed)
    machines = {f"{vm}-0": vm for vm in machine_types}
    store = FingerprintStore()
    for k in range(rounds):
        frame = runner.run_frame(machines, runs_per_type=1,
                                 t_offset=k * day)
        first = store.append(frame)
        n = len(frame)
        codes = np.zeros((n, 4), np.float32)
        anomaly = np.full(n, 0.05, np.float32)
        ramp = max(0.0, (k - healthy_rounds + 1)
                   / max(rounds - healthy_rounds, 1))
        for j in range(n):
            vm = frame.machine_types[frame.machine_type_code[j]]
            aspect = ASPECT_OF_TYPE[
                frame.benchmark_types[frame.type_code[j]]]
            quality = 1.0
            if aspect in degraded.get(vm, ()):
                quality = 1.0 - severity * ramp
                anomaly[j] = 0.05 + 0.9 * ramp
            codes[j, 0] = quality
        store.attach(np.arange(first, first + n), anomaly, codes)
    return drift_report(store), machines


def drifted_condition(machine_types: Sequence[str],
                      aspects: Sequence[str] = ("cpu",),
                      name: Optional[str] = None,
                      seed: int = 0, deferred: bool = False):
    """The canonical degraded-fleet condition used by the benchmark and
    the example: simulate the given machine types losing quality on the
    given aspects, run the fleet drift analytics, and turn the report
    into a condition.

    ``deferred=True`` returns a :class:`DeferredFleetCondition` that
    runs the store-path simulation on first use instead of now — the
    block path of ``replay_scenarios`` then overlaps that host work
    with device scans."""
    if name is None:
        name = f"{'/'.join(machine_types)}-{'/'.join(aspects)}-degraded"

    def derive() -> FleetCondition:
        report, node_types = simulate_degraded_fleet(
            machine_types, degraded={vm: tuple(aspects)
                                     for vm in machine_types}, seed=seed)
        return condition_from_drift(name, report, node_types)

    if deferred:
        return DeferredFleetCondition(name, derive)
    return derive()


@dataclasses.dataclass(frozen=True)
class Scenario:
    workload: str
    seed: int
    variant: str  # one of VARIANTS
    condition: FleetCondition  # or DeferredFleetCondition
    limit: float  # runtime constraint (seconds)


def build_scenarios(ds: ScoutDataset, *,
                    workloads: Optional[Sequence[str]] = None,
                    seeds: Sequence[int] = (0,),
                    variants: Sequence[str] = SOLO_VARIANTS,
                    conditions: Sequence[FleetCondition] = (HEALTHY,),
                    limit_percentile: float = 40.0,
                    condition_major: bool = False) -> List[Scenario]:
    """Cartesian scenario matrix. Computing the per-workload runtime
    limits materializes the simulator cache in canonical order (see
    module docstring).

    ``condition_major=True`` orders the matrix condition-outermost, so
    every contiguous lane block touches as few conditions as possible
    — with deferred (store-path-derived) conditions, the block path
    of ``replay_scenarios`` then derives each block's conditions while
    the previous block runs on device. Building the matrix never
    resolves deferred conditions."""
    workloads = list(ds.workloads) if workloads is None else workloads
    args: Dict[str, object] = {}
    with obs_trace.span("replay.build_scenarios", args=args):
        limits = {}
        for wl in workloads:
            rts, _, _ = ds.workload_arrays(wl)
            limits[wl] = float(np.percentile(rts, limit_percentile))
        if condition_major:
            matrix = [Scenario(wl, seed, variant, cond, limits[wl])
                      for cond in conditions for wl in workloads
                      for seed in seeds for variant in variants]
        else:
            matrix = [Scenario(wl, seed, variant, cond, limits[wl])
                      for wl in workloads for seed in seeds
                      for variant in variants for cond in conditions]
        args["lanes"] = len(matrix)
    return matrix


@dataclasses.dataclass
class SupportHistory:
    """Karasu's shared profiling history (arXiv 2308.11792): past
    CherryPick searches of every workload on the healthy fleet, which
    other tenants' searches borrow as RGPE support models. Row
    ``w * searches + h`` is the ``h``-th past search of
    ``workloads[w]``; ``grid`` holds each one's support model, a GP on
    its standardized observations, as a joint posterior over the
    candidate grid."""

    workloads: Tuple[str, ...]
    seeds: np.ndarray  # (H,) the past searches' seeds, per workload
    limits: np.ndarray  # (N,) runtime limit of each past search
    traces: List  # (N,) the past searches (tuning SearchTrace)
    grid: SupportGrid

    @property
    def searches(self) -> int:
        return len(self.seeds)

    @property
    def n_support(self) -> int:
        """Support slots per lane, M: one past search of each other
        workload."""
        return len(self.workloads) - 1

    def seed_ids(self, seed: int) -> np.ndarray:
        """(W, M) support rows of a search with ``seed``, one row per
        target workload: for each other workload, in order, the past
        search that the seed's stream picks."""
        n = len(self.workloads)
        pick = folded_generator(STREAM_SUPPORT, 1, seed).integers(
            0, self.searches, n)
        rows = np.arange(n) * self.searches + pick
        others = ~np.eye(n, dtype=bool)
        return np.tile(rows, (n, 1))[others].reshape(n, n - 1).astype(
            np.int32)

    def lane_ids(self, workload: str, seed: int) -> np.ndarray:
        """The M support rows of a search of ``workload`` with
        ``seed`` (see :meth:`seed_ids`)."""
        if workload not in self.workloads:
            raise ValueError(f"{workload!r} has no support history")
        return self.seed_ids(seed)[self.workloads.index(workload)]


def support_seeds(dataset_seed: int, searches: int) -> np.ndarray:
    """Seeds of the past searches in a support history: the same
    ``searches`` seeds for every workload."""
    return folded_generator(STREAM_SUPPORT, 0, dataset_seed).integers(
        0, 2**31 - 1, searches)


def support_history(ds: ScoutDataset,
                    machine_scores: Dict[str, Dict[str, float]], *,
                    workloads: Optional[Sequence[str]] = None,
                    searches: int = 8,
                    cfg: Optional[ReplayConfig] = None,
                    limit_percentile: float = 40.0) -> SupportHistory:
    """Build Karasu's support history: ``searches`` CherryPick
    searches per workload on the healthy fleet, replayed through
    :func:`replay_scenarios` itself, then one GP per search on its
    standardized, constraint-penalized observations (CherryPick's
    features, median-heuristic scales, ``cfg.noise``) and its joint
    posterior over every candidate."""
    import jax

    from repro.optimizer.gp import gp_fit, gp_joint_posterior

    cfg = ReplayConfig() if cfg is None else cfg
    workloads = list(ds.workloads) if workloads is None else workloads
    seeds = support_seeds(ds.seed, searches)
    scens = build_scenarios(ds, workloads=workloads,
                            seeds=[int(x) for x in seeds],
                            variants=("cherrypick",),
                            conditions=(HEALTHY,),
                            limit_percentile=limit_percentile)
    traces = replay_scenarios(ds, scens, machine_scores, cfg)

    col = {c.key: j for j, c in enumerate(ds.configs)}
    x_base = np.stack([ds.config_features(c) for c in ds.configs])
    x_cand = np.concatenate([x_base, np.zeros((len(x_base), 4))], 1)
    slots = next_pow2(cfg.max_runs)
    n = len(traces)
    idx = np.zeros((n, slots), np.int64)
    y = np.zeros((n, slots))
    mask = np.zeros((n, slots), bool)
    for i, (sc, tr) in enumerate(zip(scens, traces)):
        k = len(tr.evaluated)
        idx[i, :k] = [col[c.key] for c in tr.evaluated]
        y[i, :k] = [c if r <= sc.limit else c * 5.0
                    for c, r in zip(tr.costs, tr.runtimes)]
        mask[i, :k] = True

    def posterior(xo, yo, mo):
        state = gp_fit(xo, yo, mo, noise=cfg.noise,
                       median_rows=cfg.max_runs)
        return gp_joint_posterior(state, x_cand)

    with jax.enable_x64():
        mean, cov = jax.jit(jax.vmap(posterior))(x_cand[idx], y, mask)
        mean, cov = np.asarray(mean), np.asarray(cov)
    var = np.clip(np.diagonal(cov, axis1=1, axis2=2), 1e-9, None)
    return SupportHistory(
        workloads=tuple(workloads), seeds=seeds,
        limits=np.asarray([sc.limit for sc in scens]), traces=traces,
        grid=SupportGrid(mean=mean, var=np.ascontiguousarray(var),
                         cov=cov))


def _scenario_scores(scenario: Scenario, machine_scores):
    return degrade_scores(machine_scores, scenario.condition)


def reference_search(ds: ScoutDataset, scenario: Scenario,
                     machine_scores: Dict[str, Dict[str, float]],
                     cfg: Optional[ReplayConfig] = None,
                     support: Optional[SupportHistory] = None):
    """The sequential numpy tuner for one scenario — the parity and
    wall-clock baseline the batched lanes are pinned against. A Karasu
    scenario takes its support searches from ``support``; the tuner
    fits its own support models on their observations."""
    from repro.tuning.arrow import Arrow
    from repro.tuning.cherrypick import CherryPick
    from repro.tuning.karasu import Karasu
    from repro.tuning.perona_weights import PeronaAcquisitionWeighter

    cfg = ReplayConfig() if cfg is None else cfg
    scores = _scenario_scores(scenario, machine_scores)
    weighter = None
    if scenario.variant.endswith("+perona"):
        weighter = PeronaAcquisitionWeighter(
            ds, scores, strength=cfg.strength, per_dollar=cfg.per_dollar)
    kw = dict(max_runs=cfg.max_runs, n_init=cfg.n_init,
              ei_threshold=cfg.ei_threshold, seed=scenario.seed,
              acquisition_weighter=weighter)
    if scenario.variant.startswith("arrow"):
        low_fn = None
        if scenario.variant == "arrow+perona":
            low_fn = (lambda wl, c:
                      machine_score_vector(scores, c.vm_type))
        tuner = Arrow(ds, scenario.limit, low_level_fn=low_fn, **kw)
    elif scenario.variant.startswith("karasu"):
        if support is None:
            raise ValueError("a Karasu scenario needs a support history")
        past = [(support.traces[row], support.limits[row])
                for row in support.lane_ids(scenario.workload,
                                            scenario.seed)]
        tuner = Karasu(ds, scenario.limit, support=past,
                       samples=cfg.samples, **kw)
    else:
        tuner = CherryPick(ds, scenario.limit, **kw)
    return tuner.search(scenario.workload)


def lane_tables(ds: ScoutDataset, scenarios: Sequence[Scenario],
                machine_scores: Dict[str, Dict[str, float]],
                cfg: Optional[ReplayConfig] = None,
                support: Optional[SupportHistory] = None) -> LaneTables:
    """Lower scenarios to the replay engine's stacked lane tables.

    Karasu lanes take CherryPick's features and, from ``support``,
    their M support rows and the support grid; the other lanes of
    such a matrix get empty support slots. A matrix without Karasu
    lanes carries no support tables at all.

    Feature layout is unified across variants at D = 6 base + 4
    low-level dims; variants that do not use a block hold it constant,
    which leaves the reference GP's kernel unchanged exactly (constant
    dimensions median to zero pairwise distance and are floored out of
    the length scales). Arrow's candidate rows keep the low-level block
    at its search-start value (zeros): the sequential implementation
    computes candidate features once, before any run is observed."""
    from repro.tuning.perona_weights import normalized_machine_scores

    with obs_trace.span("replay.lane_tables",
                        args={"lanes": len(scenarios)}):
        cfg = ReplayConfig() if cfg is None else cfg
        configs = ds.configs
        n_cand = len(configs)
        x_base = np.stack([ds.config_features(c) for c in configs])
        prices = np.asarray([PRICES[c.vm_type] for c in configs])

        workload_cache: Dict[str, Tuple] = {}

        def workload_tables(wl: str):
            if wl not in workload_cache:
                workload_cache[wl] = ds.workload_arrays(wl)
            return workload_cache[wl]

        # keyed by object identity: distinct conditions may share a name
        cond_cache: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

        def condition_tables(cond: FleetCondition):
            if id(cond) not in cond_cache:
                scores = degrade_scores(machine_scores, cond)
                norm = normalized_machine_scores(scores)
                ns = np.stack([norm.get(c.vm_type, np.ones(4))
                               for c in configs])
                fp_low = machine_score_matrix(
                    scores, [c.vm_type for c in configs])
                cond_cache[id(cond)] = (ns, fp_low)
            return cond_cache[id(cond)]

        dim = x_base.shape[1] + 4
        n_lanes = len(scenarios)
        tab = LaneTables(
            x_train=np.zeros((n_lanes, n_cand, dim)),
            x_cand=np.zeros((n_lanes, n_cand, dim)),
            y=np.zeros((n_lanes, n_cand)),
            runtime=np.zeros((n_lanes, n_cand)),
            cost=np.zeros((n_lanes, n_cand)),
            limit=np.zeros(n_lanes),
            price=np.tile(prices, (n_lanes, 1)),
            norm_scores=np.zeros((n_lanes, n_cand, 4)),
            util_low=np.zeros((n_lanes, n_cand, 4)),
            use_weighter=np.zeros(n_lanes, bool),
            init_idx=np.zeros((n_lanes, cfg.n_init), np.int32))

        base_dim = x_base.shape[1]
        tab.x_train[:, :, :base_dim] = x_base
        tab.x_cand[:, :, :base_dim] = x_base
        # lanes sharing (workload, condition, variant, limit) get identical
        # rows: assign per group (one fancy-index write each) instead of
        # per lane — the python work is O(groups + lanes), which keeps
        # table construction cheap enough to overlap with device scans
        groups: Dict[Tuple, List[int]] = {}
        for lane, sc in enumerate(scenarios):
            groups.setdefault(
                (sc.workload, id(sc.condition), sc.variant, sc.limit),
                []).append(lane)
        for (wl, _, variant, limit), lanes in groups.items():
            sc = scenarios[lanes[0]]
            rows = np.asarray(lanes)
            runtimes, costs, lows = workload_tables(wl)
            ns, fp_low = condition_tables(sc.condition)
            if variant == "arrow":
                # evaluated runs carry their observed low-level metrics;
                # candidates keep the search-start zeros block
                tab.x_train[rows, :, base_dim:] = lows
            elif variant == "arrow+perona":
                # fingerprint scores exist before any run: both sides
                tab.x_train[rows, :, base_dim:] = fp_low
                tab.x_cand[rows, :, base_dim:] = fp_low
            tab.runtime[rows] = runtimes
            tab.cost[rows] = costs
            tab.y[rows] = np.where(runtimes <= limit, costs, costs * 5.0)
            tab.limit[rows] = limit
            tab.norm_scores[rows] = ns
            tab.util_low[rows] = lows
            tab.use_weighter[rows] = variant.endswith("+perona")
        init_cache: Dict[int, np.ndarray] = {}
        for lane, sc in enumerate(scenarios):
            if sc.seed not in init_cache:
                init_cache[sc.seed] = np.random.default_rng(sc.seed).choice(
                    n_cand, cfg.n_init, replace=False).astype(np.int32)
            tab.init_idx[lane] = init_cache[sc.seed]
        if any(sc.variant.startswith("karasu") for sc in scenarios):
            _lower_support(tab, scenarios, support)
        return tab


def _lower_support(tab: LaneTables, scenarios: Sequence[Scenario],
                   support: Optional[SupportHistory]) -> None:
    """Each Karasu lane's support rows and search seed (the rows drawn
    once per distinct seed)."""
    if support is None:
        raise ValueError("Karasu lanes need a support history "
                         "(scenarios.support_history)")
    ids = np.full((len(scenarios), support.n_support), -1, np.int32)
    row = {name: w for w, name in enumerate(support.workloads)}
    by_seed: Dict[int, np.ndarray] = {}
    for lane, sc in enumerate(scenarios):
        if sc.variant.startswith("karasu"):
            if sc.workload not in row:
                raise ValueError(f"{sc.workload!r} has no support history")
            if sc.seed not in by_seed:
                by_seed[sc.seed] = support.seed_ids(sc.seed)
            ids[lane] = by_seed[sc.seed][row[sc.workload]]
    tab.support_ids = ids
    tab.search_seed = np.asarray([sc.seed for sc in scenarios], np.uint32)
    tab.support_grid = support.grid


def lane_spec(ds: ScoutDataset, scenarios: Sequence[Scenario],
              machine_scores: Dict[str, Dict[str, float]],
              cfg: Optional[ReplayConfig] = None) -> SeededLaneSpec:
    """Lower scenarios to the *seeded* replay inputs: the shared
    deterministic grid (``ds.grid``), one score matrix per distinct
    fleet condition, and per-lane ids. O(W*C + K*C + L) host work and
    memory — the O(L*C*D) lane tables are generated inside the
    compiled program instead (``replay.replay_seeded_async``), with
    the contention noise re-drawn on device from ``ds.grid.noise_key``
    counter-based keys."""
    from repro.tuning.perona_weights import normalized_machine_scores
    from repro.tuning.scout import CONTENTION_SCALE

    with obs_trace.span("replay.lane_spec",
                        args={"lanes": len(scenarios)}):
        cfg = ReplayConfig() if cfg is None else cfg
        configs = ds.configs
        n_cand = len(configs)
        grid = ds.grid
        n_lanes = len(scenarios)

        # one score-matrix pair per distinct condition object (identity
        # keyed: distinct conditions may share a name); resolving a
        # deferred condition happens here, on the host
        cond_rows: Dict[int, int] = {}
        ns_rows: List[np.ndarray] = []
        fp_rows: List[np.ndarray] = []
        condition_id = np.empty(n_lanes, np.int32)
        workload_id = np.empty(n_lanes, np.int32)
        variant_id = np.empty(n_lanes, np.int32)
        limit = np.empty(n_lanes, np.float64)
        init_idx = np.zeros((n_lanes, cfg.n_init), np.int32)
        init_cache: Dict[int, np.ndarray] = {}
        for lane, sc in enumerate(scenarios):
            if sc.variant.startswith("karasu"):
                raise ValueError(
                    "Karasu lanes run on host tables only (seeded=False): "
                    "the seeded program has no support models")
            row = cond_rows.get(id(sc.condition))
            if row is None:
                scores = degrade_scores(machine_scores, sc.condition)
                norm = normalized_machine_scores(scores)
                ns_rows.append(np.stack([norm.get(c.vm_type, np.ones(4))
                                         for c in configs]))
                fp_rows.append(machine_score_matrix(
                    scores, [c.vm_type for c in configs]))
                row = cond_rows[id(sc.condition)] = len(ns_rows) - 1
            condition_id[lane] = row
            workload_id[lane] = ds.workload_id(sc.workload)
            variant_id[lane] = VARIANTS.index(sc.variant)
            limit[lane] = sc.limit
            if sc.seed not in init_cache:
                init_cache[sc.seed] = np.random.default_rng(sc.seed).choice(
                    n_cand, cfg.n_init, replace=False).astype(np.int32)
            init_idx[lane] = init_cache[sc.seed]

        return SeededLaneSpec(
            base_runtime=grid.base_runtime, low_num=grid.low_num,
            low_caps=np.asarray(LOW_CAPS, np.float64),
            x_base=grid.x_base, price=grid.price,
            count=grid.count.astype(np.float64, copy=False),
            config_uid=grid.config_uid,
            # reshaped, not stacked: an empty matrix has no rows
            norm_scores=np.asarray(ns_rows).reshape(-1, n_cand, 4),
            fp_low=np.asarray(fp_rows).reshape(-1, n_cand, 4),
            noise_key=grid.noise_key, noise_scale=CONTENTION_SCALE,
            workload_id=workload_id, condition_id=condition_id,
            variant_id=variant_id, limit=limit, init_idx=init_idx,
            runtime=grid.runtime, cost=grid.cost)


def replay_scenarios(ds: ScoutDataset, scenarios: Sequence[Scenario],
                     machine_scores: Dict[str, Dict[str, float]],
                     cfg: Optional[ReplayConfig] = None,
                     return_result: bool = False, *,
                     devices: Optional[Sequence] = None,
                     seeded: bool = False,
                     support: Optional[SupportHistory] = None):
    """End to end: lower the matrix, run the batched replay (sharded
    over ``devices`` when given), return the per-scenario
    :class:`SearchTrace` list (order matches input).

    A matrix whose padded lane count (``shard_size``) reaches twice
    :data:`REPLAY_BLOCK_LANES` runs as contiguous blocks of that many
    lanes (:func:`_replay_blocks`), two in flight, with the same
    traces; one with ``devices=`` keeps its single sharded dispatch.

    ``seeded=True`` lowers to the compact :class:`SeededLaneSpec` and
    generates the lane tables inside the compiled program instead of
    materializing them on host — bit-identical traces. Karasu
    scenarios need ``support`` (:func:`support_history`) and the host
    tables."""
    cfg = ReplayConfig() if cfg is None else cfg
    if seeded:
        def lower(chunk):
            return lane_spec(ds, chunk, machine_scores, cfg)

        def launch(spec, _, **kw):
            return replay_seeded_async(spec, cfg, **kw)
        run, materialize = replay_seeded, traces_from_spec
    else:
        karasu = any(sc.variant.startswith("karasu") for sc in scenarios)

        def lower(chunk):
            tab = lane_tables(ds, chunk, machine_scores, cfg, support)
            if karasu and tab.support_grid is None:
                # a block without Karasu lanes in a matrix with them:
                # empty support slots, the matrix's program
                _lower_support(tab, chunk, support)
            return tab

        def launch(tab, prev, **kw):
            # the support grid is placed by the first block only
            return replay_async(
                tab, cfg, support_grid=prev and prev.support_grid, **kw)
        run, materialize = replay, traces_from_result
    if (devices is None
            and shard_size(len(scenarios)) >= 2 * REPLAY_BLOCK_LANES):
        traces, result = _replay_blocks(ds, scenarios, lower, launch,
                                        materialize)
    else:
        lowered = lower(scenarios)
        result = run(lowered, cfg, devices=devices)
        traces = materialize(lowered, result, ds.configs)
    if return_result:
        return traces, result
    return traces


def _replay_blocks(ds: ScoutDataset, scenarios: Sequence[Scenario],
                   lower, launch, materialize):
    """Replay a matrix as contiguous blocks of
    :data:`REPLAY_BLOCK_LANES` lanes, one compiled program for all of
    them (only the last block is padded), on one thread with two blocks
    in flight: block i is lowered and dispatched before block i-1 is
    fetched and materialized, so that host work overlaps block i's scan
    on a device whose dispatch returns at once.

    ``lower(chunk)`` gives a block's tables, ``launch(tables,
    pending, **kw)`` launches it (``pending``: the block in flight, or
    ``None``) and ``materialize(tables, result, configs)`` gives its
    traces. Returns the traces in lane order and one
    :class:`BatchReplayResult` of the blocks' outputs, concatenated."""
    size = REPLAY_BLOCK_LANES
    blocks = -(-len(scenarios) // size)
    traces: List = []
    results: List[BatchReplayResult] = []

    def fetch(tab, pending):
        result = pending.result()
        results.append(result)
        traces.extend(materialize(tab, result, ds.configs))

    in_flight = None  # (tables, PendingReplay) of the block scanning
    for i in range(blocks):
        tab = lower(scenarios[i * size:(i + 1) * size])
        pending = launch(tab, in_flight and in_flight[1],
                         lanes_floor=size, block=i, blocks=blocks)
        if in_flight is not None:
            fetch(*in_flight)
        in_flight = tab, pending
    fetch(*in_flight)
    peaks = [r.peaks for r in results]
    return traces, BatchReplayResult(
        chosen=np.concatenate([r.chosen for r in results]),
        count=np.concatenate([r.count for r in results]),
        dispatches=sum(r.dispatches for r in results),
        peaks=None if peaks[0] is None else np.concatenate(peaks))
