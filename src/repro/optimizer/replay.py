"""Vmapped BO search lanes as one ``lax.scan`` over rounds — optionally
sharded over a 1-D device mesh.

Replays many CherryPick/Arrow-style configuration searches (paper
§IV-D) in parallel: every *lane* is one (workload, seed, tuner variant,
fleet condition) scenario over the same candidate grid; one scan step
advances every still-active lane by one BO round (masked GP fit on the
lane's evaluated set, EI + optional Perona weighting, stopping rules,
argmax selection). The whole search is a single device dispatch —
carries are donated, lanes and observation slots are pow2-padded
(``common.mesh.shard_size``) so repeated replays of similar matrices
reuse one compiled program (``REPLAY_TRACES`` counts tracings; tests
assert amortization).

Pass ``devices=`` to partition the lane axis across a device mesh
(``common.mesh`` plumbing, the ``fleet.shard`` pattern):
``shard_map(vmap(step))`` gives every device its own lane bucket, the
scan runs once per device over local lanes, and carries stay donated.
Lanes never interact, so sharded replay is *bit-identical* to the
single-device scan — and therefore to the sequential scipy traces
(asserted under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in tests/test_optimizer.py).

``replay_async`` (and ``replay_seeded_async``) dispatches and defers
the host fetch (:class:`PendingReplay`). On a backend whose dispatch
returns before the compute finishes, as a TPU's does, that is an
overlap window: ``scenarios.replay_scenarios`` dispatches a wide
matrix as lane blocks through it, lowering the next block and
materializing the last one while a block scans, on one thread. XLA on
the CPU executes synchronously, so there the blocks run one after the
other with the same traces.

All math runs in float64 (``jax.enable_x64`` around the
dispatch) so batched lanes reproduce the sequential scipy traces
bit-for-bit on identical seeds: same evaluated configs, same
best-valid-cost curves (see tests/test_optimizer.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.common.mesh import (axis_specs, build_mesh, pad_lanes,
                               pow2_devices, shard_map_1d, shard_size)
from repro.common.rng import x64_streams
from repro.obs import trace as obs_trace
from repro.obs.jaxstat import JitSite

#: Ticked once per tracing of the scanned replay program — a
#: registry-backed :class:`repro.obs.jaxstat.JitSite` whose
#: ``dispatch()`` wrapper additionally books per-dispatch wall time
#: into compile-vs-run registry counters and records the launch as a
#: device span (the device time is the ``replay.wait`` span of
#: :meth:`PendingReplay.result`).
REPLAY_TRACES = JitSite("optimizer.replay")


@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    """Search hyperparameters, matching the sequential defaults
    (``CherryPick.__init__`` / ``GP`` / ``PeronaAcquisitionWeighter``)."""

    max_runs: int = 9
    n_init: int = 3
    ei_threshold: float = 0.1
    noise: float = 1e-3
    xi: float = 0.01
    strength: float = 0.3
    per_dollar: bool = True
    #: RGPE posterior samples per model and round (Karasu lanes only)
    samples: int = 256


#: Diagonal jitter of a support model's posterior covariance at the
#: target's observations before its Cholesky factor (standardized
#: units; numerical, far below the GP's 1e-3 noise).
RGPE_JITTER = 1e-6


@dataclasses.dataclass
class SupportGrid:
    """Posteriors of the support models of Karasu lanes over the
    shared candidate grid, one row per past search, in each search's
    standardized units (``optimizer.scenarios.support_history``)."""

    mean: np.ndarray  # (N, C) posterior mean
    var: np.ndarray  # (N, C) posterior variance, floored like gp_predict
    cov: np.ndarray  # (N, C, C) joint posterior covariance


@dataclasses.dataclass
class LaneTables:
    """Per-lane constant tables (numpy, lane-stacked; L lanes over a
    shared candidate grid of C configurations, feature dim D)."""

    x_train: np.ndarray  # (L, C, D) GP features of *evaluated* configs
    x_cand: np.ndarray  # (L, C, D) GP features of candidates (Arrow's
    #                      imputation quirk makes these differ, see
    #                      scenarios.lane_tables)
    y: np.ndarray  # (L, C) constraint-penalized objective
    runtime: np.ndarray  # (L, C) runtimes (constraint checks)
    cost: np.ndarray  # (L, C) raw execution cost (trace reporting)
    limit: np.ndarray  # (L,) runtime constraint
    price: np.ndarray  # (L, C) $/h of the candidate's machine type
    norm_scores: np.ndarray  # (L, C, 4) normalized fingerprint scores
    util_low: np.ndarray  # (L, C, 4) per-run utilization metrics
    use_weighter: np.ndarray  # (L,) Perona-weighted lane flag
    init_idx: np.ndarray  # (L, n_init) seeded init draws
    # Karasu lanes (RGPE over support models); absent for a matrix
    # without them, which then runs today's program unchanged
    support_ids: Optional[np.ndarray] = None  # (L, M) rows of
    #                      support_grid, -1 an empty support slot
    search_seed: Optional[np.ndarray] = None  # (L,) RGPE sample stream
    support_grid: Optional[SupportGrid] = None

    def __len__(self) -> int:
        return len(self.y)

    @property
    def n_support(self) -> int:
        """Support slots per lane, M (0: no Karasu lane)."""
        return 0 if self.support_ids is None else self.support_ids.shape[1]


@dataclasses.dataclass
class BatchReplayResult:
    chosen: np.ndarray  # (L, max_runs) evaluated config indices, -1 pad
    count: np.ndarray  # (L,) evaluations performed per lane
    dispatches: int  # device dispatches of this replay (its blocks)
    #: (L, rounds) each round's peak EI (see _lane_step); Karasu
    #: programs only
    peaks: Optional[np.ndarray] = None


@dataclasses.dataclass
class SeededLaneSpec:
    """Seeded replay program inputs: O(W*C + K*C + L) instead of the
    O(L*C*D) materialized :class:`LaneTables`.

    The shared grid tables (deterministic, workload/config/condition
    indexed) are replicated across devices; the per-lane arrays are
    just ids + the runtime limit + seeded init draws. The compiled
    program re-derives every stochastic table cell in-program from
    ``noise_key`` (counter-based ``fold_in(key, workload_id,
    config_uid)`` draws, see ``common.rng``), bit-identical to the
    host grid — lane tables are never materialized on host.

    ``runtime``/``cost`` are the host copies of the (W, C) grids used
    only to materialize traces after the fetch; they are not shipped
    to the device."""

    # shared grid tables (replicated)
    base_runtime: np.ndarray  # (W, C) noise-free runtime component
    low_num: np.ndarray  # (W, C, 4) utilization-metric numerators
    low_caps: np.ndarray  # (4,) utilization metric caps
    x_base: np.ndarray  # (C, B) base feature block
    price: np.ndarray  # (C,) USD/h per candidate
    count: np.ndarray  # (C,) node counts
    config_uid: np.ndarray  # (C,) fold-in uids (noise counters)
    norm_scores: np.ndarray  # (K, C, 4) per-condition weighter scores
    fp_low: np.ndarray  # (K, C, 4) per-condition fingerprint features
    noise_key: np.ndarray  # (2,) uint32 contention stream key
    noise_scale: float  # lognormal noise scale
    # per-lane (partitioned over devices)
    workload_id: np.ndarray  # (L,) int32
    condition_id: np.ndarray  # (L,) int32 row into norm_scores/fp_low
    variant_id: np.ndarray  # (L,) int32 index into scenarios.VARIANTS
    limit: np.ndarray  # (L,) runtime constraint
    init_idx: np.ndarray  # (L, n_init) seeded init draws
    # host-only trace tables
    runtime: np.ndarray  # (W, C)
    cost: np.ndarray  # (W, C)

    def __len__(self) -> int:
        return len(self.workload_id)


def _lane_step(sel, count, active, xt, xc, y_tab, r_tab, ulow, ns,
               price, limit, use_w, *, cfg: ReplayConfig, slots: int,
               support=None):
    """One BO round of one lane (vmapped over lanes by the caller).

    ``support`` (Karasu lanes): ``(ids, seed, grid, key)``, the lane's
    M support rows (-1 empty), its search seed, the replicated
    :class:`SupportGrid` arrays and the RGPE stream key; see
    :func:`_rgpe_predict`. Such a step also returns the round's peak:
    the largest float64 EI over the unseen candidates before the
    float32 selection grid (NaN once the lane has stopped), the number
    the Karasu cell's check compares with its reference."""
    import jax.numpy as jnp

    from repro.optimizer.acquire import (expected_improvement,
                                         perona_weight_factors)
    from repro.optimizer.gp import gp_fit, gp_predict

    n_cand = y_tab.shape[0]
    idx = jnp.maximum(sel, 0)
    omask = jnp.arange(cfg.max_runs) < count
    # pad the observation axis to the pow2 slot count
    idx_p = jnp.zeros(slots, sel.dtype).at[: cfg.max_runs].set(idx)
    mask_p = jnp.arange(slots) < count

    x_obs = xt[idx_p]
    y_obs = y_tab[idx_p]
    state = gp_fit(x_obs, y_obs, mask_p, noise=cfg.noise,
                   median_rows=cfg.max_runs)
    mu, sigma = gp_predict(state, xc)
    if support is not None:
        mu, sigma = _rgpe_predict(state, y_obs, idx, count, mu, sigma,
                                  *support, cfg=cfg)
    best = jnp.min(jnp.where(mask_p, y_obs, jnp.inf))
    ei = expected_improvement(mu, sigma, best, xi=cfg.xi)

    util = jnp.sum(jnp.where(mask_p[:, None], ulow[idx_p], 0.0),
                   axis=0) / count
    any_valid = jnp.any(mask_p & (r_tab[idx_p] <= limit))
    factor = perona_weight_factors(util, ns, price, any_valid,
                                   strength=cfg.strength,
                                   per_dollar=cfg.per_dollar)
    ei = jnp.where(use_w, ei * factor, ei)

    seen = jnp.zeros(n_cand, jnp.int32).at[idx].add(
        omask.astype(jnp.int32)) > 0
    ei = jnp.where(seen, -jnp.inf, ei)
    peak = jnp.max(ei)
    # float32-rounded selection grid, shared with the sequential
    # reference (see CherryPick.search): deterministic tie-breaks on
    # ulp-close candidates regardless of backend rounding
    ei = ei.astype(jnp.float32).astype(jnp.float64)

    mx = jnp.max(ei)
    stop_flat = mx <= 0.0
    stop_converged = ((mx / jnp.maximum(best, 1e-9) < cfg.ei_threshold)
                      & (count >= cfg.n_init + 2))
    advance = active & ~stop_flat & ~stop_converged
    pick = jnp.argmax(ei).astype(sel.dtype)
    sel = sel.at[count].set(jnp.where(advance, pick, sel[count]))
    count = count + advance.astype(count.dtype)
    if support is not None:
        return sel, count, advance, jnp.where(active, peak, jnp.nan)
    return sel, count, advance


def _rgpe_predict(state, y_obs, idx, count, mu, sigma, ids, seed, grid,
                  key, *, cfg: ReplayConfig):
    """The RGPE ensemble's predictive at the candidates for one lane
    and round (arXiv 1802.02219, sections 3-4; ``tuning.karasu`` is
    the sequential reference).

    Over the R = ``max_runs`` observation slots (valid ones first):
    S joint samples of each support model at the target's observed
    configurations, the target's own from its leave-one-out
    posteriors, all drawn from ``fold_in(fold_in(key, seed), count)``;
    their ranking losses give the weights
    (:func:`acquire.rgpe_weights`), which mix the target's and the
    support models' posteriors (:func:`acquire.ensemble_predict`).
    With every support slot empty the target holds all the weight and
    the lane is a CherryPick lane, bit for bit."""
    import jax
    import jax.numpy as jnp

    from repro.common.rng import normal_block
    from repro.optimizer.acquire import (ensemble_predict, ranking_losses,
                                         rgpe_weights)
    from repro.optimizer.gp import joint_samples, loo_posterior

    mean_g, var_g, cov_g = grid
    r = cfg.max_runs
    m = ids.shape[0]
    n_cand = mean_g.shape[1]
    mask = jnp.arange(r) < count
    z = normal_block(key, seed, count, (m + 1, cfg.samples, r))
    mu_loo, sd_loo = loo_posterior(state, y_obs)
    f_t = mu_loo[:r] + sd_loo[:r] * z[0]
    row = jnp.maximum(ids, 0)
    # gather only the (M, R, R) block each round, never a (M, C, C) one
    flat = ((row[:, None, None] * n_cand + idx[None, :, None]) * n_cand
            + idx[None, None, :])
    cov_o = cov_g.reshape(-1)[flat]
    mean_o = mean_g[row[:, None], idx[None, :]]
    f_s = jax.vmap(joint_samples, in_axes=(0, 0, None, 0, None))(
        mean_o, cov_o, mask, z[1:], RGPE_JITTER)
    losses = ranking_losses(jnp.concatenate([f_t[None], f_s]), y_obs[:r],
                            mask)
    eligible = jnp.concatenate([jnp.ones(1, bool), ids >= 0])
    w = rgpe_weights(losses, eligible, r * (r - 1))
    return ensemble_predict(w, mu, sigma, state.y_mean, state.y_std,
                            mean_g[row], var_g[row])


#: Number of stacked lane-table arrays a replay dispatch consumes.
N_TABLES = 9

#: Replicated grid tables of a seeded dispatch (incl. the noise key).
N_GRID_TABLES = 10

#: Per-lane arrays of a seeded dispatch (ids + limit).
N_LANE_ARGS = 4


@functools.lru_cache(maxsize=32)
def _replay_fn(cfg: ReplayConfig, lanes: int, slots: int, n_cand: int,
               dim: int, rounds: int,
               devices: Optional[Tuple] = None, support: int = 0):
    """Jitted scan program for one (config, shape, mesh) signature.

    ``devices=None`` is the single-device program. A device tuple
    shards the lane axis: each device scans its own
    ``lanes/len(devices)`` lane bucket (``shard_map`` around the
    vmapped step), one dispatch total.

    ``support`` is the static count M of support slots per lane. With
    M > 0 the program takes two more argument groups, the lanes'
    ``(support_ids, search_seed)`` and the replicated
    :class:`SupportGrid` arrays, and every lane runs the RGPE ensemble
    (a lane whose slots are all empty reduces to CherryPick); M = 0
    traces today's program exactly.
    """
    import jax

    if support:
        from repro.common.rng import STREAM_RGPE, stream_key

        key = stream_key(0, STREAM_RGPE)

    def run(carry, tables, lane_support=(), grid=()):
        REPLAY_TRACES.tick()
        if support:
            def lane_step(sel, count, active, *a):
                *tabs, ids, seed = a
                return _lane_step(sel, count, active, *tabs, cfg=cfg,
                                  slots=slots,
                                  support=(ids, seed, grid, key))
        else:
            lane_step = functools.partial(_lane_step, cfg=cfg,
                                          slots=slots)
        step_v = jax.vmap(lane_step)

        def scan_step(c, _):
            sel, count, active, *peak = step_v(*c, *tables, *lane_support)
            return (sel, count, active), (peak[0] if peak else None)

        (sel, count, _), peaks = jax.lax.scan(scan_step, carry, None,
                                              length=rounds)
        if support:
            return sel, count, peaks.T
        return sel, count

    if devices is not None and len(devices) > 1:
        mesh = build_mesh("lanes", devices)
        lane = axis_specs("lanes", 1)[0]
        in_specs = ((lane,) * 3, (lane,) * N_TABLES)
        out_specs = (lane, lane)
        if support:
            in_specs += ((lane,) * 2, axis_specs("lanes", 0, 3))
            out_specs += (lane,)
        run = shard_map_1d(run, mesh, in_specs=in_specs,
                           out_specs=out_specs)
    return jax.jit(run, donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _seeded_replay_fn(cfg: ReplayConfig, lanes: int, slots: int,
                      n_cand: int, base_dim: int, rounds: int,
                      n_workloads: int, n_conds: int,
                      noise_scale: float,
                      devices: Optional[Tuple] = None):
    """Jitted scan program that *generates* its lane tables in-program.

    Same scanned search as :func:`_replay_fn`, but the per-lane tables
    are expanded on device from the replicated grid + the lane's
    ``(workload_id, condition_id, variant_id, limit)`` ids: the
    contention noise is re-drawn from counter-based
    ``fold_in(noise_key, workload_id, config_uid)`` keys
    (``common.rng.lognormal_noise_row``), bit-identical to the host
    grid, and every derived table (objective, penalized cost,
    utilization metrics, feature blocks) follows the exact op order of
    ``tuning.scout._build_grid`` / ``scenarios.lane_tables`` so the
    f32-rounded argmax selections match the host-table program
    bit-for-bit. Nothing of size O(lanes x candidates) ever exists on
    host."""
    import jax
    import jax.numpy as jnp

    from repro.common.rng import lognormal_noise_row

    step = functools.partial(_lane_step, cfg=cfg, slots=slots)
    step_v = jax.vmap(step)

    def expand(noise_key, grid, wid, cid, vid, limit):
        (base, low_num, low_caps, x_base, price, count, uid,
         ns, fp) = grid
        # same op order as the host grid: one multiply for runtime,
        # left-to-right cost chain, capped utilization ratios
        noise = lognormal_noise_row(noise_key, wid, uid, noise_scale)
        rt = base[wid] * noise
        cost = rt / 3600.0 * price * count
        y = jnp.where(rt <= limit, cost, cost * 5.0)
        rtm = jnp.maximum(rt, 1e-6)
        denom = jnp.stack([rtm, jnp.ones_like(rtm), rtm, rtm], axis=-1)
        lows = jnp.minimum(low_caps, low_num[wid] / denom)
        zeros = jnp.zeros_like(lows)
        # variant feature blocks (scenarios.VARIANTS order): arrow
        # trains on observed lows (candidates imputed to zero),
        # arrow+perona uses the fingerprint lows on both sides
        low_train = jnp.where(vid == 2, lows,
                              jnp.where(vid == 3, fp[cid], zeros))
        low_cand = jnp.where(vid == 3, fp[cid], zeros)
        xt = jnp.concatenate([x_base, low_train], axis=1)
        xc = jnp.concatenate([x_base, low_cand], axis=1)
        return (xt, xc, y, rt, lows, ns[cid],
                jnp.broadcast_to(price, rt.shape), limit,
                (vid % 2) == 1)

    def run(carry, lane_args, grid_args):
        REPLAY_TRACES.tick()
        noise_key = grid_args[-1]
        grid = grid_args[:-1]
        wid, cid, vid, limit = lane_args
        tables = jax.vmap(
            lambda w, k, v, l: expand(noise_key, grid, w, k, v, l)
        )(wid, cid, vid, limit)

        def scan_step(c, _):
            sel, count, active = c
            sel, count, active = step_v(sel, count, active, *tables)
            return (sel, count, active), None

        (sel, count, _), _ = jax.lax.scan(scan_step, carry, None,
                                          length=rounds)
        return sel, count

    if devices is not None and len(devices) > 1:
        mesh = build_mesh("lanes", devices)
        lane = axis_specs("lanes", 1)[0]
        run = shard_map_1d(
            run, mesh,
            in_specs=((lane,) * 3, (lane,) * N_LANE_ARGS,
                      axis_specs("lanes", 0, N_GRID_TABLES)),
            out_specs=(lane, lane))
    return jax.jit(run, donate_argnums=(0,))


def _placement(devs):
    """Host-to-device placement of a dispatch's inputs: ``(lane_put,
    grid_put)`` for lane-partitioned and replicated arrays. With a
    device mesh each array goes straight to its sharding, so nothing
    is staged on the first device; otherwise everything goes to the
    default device."""
    import jax

    if devs is None:
        return jax.device_put, jax.device_put
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh("lanes", devs)
    lanes, grid = NamedSharding(mesh, P("lanes")), NamedSharding(mesh, P())
    return (lambda a: jax.device_put(a, lanes),
            lambda a: jax.device_put(a, grid))


@contextlib.contextmanager
def _staging(n_lanes: int, padded: int, devs):
    """The ``replay.stage`` span around padding, casting and placing a
    dispatch's inputs; yields ``(to_lanes, to_all)``, which place a
    lane-partitioned or a replicated host array and add its bytes to
    the span's ``bytes`` arg."""
    args = {"bytes": 0, "lanes": n_lanes, "padded": padded}

    def counted(place):
        def put(a):
            args["bytes"] += a.nbytes
            return place(a)
        return put

    with obs_trace.span("replay.stage", args=args):
        yield tuple(map(counted, _placement(devs)))


def _stage_support(pad, to_lanes, to_all, tables: LaneTables,
                   placed=None):
    """The ``replay.support_tables`` span (inside ``replay.stage``):
    pad and cast the lanes' support ids and search seeds, and place
    them with the replicated :class:`SupportGrid`, unless ``placed``
    holds the grid as an earlier block placed it; its ``bytes`` arg
    counts what was placed, ``models`` the support slots per lane."""
    grid = tables.support_grid
    if grid is None:
        raise ValueError("Karasu lanes need the support grid their "
                         "support ids index")
    args = {"models": tables.n_support, "bytes": 0}

    def counted(place, a):
        args["bytes"] += a.nbytes
        return place(a)

    with obs_trace.span("replay.support_tables", args=args):
        lane = tuple(counted(to_lanes, pad(a)) for a in (
            tables.support_ids.astype(np.int32, copy=False),
            tables.search_seed.astype(np.uint32, copy=False)))
        shared = placed if placed is not None else tuple(
            counted(to_all, a.astype(np.float64, copy=False))
            for a in (grid.mean, grid.var, grid.cov))
    return lane, shared


@dataclasses.dataclass
class PendingReplay:
    """A dispatched-but-not-fetched replay: ``sel``/``count`` may still
    be device arrays (jax async dispatch); :meth:`result` blocks."""

    n_lanes: int
    dispatches: int
    _sel: object
    _count: object
    _peaks: object = None
    #: the placed support grid (Karasu lanes), for a later block of the
    #: same matrix to reuse (``replay_async(support_grid=...)``)
    support_grid: Optional[tuple] = None

    def result(self) -> BatchReplayResult:
        with obs_trace.span("replay.wait", cat=obs_trace.CAT_DEVICE,
                            args={"lanes": self.n_lanes}):
            sel = np.asarray(self._sel)[: self.n_lanes]
            count = np.asarray(self._count)[: self.n_lanes]
            peaks = (None if self._peaks is None
                     else np.asarray(self._peaks)[: self.n_lanes])
        return BatchReplayResult(chosen=sel, count=count,
                                 dispatches=self.dispatches, peaks=peaks)


def _launch(source, cfg: ReplayConfig, devices, lanes_floor: int,
            program, stage, streams, span: str,
            args: dict) -> PendingReplay:
    """The launch body both lowerings share: size the lane bucket for
    ``source`` (:class:`LaneTables` or :class:`SeededLaneSpec`), stage
    its inputs and initial carry, and dispatch without blocking.

    ``program(lanes, slots, rounds, devs)`` gives the compiled scan;
    ``stage(pad, to_lanes, to_all)`` places the lowering's own arrays
    and returns ``(inputs, support_grid)``, the program's arguments
    after the carry and the placed grid a later block may reuse.
    ``streams`` is the x64 context of the dispatch, ``span`` and
    ``args`` name the ``replay.dispatch*`` span and its extra args."""
    n_lanes = len(source)
    if n_lanes == 0:
        return PendingReplay(
            n_lanes=0, dispatches=0,
            _sel=np.zeros((0, cfg.max_runs), np.int32),
            _count=np.zeros(0, np.int32))
    devs = tuple(pow2_devices(devices)) if devices is not None else None
    if devs is not None and len(devs) <= 1:
        devs = None  # same un-sharded program: share its cache entry
    lanes = shard_size(n_lanes, len(devs) if devs else 1,
                       floor=lanes_floor)
    slots = shard_size(cfg.max_runs)
    rounds = cfg.max_runs - cfg.n_init
    fn = program(lanes, slots, rounds, devs)

    def pad(a):  # pad the lane axis by repeating lane 0 (masked out)
        return pad_lanes(a, lanes)

    from repro.serving.engine import silence_unusable_donation

    with streams(), silence_unusable_donation():
        with _staging(n_lanes, lanes, devs) as (to_lanes, to_all):
            inputs, grid = stage(pad, to_lanes, to_all)
            # every lane starts with its n_init seeded draws evaluated
            sel0 = np.full((lanes, cfg.max_runs), -1, np.int32)
            sel0[:, : cfg.n_init] = pad(source.init_idx)
            carry0 = tuple(to_lanes(a) for a in (
                sel0, np.full(lanes, cfg.n_init, np.int32),
                np.ones(lanes, bool)))
        with REPLAY_TRACES.dispatch(span, args={
                "lanes": n_lanes, "padded": lanes, "rounds": rounds,
                **args}):
            outs = fn(carry0, *inputs)
    return PendingReplay(n_lanes, 1, *outs, support_grid=grid)


def replay_async(tables: LaneTables,
                 cfg: Optional[ReplayConfig] = None, *,
                 devices: Optional[Sequence] = None,
                 lanes_floor: int = 1,
                 support_grid: Optional[tuple] = None,
                 block: int = 0, blocks: int = 1) -> PendingReplay:
    """Dispatch every lane's full search as one (optionally sharded)
    scanned device call and return without blocking on the outputs.

    ``devices``: shard the lane axis over these devices (pow2 prefix;
    ``None`` keeps the single-device program). ``lanes_floor``:
    minimum padded lane-bucket size (a power of two), so that
    fixed-size lane blocks of differing matrices reuse one compiled
    program (``scenarios.replay_scenarios``). ``support_grid``: the
    Karasu support grid as an earlier block's :class:`PendingReplay`
    placed it, reused instead of placed again. ``block`` / ``blocks``:
    this dispatch's place among a matrix's lane blocks, for the
    ``replay.dispatch`` span.
    """
    import jax

    cfg = ReplayConfig() if cfg is None else cfg
    n_cand, dim = tables.x_train.shape[1:]
    n_support = tables.n_support
    args = {"block": block, "blocks": blocks}
    if n_support:
        args.update(support_models=n_support,
                    posterior_samples=cfg.samples)

    def program(lanes, slots, rounds, devs):
        return _replay_fn(cfg, lanes, slots, n_cand, dim, rounds, devs,
                          n_support)

    def stage(pad, to_lanes, to_all):
        # copy=False: lane_tables already builds f64 columns, so the
        # dtype casts are no-ops for the common path
        lane = tuple(to_lanes(pad(a)) for a in (
            tables.x_train.astype(np.float64, copy=False),
            tables.x_cand.astype(np.float64, copy=False),
            tables.y.astype(np.float64, copy=False),
            tables.runtime.astype(np.float64, copy=False),
            tables.util_low.astype(np.float64, copy=False),
            tables.norm_scores.astype(np.float64, copy=False),
            tables.price.astype(np.float64, copy=False),
            tables.limit.astype(np.float64, copy=False),
            tables.use_weighter.astype(bool, copy=False)))
        if not n_support:
            return (lane,), None
        support, grid = _stage_support(pad, to_lanes, to_all, tables,
                                       support_grid)
        return (lane, support, grid), grid

    # Karasu lanes draw RGPE samples: threefry's pinned derivation
    streams = x64_streams if n_support else jax.enable_x64
    return _launch(tables, cfg, devices, lanes_floor, program, stage,
                   streams, "replay.dispatch", args)


def replay(tables: LaneTables,
           cfg: Optional[ReplayConfig] = None, *,
           devices: Optional[Sequence] = None,
           lanes_floor: int = 1) -> BatchReplayResult:
    """Run every lane's full search as one scanned device dispatch
    (sharded over ``devices`` when given) and fetch the result."""
    return replay_async(tables, cfg, devices=devices,
                        lanes_floor=lanes_floor).result()


def replay_seeded_async(spec: SeededLaneSpec,
                        cfg: Optional[ReplayConfig] = None, *,
                        devices: Optional[Sequence] = None,
                        lanes_floor: int = 1,
                        block: int = 0,
                        blocks: int = 1) -> PendingReplay:
    """Dispatch a seeded replay: lane tables are generated *inside*
    the compiled program from ``spec``'s grid + per-lane ids, so the
    host ships O(W*C + K*C + L) arrays instead of the O(L*C*D)
    :class:`LaneTables`. Options mirror :func:`replay_async`.

    The condition axis is pow2-padded so matrices with different
    condition counts reuse one compiled program."""
    cfg = ReplayConfig() if cfg is None else cfg
    n_cand, base_dim = spec.x_base.shape
    n_conds = shard_size(len(spec.norm_scores))

    def program(lanes, slots, rounds, devs):
        return _seeded_replay_fn(cfg, lanes, slots, n_cand, base_dim,
                                 rounds, spec.base_runtime.shape[0],
                                 n_conds, float(spec.noise_scale), devs)

    def stage(pad, to_lanes, to_all):
        # pad the condition axis to pow2: fleet sweeps with differing
        # condition counts then share one compiled program
        ns, fp = (np.concatenate(
            [a, np.zeros((n_conds - len(a),) + a.shape[1:])], 0)
            for a in (spec.norm_scores, spec.fp_low))
        lane_args = tuple(to_lanes(pad(a)) for a in (
            spec.workload_id.astype(np.int32, copy=False),
            spec.condition_id.astype(np.int32, copy=False),
            spec.variant_id.astype(np.int32, copy=False),
            spec.limit.astype(np.float64, copy=False)))
        grid_args = tuple(to_all(a) for a in (
            spec.base_runtime.astype(np.float64, copy=False),
            spec.low_num.astype(np.float64, copy=False),
            spec.low_caps.astype(np.float64, copy=False),
            spec.x_base.astype(np.float64, copy=False),
            spec.price.astype(np.float64, copy=False),
            spec.count.astype(np.float64, copy=False),
            spec.config_uid.astype(np.int32, copy=False),
            ns.astype(np.float64, copy=False),
            fp.astype(np.float64, copy=False),
            spec.noise_key))
        return (lane_args, grid_args), None

    return _launch(spec, cfg, devices, lanes_floor, program, stage,
                   x64_streams, "replay.dispatch_seeded",
                   {"block": block, "blocks": blocks})


def replay_seeded(spec: SeededLaneSpec,
                  cfg: Optional[ReplayConfig] = None, *,
                  devices: Optional[Sequence] = None,
                  lanes_floor: int = 1) -> BatchReplayResult:
    """Run a seeded replay (tables generated in-program) and fetch."""
    return replay_seeded_async(spec, cfg, devices=devices,
                               lanes_floor=lanes_floor).result()


def traces_from_result(tables: LaneTables, result: BatchReplayResult,
                       configs) -> List["SearchTrace"]:
    """Materialize per-lane :class:`tuning.cherrypick.SearchTrace`
    objects (identical field-for-field to the sequential traces when
    the lane reproduced the sequential decisions).

    Vectorized across lanes (one gather + running-min per field): the
    per-lane python work is just the object construction, which keeps
    trace materialization cheap enough to overlap with a lane block's
    device scan."""
    n = len(tables)
    if n == 0:
        return []
    with obs_trace.span("replay.materialize_traces", args={"lanes": n}):
        picks_all = result.chosen[:n]
        idx = np.maximum(picks_all, 0)
        costs_all = np.take_along_axis(tables.cost, idx, axis=1)
        runtimes_all = np.take_along_axis(tables.runtime, idx, axis=1)
        return _materialize_traces(picks_all, result.count[:n],
                                   costs_all, runtimes_all,
                                   tables.limit[:n], configs)


def traces_from_spec(spec: SeededLaneSpec, result: BatchReplayResult,
                     configs) -> List["SearchTrace"]:
    """Materialize seeded-replay traces: per-lane costs/runtimes are
    gathered from the spec's host-side (W, C) grid tables via the
    lane's workload row — no per-lane tables needed."""
    n = len(spec)
    if n == 0:
        return []
    with obs_trace.span("replay.materialize_traces", args={"lanes": n}):
        picks_all = result.chosen[:n]
        idx = np.maximum(picks_all, 0)
        wid = spec.workload_id[:n, None]
        costs_all = spec.cost[wid, idx]
        runtimes_all = spec.runtime[wid, idx]
        return _materialize_traces(picks_all, result.count[:n],
                                   costs_all, runtimes_all,
                                   spec.limit[:n], configs)


def _materialize_traces(picks_all, counts, costs_all, runtimes_all,
                        limits, configs) -> List["SearchTrace"]:
    from repro.tuning.cherrypick import SearchTrace

    valid = runtimes_all <= limits[:, None]
    # running min over valid runs only; lanes with no valid run yet
    # stay at +inf (the sequential bookkeeping)
    best_all = np.minimum.accumulate(
        np.where(valid, costs_all, np.inf), axis=1)

    out = []
    for lane in range(len(counts)):
        k = int(counts[lane])
        out.append(SearchTrace(
            evaluated=[configs[int(i)] for i in picks_all[lane, :k]],
            costs=costs_all[lane, :k].tolist(),
            runtimes=runtimes_all[lane, :k].tolist(),
            best_valid_cost=best_all[lane, :k].tolist(),
            search_cost=float(np.sum(costs_all[lane, :k]))))
    return out
