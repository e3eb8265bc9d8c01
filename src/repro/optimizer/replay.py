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

``replay_async`` dispatches and defers the host fetch
(:class:`PendingReplay`) — a real overlap window on asynchronous
backends (GPU/TPU dispatch returns before compute finishes). XLA:CPU
executes synchronously, so there ``scenarios.replay_pipelined``
produces the overlap instead: per-device worker threads run this same
entry point while the main thread builds the next lane block's
tables.

All math runs in float64 (``jax.enable_x64`` around the
dispatch) so batched lanes reproduce the sequential scipy traces
bit-for-bit on identical seeds: same evaluated configs, same
best-valid-cost curves (see tests/test_optimizer.py).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.common.mesh import (axis_specs, build_mesh, pad_lanes,
                               pow2_devices, shard_map_1d, shard_size)
from repro.obs.jaxstat import JitSite

#: Ticked once per tracing of the scanned replay program — a
#: registry-backed :class:`repro.obs.jaxstat.JitSite` whose
#: ``dispatch()`` wrapper additionally books per-dispatch wall time
#: into compile-vs-run registry counters and records a device span.
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

    def __len__(self) -> int:
        return len(self.y)


@dataclasses.dataclass
class BatchReplayResult:
    chosen: np.ndarray  # (L, max_runs) evaluated config indices, -1 pad
    count: np.ndarray  # (L,) evaluations performed per lane
    dispatches: int  # device dispatches of this replay (always 1)


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
               price, limit, use_w, *, cfg: ReplayConfig, slots: int):
    """One BO round of one lane (vmapped over lanes by the caller)."""
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
    return sel, count, advance


#: Number of stacked lane-table arrays a replay dispatch consumes.
N_TABLES = 9

#: Replicated grid tables of a seeded dispatch (incl. the noise key).
N_GRID_TABLES = 10

#: Per-lane arrays of a seeded dispatch (ids + limit).
N_LANE_ARGS = 4

# first call per program signature traces + compiles; concurrent cold
# calls from the pipelined per-device workers would each do so (jax
# does not dedupe concurrent first-call tracing) — serialize only the
# cold call, warm dispatches stay lock-free
_COMPILED_SIGNATURES: set = set()
_COMPILE_LOCK = threading.Lock()


@functools.lru_cache(maxsize=32)
def _replay_fn(cfg: ReplayConfig, lanes: int, slots: int, n_cand: int,
               dim: int, rounds: int,
               devices: Optional[Tuple] = None):
    """Jitted scan program for one (config, shape, mesh) signature.

    ``devices=None`` is the single-device program. A device tuple
    shards the lane axis: each device scans its own
    ``lanes/len(devices)`` lane bucket (``shard_map`` around the
    vmapped step), one dispatch total.
    """
    import jax

    step = functools.partial(_lane_step, cfg=cfg, slots=slots)
    step_v = jax.vmap(step)

    def run(carry, tables):
        REPLAY_TRACES.tick()

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
        run = shard_map_1d(run, mesh,
                           in_specs=((lane,) * 3, (lane,) * N_TABLES),
                           out_specs=(lane, lane))
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


def _placement(device, devs):
    """Host-to-device placement of a dispatch's inputs: ``(lane_put,
    grid_put)`` for lane-partitioned and replicated arrays. With a
    device mesh each array goes straight to its sharding, so nothing
    is staged on the first device; otherwise everything goes to
    ``device`` (or the default device)."""
    import jax

    if devs is None:
        def put(a):
            return jax.device_put(a, device)
        return put, put
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh("lanes", devs)
    lanes, grid = NamedSharding(mesh, P("lanes")), NamedSharding(mesh, P())
    return (lambda a: jax.device_put(a, lanes),
            lambda a: jax.device_put(a, grid))


@dataclasses.dataclass
class PendingReplay:
    """A dispatched-but-not-fetched replay: ``sel``/``count`` may still
    be device arrays (jax async dispatch); :meth:`result` blocks."""

    n_lanes: int
    dispatches: int
    _sel: object
    _count: object

    def result(self) -> BatchReplayResult:
        sel = np.asarray(self._sel)[: self.n_lanes]
        count = np.asarray(self._count)[: self.n_lanes]
        return BatchReplayResult(chosen=sel, count=count,
                                 dispatches=self.dispatches)


def replay_async(tables: LaneTables,
                 cfg: Optional[ReplayConfig] = None, *,
                 devices: Optional[Sequence] = None,
                 device=None,
                 lanes_floor: int = 1) -> PendingReplay:
    """Dispatch every lane's full search as one (optionally sharded)
    scanned device call and return without blocking on the outputs.

    ``devices``: shard the lane axis over these devices (pow2 prefix;
    ``None`` keeps the single-device program). ``device``: place the
    single-device program's inputs on that device instead of the
    default — ``replay_pipelined`` round-robins lane blocks over the
    devices this way, so blocks execute concurrently as independent
    per-device dispatches. ``lanes_floor``: minimum padded lane-bucket
    size (a power of two) — fixed-size lane blocks let differing
    matrix sizes reuse one compiled program (see
    ``scenarios.replay_pipelined``).
    """
    import jax

    cfg = ReplayConfig() if cfg is None else cfg
    if devices is not None and device is not None:
        raise ValueError("pass either devices= (shard_map) or "
                         "device= (placement), not both")
    n_lanes = len(tables)
    if n_lanes == 0:
        return PendingReplay(
            n_lanes=0, dispatches=0,
            _sel=np.zeros((0, cfg.max_runs), np.int32),
            _count=np.zeros(0, np.int32))
    devs = tuple(pow2_devices(devices)) if devices is not None else None
    if devs is not None and len(devs) <= 1:
        devs = None  # same un-sharded program: share its cache entry
    n_dev = len(devs) if devs else 1
    lanes = shard_size(n_lanes, n_dev, floor=lanes_floor)
    slots = shard_size(cfg.max_runs)
    n_cand, dim = tables.x_train.shape[1:]
    rounds = cfg.max_runs - cfg.n_init

    def pad(a):  # pad the lane axis by repeating lane 0 (masked out)
        return pad_lanes(a, lanes)

    sel0 = np.full((lanes, cfg.max_runs), -1, np.int32)
    sel0[:, : cfg.n_init] = pad(tables.init_idx)
    count0 = np.full(lanes, cfg.n_init, np.int32)
    active0 = np.ones(lanes, bool)

    from repro.serving.engine import silence_unusable_donation

    fn = _replay_fn(cfg, lanes, slots, n_cand, dim, rounds, devs)
    to_dev, _ = _placement(device, devs)

    with jax.enable_x64(), silence_unusable_donation():
        # copy=False: lane_tables already builds f64 columns, so the
        # dtype casts are no-ops for the common path
        jnp_tables = tuple(
            to_dev(pad(a)) for a in (
                tables.x_train.astype(np.float64, copy=False),
                tables.x_cand.astype(np.float64, copy=False),
                tables.y.astype(np.float64, copy=False),
                tables.runtime.astype(np.float64, copy=False),
                tables.util_low.astype(np.float64, copy=False),
                tables.norm_scores.astype(np.float64, copy=False),
                tables.price.astype(np.float64, copy=False),
                tables.limit.astype(np.float64, copy=False),
                tables.use_weighter.astype(bool, copy=False)))
        carry0 = (to_dev(sel0), to_dev(count0), to_dev(active0))
        # keyed on placement too: each device's first call compiles
        # its own executable and must take the serialized branch
        sig = (cfg, lanes, slots, n_cand, dim, rounds, devs, device)
        with REPLAY_TRACES.dispatch(
                "replay.dispatch",
                args={"lanes": n_lanes, "padded": lanes,
                      "rounds": rounds}):
            if sig in _COMPILED_SIGNATURES:
                sel, count = fn(carry0, jnp_tables)
            else:
                with _COMPILE_LOCK:
                    sel, count = fn(carry0, jnp_tables)
                    _COMPILED_SIGNATURES.add(sig)
    return PendingReplay(n_lanes=n_lanes, dispatches=1,
                         _sel=sel, _count=count)


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
                        device=None,
                        lanes_floor: int = 1) -> PendingReplay:
    """Dispatch a seeded replay: lane tables are generated *inside*
    the compiled program from ``spec``'s grid + per-lane ids, so the
    host ships O(W*C + K*C + L) arrays instead of the O(L*C*D)
    :class:`LaneTables`. Options mirror :func:`replay_async`.

    The condition axis is pow2-padded so matrices with different
    condition counts reuse one compiled program."""
    from repro.common.rng import x64_streams

    cfg = ReplayConfig() if cfg is None else cfg
    if devices is not None and device is not None:
        raise ValueError("pass either devices= (shard_map) or "
                         "device= (placement), not both")
    n_lanes = len(spec)
    if n_lanes == 0:
        return PendingReplay(
            n_lanes=0, dispatches=0,
            _sel=np.zeros((0, cfg.max_runs), np.int32),
            _count=np.zeros(0, np.int32))
    devs = tuple(pow2_devices(devices)) if devices is not None else None
    if devs is not None and len(devs) <= 1:
        devs = None  # same un-sharded program: share its cache entry
    n_dev = len(devs) if devs else 1
    lanes = shard_size(n_lanes, n_dev, floor=lanes_floor)
    slots = shard_size(cfg.max_runs)
    n_cand, base_dim = spec.x_base.shape
    rounds = cfg.max_runs - cfg.n_init
    n_workloads = spec.base_runtime.shape[0]
    # pad the condition axis to pow2: fleet sweeps with differing
    # condition counts then share one compiled program
    n_conds = shard_size(len(spec.norm_scores))
    ns, fp = spec.norm_scores, spec.fp_low
    if n_conds > len(ns):
        extra = n_conds - len(ns)
        ns = np.concatenate([ns, np.zeros((extra,) + ns.shape[1:])], 0)
        fp = np.concatenate([fp, np.zeros((extra,) + fp.shape[1:])], 0)

    def pad(a):  # pad the lane axis by repeating lane 0 (masked out)
        return pad_lanes(a, lanes)

    sel0 = np.full((lanes, cfg.max_runs), -1, np.int32)
    sel0[:, : cfg.n_init] = pad(spec.init_idx)
    count0 = np.full(lanes, cfg.n_init, np.int32)
    active0 = np.ones(lanes, bool)

    from repro.serving.engine import silence_unusable_donation

    fn = _seeded_replay_fn(cfg, lanes, slots, n_cand, base_dim, rounds,
                           n_workloads, n_conds,
                           float(spec.noise_scale), devs)
    to_dev, to_all = _placement(device, devs)

    with x64_streams(), silence_unusable_donation():
        lane_args = tuple(
            to_dev(pad(a)) for a in (
                spec.workload_id.astype(np.int32, copy=False),
                spec.condition_id.astype(np.int32, copy=False),
                spec.variant_id.astype(np.int32, copy=False),
                spec.limit.astype(np.float64, copy=False)))
        grid_args = tuple(
            to_all(a) for a in (
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
        carry0 = (to_dev(sel0), to_dev(count0), to_dev(active0))
        sig = ("seeded", cfg, lanes, slots, n_cand, base_dim, rounds,
               n_workloads, n_conds, devs, device)
        with REPLAY_TRACES.dispatch(
                "replay.dispatch_seeded",
                args={"lanes": n_lanes, "padded": lanes,
                      "rounds": rounds}):
            if sig in _COMPILED_SIGNATURES:
                sel, count = fn(carry0, lane_args, grid_args)
            else:
                with _COMPILE_LOCK:
                    sel, count = fn(carry0, lane_args, grid_args)
                    _COMPILED_SIGNATURES.add(sig)
    return PendingReplay(n_lanes=n_lanes, dispatches=1,
                         _sel=sel, _count=count)


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
    trace materialization cheap enough to overlap with device scans in
    the pipelined path."""
    n = len(tables)
    if n == 0:
        return []
    picks_all = result.chosen[:n]
    idx = np.maximum(picks_all, 0)
    costs_all = np.take_along_axis(tables.cost, idx, axis=1)
    runtimes_all = np.take_along_axis(tables.runtime, idx, axis=1)
    return _materialize_traces(picks_all, result.count[:n], costs_all,
                               runtimes_all, tables.limit[:n], configs)


def traces_from_spec(spec: SeededLaneSpec, result: BatchReplayResult,
                     configs) -> List["SearchTrace"]:
    """Materialize seeded-replay traces: per-lane costs/runtimes are
    gathered from the spec's host-side (W, C) grid tables via the
    lane's workload row — no per-lane tables needed."""
    n = len(spec)
    if n == 0:
        return []
    picks_all = result.chosen[:n]
    idx = np.maximum(picks_all, 0)
    wid = spec.workload_id[:n, None]
    costs_all = spec.cost[wid, idx]
    runtimes_all = spec.runtime[wid, idx]
    return _materialize_traces(picks_all, result.count[:n], costs_all,
                               runtimes_all, spec.limit[:n], configs)


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
