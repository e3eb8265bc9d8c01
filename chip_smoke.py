"""Smoke test of the Perona fleet path on a TPU, through its normal
entry points.

    python3 chip_smoke.py             # one chip: train, stream, kernel, replay
    python3 chip_smoke.py --chips 4   # four chips: sharded scorer and replay
                                      # against one device of the same host

One chip, in order:

1. device check — no TPU, no run (there is no CPU fallback);
2. train — ``launch.serve.serve_daemon`` first runs the scanned
   trainer on the seeded acquisition of the fleet's first
   ``TRAIN_NODES`` nodes; the loss history must be finite and
   fall;
3. stream — the same ``serve_daemon`` call, with faults on:
   IngestionDaemon -> FleetScoringService -> FingerprintStore ->
   ShardedScorer over NODES nodes x the six-tool suite, ROUNDS rounds,
   one node truly degraded. No flush may fail and no dispatch may be
   retried; every admitted row is scored and finite except the rows the
   backpressure ladder reports it sampled out; the degraded node is
   flagged; every dispatched stack is rescored on the host CPU through
   the same ``make_score_fn`` and must agree within SCORE_TOL;
4. kernel — the edge-softmax Pallas kernel compiled for the chip
   (``tpu_custom_call`` in the lowered program) at the model's widths,
   against ``kernels/edge_softmax/ref.py`` on the CPU;
5. replay — the §IV-D 432-lane matrix through
   ``optimizer.replay_scenarios``, lane-for-lane trace parity with the
   sequential ``reference_search``; then Karasu lanes (every workload x
   KARASU_SEEDS x {karasu, karasu+perona} x both conditions, 17 RGPE
   support models each from ``support_history``) through the same
   entry, trace parity with ``tuning.karasu``.

Four chips run only what exists across chips, each against one device
of the same host, and require bit-identical results: the
``ShardedScorer`` over a 4-device ``"fleet"`` mesh, and the replay with
its lane axis sharded over 4 devices, CherryPick/Arrow lanes and
Karasu lanes (bit-identical to one device at the same per-device lane
count; see ``phase_sharded_karasu``).

Any failed check raises. The last line of standard output is a JSON
object ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: Nodes in the streamed fleet: an assumed cloud-region size.
NODES = 1024
#: Telemetry rounds; the degraded node turns bad at ROUNDS // 2.
ROUNDS = 6
SEED = 0
#: Max |anomaly_prob(chip) - anomaly_prob(CPU)| over every dispatched
#: row. The TPU runs f32 matmuls with bf16 operands by default (one
#: pass). Rounding the matmul operands to bf16 on the CPU moves this
#: model's scores by up to 8.0e-3 (64 nodes) and 1.1e-2 (256 nodes):
#: the logits pass about eight matmuls, each operand rounded to 8
#: mantissa bits. The bound leaves 3x headroom for the larger fleet.
SCORE_TOL = 3e-2
#: The kernel runs f32 arithmetic on the VPU (no matmul), so it may
#: differ from the CPU oracle only in summation order and in the exp
#: implementation: a few f32 ulps at O(1) values.
KERNEL_TOL = 1e-5
#: The model's attention widths: K = 32 split over 4 heads, P = 3.
KERNEL_SHAPE = dict(n=4096, heads=4, head_dim=8, preds=3)
#: The §IV-D matrix: 18 workloads x 3 seeds x 4 variants x 2 conditions.
REPLAY_SEEDS = (0, 1, 2)
#: search seeds of the Karasu lanes (72 lanes, padded to 128)
KARASU_SEEDS = (0,)


def _say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def device_check(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; exits unless they are
    TPUs, ``chips`` of them or more."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}
    _say("device", f"platform={info['platform']} kind={info['kind']} "
                   f"count={info['count']}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {d0.platform}); "
                 "nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {len(devices)}")
    return info


def _cpu():
    import jax

    return jax.devices("cpu")[0]


# ------------------------------------------------------- train and stream
def phase_serve(nodes: int, rounds: int, seed: int) -> None:
    """``serve_daemon`` trains the model on its acquisition, then streams
    the faulty telemetry; both phases are checked here."""
    import numpy as np

    from repro.core.trainer import TRAINER_TRACES
    from repro.fleet.shard import ShardedScorer
    from repro.launch.serve import TRAIN_NODES, serve_daemon

    dispatched = []  # (params, stack, outputs) of every scorer dispatch
    score_stack = ShardedScorer.score_stack

    def recorded(self, params, stack):
        out = score_stack(self, params, stack)
        dispatched.append((params, stack, out))
        return out

    ShardedScorer.score_stack = recorded
    try:
        t0 = time.perf_counter()
        out = serve_daemon(nodes, rounds, seed=seed, faults=True)
        wall = time.perf_counter() - t0
    finally:
        ShardedScorer.score_stack = score_stack

    trained = out["trained"]
    losses = [h["train_loss"] for h in trained.history]
    _say("train", f"first {min(nodes, TRAIN_NODES)} nodes: "
                  f"{len(trained.frame)} executions, {len(losses)} "
                  f"epochs, loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
                  f"one dispatch incl. compile "
                  f"{TRAINER_TRACES.compile_seconds:.1f}s")
    check(all(map(math.isfinite, losses)), "non-finite training loss")
    check(losses[-1] < losses[0], "training loss did not fall")

    st = out["stats"]
    svc = out["daemon"].service
    sst = svc.stats
    _say("stream", f"{nodes} nodes x {rounds} rounds: "
                   f"{st['events_seen']} events, "
                   f"{st['rows_staged_total']} rows staged, "
                   f"{sst['flushes']} flushes, {sst['dispatches']} "
                   f"dispatches, quarantined {sst['quarantined_rows']}, "
                   f"shed {st['shed_rows']}, sampled out "
                   f"{st['degrade_unscored_rows']}, flush_failures="
                   f"{st['flush_failures']}, scorer_retries="
                   f"{st['scorer_retries']}")
    _say("stream", f"scorer compile {svc.scorer.jit.compile_seconds:.1f}s "
                   f"({svc.scorer.trace_count} programs), warm dispatches "
                   f"{svc.scorer.jit.run_seconds:.3f}s, wall {wall:.1f}s "
                   "incl. training")
    check(st["flush_failures"] == 0, "a flush failed")
    check(st["scorer_retries"] == 0, "a dispatch was retried")

    streamed = svc.store.anomaly[len(trained.frame):]
    unscored = np.isnan(streamed)
    check(int(unscored.sum()) == st["degrade_unscored_rows"],
          f"{int(unscored.sum())} unscored rows, but the ladder sampled "
          f"out {st['degrade_unscored_rows']}")
    check(np.isfinite(streamed[~unscored]).all(), "non-finite score")
    check(sst["rows_scored"] == int((~unscored).sum()),
          "scored-row count disagrees with the store")
    for results in out["daemon"].results().values():
        for r in results:
            check(np.isfinite(r.anomaly_prob).all(),
                  f"non-finite result for {r.node}")
    _say("stream", f"degraded node {out['degraded_node']}; flagged "
                   f"{len(out['flagged'])} node(s): {out['flagged'][:8]}")
    check(out["degraded_node"] in out["flagged"],
          "the degraded node was not flagged")

    worst = _cpu_reference_gap(trained, dispatched)
    _say("stream", f"max |d anomaly_prob| chip vs CPU reference over "
                   f"{len(dispatched)} dispatched stacks: {worst:.3e} "
                   f"(tolerance {SCORE_TOL:.0e})")
    check(worst <= SCORE_TOL,
          f"chip scores differ from the CPU by {worst}")


def _cpu_reference_gap(trained, dispatched) -> float:
    """Rescore every dispatched stack with the same params through
    ``make_score_fn`` on the host CPU; returns the largest
    |anomaly_prob| difference."""
    import jax
    import numpy as np

    from repro.serving.engine import ARG_NAMES, make_score_fn

    cpu = _cpu()
    with jax.default_device(cpu):
        score = jax.jit(jax.vmap(
            make_score_fn(trained.model, trained.pre),
            in_axes=(None,) + (0,) * len(ARG_NAMES)))
        worst, placed = 0.0, {}
        for params, stack, got in dispatched:
            if id(params) not in placed:
                placed[id(params)] = jax.device_put(params, cpu)
            ref = score(placed[id(params)],
                        *(stack[k] for k in ARG_NAMES))
            want = np.asarray(ref["anomaly_prob"])
            check(np.isfinite(got["anomaly_prob"]).all()
                  and np.isfinite(want).all(), "non-finite score")
            worst = max(worst, float(np.abs(got["anomaly_prob"]
                                            - want).max()))
    return worst


# ----------------------------------------------------------------- kernel
def phase_kernel(seed: int) -> None:
    import jax
    import numpy as np

    from repro.kernels.edge_softmax import ops, ref

    check(not ops._interpret_default(), "kernel would run interpreted")
    n, h, hd, p = (KERNEL_SHAPE[k]
                   for k in ("n", "heads", "head_dim", "preds"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (n, h, hd))
    k = jax.random.normal(ks[1], (n, p, h, hd))
    v = jax.random.normal(ks[2], (n, p, h, hd))
    mask = jax.random.bernoulli(ks[3], 0.8, (n, p))

    t0 = time.perf_counter()
    lowered = jax.jit(lambda *a: ops.edge_softmax_aggregate(
        *a, interpret=False)).lower(q, k, v, mask)
    check("tpu_custom_call" in lowered.as_text(), "no Mosaic kernel")
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    out, att = compiled(q, k, v, mask)
    with jax.default_device(_cpu()):
        want_out, want_att = ref.edge_softmax_aggregate(
            *jax.device_put((q, k, v, mask), _cpu()))
    gap = max(float(np.abs(np.asarray(out) - np.asarray(want_out)).max()),
              float(np.abs(np.asarray(att) - np.asarray(want_att)).max()))
    _say("kernel", f"edge_softmax N={n} H={h} hd={hd} P={p}: "
                   f"tpu_custom_call present, compile {t_compile:.2f}s, "
                   f"max |d| vs ref.py on CPU {gap:.3e} "
                   f"(tolerance {KERNEL_TOL:.0e})")
    check(gap <= KERNEL_TOL, f"kernel differs from ref.py by {gap}")


# ----------------------------------------------------------------- replay
def replay_matrix():
    """The §IV-D scenario matrix with its machine scores: every
    workload x REPLAY_SEEDS x variant x (healthy, drift-degraded)."""
    from benchmarks.bench_optimizer import _conditions, _profile_scores
    from repro.optimizer import build_scenarios
    from repro.tuning.scout import VM_TYPES, ScoutDataset

    ds = ScoutDataset(seed=SEED)
    scens = build_scenarios(ds, seeds=REPLAY_SEEDS,
                            conditions=_conditions())
    return ds, scens, _profile_scores(VM_TYPES)


def phase_replay() -> None:
    from repro.optimizer import (REPLAY_TRACES, reference_search,
                                 replay_scenarios)

    ds, scens, scores = replay_matrix()
    t0 = time.perf_counter()
    traces = replay_scenarios(ds, scens, scores)
    wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs = [reference_search(ds, sc, scores) for sc in scens]
    t_ref = time.perf_counter() - t0
    same = sum(_same_trace(a, b) for a, b in zip(refs, traces))
    _say("replay", f"{len(scens)} lanes: trace parity {same}/"
                   f"{len(scens)} vs reference_search; replay compile "
                   f"{REPLAY_TRACES.compile_seconds:.1f}s, replay wall "
                   f"{wall:.1f}s, sequential reference {t_ref:.1f}s")
    check(same == len(scens), f"trace parity {same}/{len(scens)}")


def karasu_matrix():
    """Karasu lanes over the §IV-D shape, with the support history
    they borrow from (8 past CherryPick searches a workload)."""
    from benchmarks.bench_optimizer import _conditions, _profile_scores
    from repro.optimizer import build_scenarios, support_history
    from repro.tuning.scout import VM_TYPES, ScoutDataset

    ds = ScoutDataset(seed=SEED)
    scores = _profile_scores(VM_TYPES)
    hist = support_history(ds, scores)
    scens = build_scenarios(ds, seeds=KARASU_SEEDS,
                            variants=("karasu", "karasu+perona"),
                            conditions=_conditions())
    return ds, scens, scores, hist


def phase_karasu() -> None:
    from repro.optimizer import reference_search, replay_scenarios

    ds, scens, scores, hist = karasu_matrix()
    t0 = time.perf_counter()
    traces = replay_scenarios(ds, scens, scores, support=hist)
    wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    refs = [reference_search(ds, sc, scores, support=hist) for sc in scens]
    t_ref = time.perf_counter() - t0
    same = sum(_same_trace(a, b) for a, b in zip(refs, traces))
    _say("karasu", f"{len(scens)} lanes x {hist.n_support} support "
                   f"models: trace parity {same}/{len(scens)} vs "
                   f"tuning.karasu; replay wall {wall:.1f}s incl. "
                   f"compile, sequential reference {t_ref:.1f}s")
    check(same == len(scens), f"Karasu trace parity {same}/{len(scens)}")


def _same_trace(a, b) -> bool:
    return ([c.key for c in a.evaluated] == [c.key for c in b.evaluated]
            and a.best_valid_cost == b.best_valid_cost
            and a.costs == b.costs and a.runtimes == b.runtimes
            and a.search_cost == b.search_cost)


# ------------------------------------------------------------- four chips
def phase_sharded_scorer(devices, nodes: int, seed: int) -> None:
    """Score one fleet round through the service on a 4-device mesh
    and on its first device alone: identical stacks, identical bits."""
    import jax
    import numpy as np

    from repro.core.graph_data import build_graphs
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.fingerprint.runner import SuiteRunner
    from repro.fleet import FleetScoringService
    from repro.launch.serve import fleet_machines

    machines = fleet_machines(nodes)
    runner = SuiteRunner(seed=seed)
    history = runner.run_frame(machines, runs_per_type=10,
                               stress_fraction=0.2)
    pre = Preprocessor().fit(history)
    edge_dim = build_graphs(history, pre).edge.shape[-1]
    model = PeronaModel(PeronaConfig(feature_dim=pre.feature_dim,
                                     edge_dim=edge_dim))
    with jax.default_device(_cpu()):
        # host params: nothing sits on a chip before the scorer places it
        params = jax.tree_util.tree_map(
            np.asarray, model.init(jax.random.PRNGKey(seed)))
    param_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    rnd = runner.run_frame(machines, runs_per_type=1, t_offset=86400.0)

    results = {}
    for n_dev in (len(devices), 1):
        mesh = set(devices[:n_dev])
        svc = FleetScoringService(model, params, pre,
                                  devices=devices[:n_dev])
        check(svc.scorer.n_devices == n_dev, "mesh size")
        svc.seed_history(history)
        placements = []  # (device set, shard shape, shape) per stack array
        place_stack = svc.scorer.place_stack

        def recorded(stack):
            args = place_stack(stack)
            placements.extend((a.sharding.device_set,
                               a.sharding.shard_shape(a.shape), a.shape)
                              for a in args)
            return args

        svc.scorer.place_stack = recorded
        t0 = time.perf_counter()
        results[n_dev] = svc.score_round(rnd)
        _say("4chip", f"scorer on {n_dev} device(s): {len(rnd)} rows "
                      f"of {len(results[n_dev])} nodes in "
                      f"{svc.stats['dispatches']} dispatch(es), "
                      f"{time.perf_counter() - t0:.1f}s incl. compile")
        placed = jax.tree_util.tree_leaves(svc.scorer.place_params(params))
        check(all(leaf.sharding.device_set == mesh for leaf in placed),
              "params not on every device")
        check(placements and all(
            devs == mesh and shard[0] * n_dev == shape[0]
            for devs, shard, shape in placements),
            "a request stack is not split over every device")
        if n_dev > 1:
            used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                    for d in devices]
            _say("4chip", f"after the {n_dev}-device round: device bytes "
                          f"in use {used}, params {param_bytes} bytes, "
                          f"{len(placements)} stack arrays split "
                          f"{n_dev} ways")
            check(min(used) >= param_bytes,
                  "a device does not hold the replicated params")
    sharded, single = results[len(devices)], results[1]
    check(sorted(sharded) == sorted(single), "scored node sets differ")
    worst = 0.0
    for node in single:
        for field in ("anomaly_prob", "codes", "type_logits"):
            a = getattr(sharded[node], field)
            b = getattr(single[node], field)
            worst = max(worst, float(np.abs(a - b).max()))
    _say("4chip", f"scorer {len(devices)} devices vs 1: max |d| = "
                  f"{worst:.3e} over {len(single)} nodes")
    check(worst == 0.0, "sharded scores are not bit-identical")


def phase_sharded_replay(devices) -> None:
    """The 432-lane matrix with its lane axis sharded over ``devices``
    against the single-device program: identical picks and counts."""
    import numpy as np

    from repro.optimizer import (ReplayConfig, lane_tables, replay,
                                 replay_async)

    ds, scens, scores = replay_matrix()
    cfg = ReplayConfig()
    tab = lane_tables(ds, scens, scores, cfg)
    t0 = time.perf_counter()
    single = replay(tab, cfg)
    t_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    pending = replay_async(tab, cfg, devices=devices)
    for out in (pending._sel, pending._count):
        check(out.sharding.device_set == set(devices)
              and out.sharding.shard_shape(out.shape)[0] * len(devices)
              == out.shape[0], "replay lanes are not split over every "
                               "device")
    sharded = pending.result()
    t_sharded = time.perf_counter() - t0
    same = int(np.sum(np.all(sharded.chosen == single.chosen, axis=1)
                      & (sharded.count == single.count)))
    _say("4chip", f"replay on {len(devices)} devices vs 1: {same}/"
                  f"{len(scens)} lanes bit-identical; compile+run "
                  f"{t_sharded:.1f}s sharded, {t_single:.1f}s single")
    check(same == len(scens), "sharded replay diverged")


def phase_sharded_karasu(devices) -> None:
    """The Karasu lanes with the lane axis sharded over ``devices``
    (support grid replicated) against one device. The float64 EI's
    last bits depend on how many lanes one device's program holds, so
    the first device's lanes must match the single-device program at
    that per-device lane count bit for bit (picks, counts, every
    round's peak EI), and every lane must pick as the single-device
    program over the whole matrix, with peaks within the Karasu
    cell's ``ei_peak_rtol``."""
    import dataclasses

    import numpy as np

    from repro.common.mesh import shard_size
    from repro.optimizer import ReplayConfig, lane_tables, replay

    cfg = ReplayConfig()
    ds, scens, scores, hist = karasu_matrix()
    tab = lane_tables(ds, scens, scores, cfg, hist)
    single = replay(tab, cfg)
    sharded = replay(tab, cfg, devices=devices)
    per_dev = shard_size(len(tab), len(devices)) // len(devices)
    head = dataclasses.replace(tab, **{
        f.name: getattr(tab, f.name)[:per_dev]
        for f in dataclasses.fields(tab)
        if isinstance(getattr(tab, f.name), np.ndarray)})
    first = replay(head, cfg, lanes_floor=per_dev)
    n = len(first.count)
    same = int(np.sum(np.all(sharded.chosen == single.chosen, axis=1)
                      & (sharded.count == single.count)))
    first_bits = (np.array_equal(sharded.chosen[:n], first.chosen)
                  and np.array_equal(sharded.count[:n], first.count)
                  and np.array_equal(sharded.peaks[:n], first.peaks,
                                     equal_nan=True))
    gap = float(np.nanmax(np.abs(sharded.peaks - single.peaks)
                          / np.maximum(np.abs(single.peaks), 1e-12),
                          initial=0.0))
    _say("4chip", f"Karasu replay on {len(devices)} devices vs 1: "
                  f"{same}/{len(scens)} lanes with identical picks and "
                  f"counts, largest relative peak EI gap {gap:.3e}; the "
                  f"first device's {n} lanes bit-identical to one "
                  f"device at {per_dev} lanes: {first_bits}")
    check(same == len(scens), "sharded Karasu replay diverged")
    check(first_bits, "sharded Karasu lanes differ from one device "
                      "at the same lane count")
    check(gap <= 1e-7, f"sharded Karasu peak EI gap {gap}")


# ------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the paths that span chips, each "
                         "against one device")
    args = ap.parse_args()

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # the CPU reference runs in this process next to the chip
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    device = device_check(args.chips)

    from repro.common.compile_cache import enable_compile_cache

    _say("device", f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        import jax

        devices = jax.devices()[:4]
        phase_sharded_scorer(devices, NODES, SEED)
        phase_sharded_replay(devices)
        phase_sharded_karasu(devices)
    else:
        phase_serve(NODES, ROUNDS, SEED)
        phase_kernel(SEED)
        phase_replay()
        phase_karasu()
    _say("done", f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
