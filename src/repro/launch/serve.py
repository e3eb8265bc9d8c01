"""Serving driver: batched prefill + decode with slot-based batching.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 16 --max-new 32 --scale small

A fixed pool of batch slots serves a request queue continuous-batching
style: finished sequences release their slot, the next request prefills
into it (single-sequence prefill), and all occupied slots decode in
lockstep with one jit'd decode_step per token. The same serve_step is
what the decode_32k / long_500k dry-run cells lower onto the production
meshes.

``--fingerprint`` serves Perona fingerprint scoring instead: rounds of
benchmark executions stream through one shared
:class:`repro.fleet.FleetScoringService` — the watchdog submits
per-node requests, the service coalesces them into shape-bucketed
micro-batches and dispatches one sharded call per flush (the same
scoring path `--fleet` exercises), amortizing one compile across
rounds:

    PYTHONPATH=src python -m repro.launch.serve --fingerprint \
        --rounds 20

``--fleet`` runs the raw fleet service loop (no watchdog): per-node
requests are queued and flushed in micro-batches, and the run reports
requests/s, dispatch counts and the store-backed drift summary. Pair
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see the
request batch sharded across 8 virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --fleet \
        --nodes 16 --rounds 10

``--daemon`` runs the streaming ingestion daemon over the same
service: seeded per-node telemetry events (bursty arrivals) stream
through the bounded staging ring with deadline/row-bucket flushes, and
the run reports sustained req/s, p99 queue latency and the rolling
drift flags. Add ``--faults`` to route the stream through the seeded
fault injector (dropout, delays, duplicates, reordering, NaN/Inf
corruption, bursts, one genuinely degraded node) and watch the
backpressure/quarantine counters and the degradation flag:

    PYTHONPATH=src python -m repro.launch.serve --daemon --faults \
        --nodes 6 --rounds 12

``--daemon --modelplane`` additionally runs the model management
plane over the stream: the run bootstraps the trained parameters as
version 1, canaries + hot-promotes an identical candidate mid-stream
(zero-downtime swap at a flush boundary), then force-promotes a
NaN-poisoned candidate and lets the post-promote health watch roll it
back automatically — promote/rollback instants land on the exported
timeline. ``--registry PATH`` persists the version registry;
``--modelplane-cmd {status,list,promote,rollback}`` (with
``--registry``, plus ``--version N`` for promote) performs offline
registry operations and exits:

    PYTHONPATH=src python -m repro.launch.serve --daemon \
        --modelplane --faults --nodes 3 --rounds 6
    PYTHONPATH=src python -m repro.launch.serve \
        --modelplane-cmd list --registry /tmp/perona-registry

Every mode accepts ``--timeline PATH`` (export the run's span
recording as Chrome trace-event JSON — open it in
https://ui.perfetto.dev) and ``--metrics`` (periodic + final text
dump of the process metrics registry; ``--metrics-interval`` seconds
between dumps). ``--daemon`` exports the daemon's own virtual-clock
tracer; the other modes export the process-wide wall-clock tracer.
"""

from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.models.model_zoo import build_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    tokens: Optional[List[int]] = None


class SlotServer:
    """Slot-based continuous batching on top of prefill/decode_step."""

    def __init__(self, model, params, *, n_slots: int = 4,
                 max_len: int = 512):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = model.init_cache(n_slots, max_len)
        self.pos = np.zeros(n_slots, np.int64)
        self.remaining = np.zeros(n_slots, np.int64)
        self.live = np.zeros(n_slots, bool)
        self.request_of_slot: List[Optional[Request]] = [None] * n_slots
        self.last_token = np.zeros(n_slots, np.int64)
        self._decode = jax.jit(model.decode_step)

    def _prefill_slot(self, slot: int, request: Request):
        """Prefill one sequence into one slot via a batched prefill with
        only this slot's row active (slot-wise cache merge)."""
        S = len(request.prompt)
        toks = np.zeros((self.n_slots, S), np.int32)
        toks[slot] = request.prompt
        logits, new_cache = self.model.prefill(
            self.params, self.cache, tokens=jnp.asarray(toks))
        # merge only this slot's rows into the live cache
        self.cache = merge_cache_slot(self.cache, new_cache, slot)
        request.tokens = []
        nxt = int(np.asarray(jnp.argmax(logits[slot])))
        request.tokens.append(nxt)
        self.last_token[slot] = nxt
        self.pos[slot] = S
        self.remaining[slot] = request.max_new - 1
        self.live[slot] = True
        self.request_of_slot[slot] = request

    def step(self):
        toks = jnp.asarray(self.last_token[:, None].astype(np.int32))
        pos = jnp.asarray(self.pos.astype(np.int32))
        logits, self.cache = self._decode(self.params, toks, pos,
                                          self.cache)
        nxt = np.asarray(jnp.argmax(logits, -1))
        for s in range(self.n_slots):
            if not self.live[s]:
                continue
            req = self.request_of_slot[s]
            req.tokens.append(int(nxt[s]))
            self.last_token[s] = int(nxt[s])
            self.pos[s] += 1
            self.remaining[s] -= 1
            if self.remaining[s] <= 0 or self.pos[s] >= self.max_len - 1:
                self.live[s] = False
                self.request_of_slot[s] = None

    def serve(self, requests: List[Request]) -> dict:
        queue = list(requests)
        done: List[Request] = []
        steps = 0
        while queue or self.live.any():
            for s in range(self.n_slots):
                if not self.live[s] and queue:
                    self._prefill_slot(s, queue.pop(0))
            before = [self.request_of_slot[s] for s in range(self.n_slots)]
            self.step()
            steps += 1
            for s, req in enumerate(before):
                if req is not None and self.request_of_slot[s] is None:
                    done.append(req)
        return {"completed": done, "decode_steps": steps}


def merge_cache_slot(cache_old, cache_new, slot: int):
    """Copy only ``slot``'s rows from a freshly prefilled cache into the
    live cache. Batch axis is 0 for head/tail group caches and 1 for
    body caches (leading n_periods stacking axis)."""

    def merge_group(old_tree, new_tree, batch_axis):
        def one(o, n):
            if o.ndim <= batch_axis or o.shape[batch_axis] <= slot:
                return o  # sentinel / non-batched leaf
            sel = (slice(None),) * batch_axis + (slot,)
            return o.at[sel].set(n[sel])

        return jax.tree_util.tree_map(one, old_tree, new_tree)

    out = {}
    for group in cache_old:
        ax = 1 if group == "body" else 0
        out[group] = merge_group(cache_old[group], cache_new[group], ax)
    return out


class TrainedPerona(NamedTuple):
    """A seeded acquisition and the Perona model trained on it."""

    runner: object  # fingerprint.runner.SuiteRunner
    frame: object  # fingerprint.frame.BenchmarkFrame (the acquisition)
    pre: object  # core.preprocess.Preprocessor fitted on ``frame``
    model: object  # core.model.PeronaModel
    params: dict
    history: list  # per-epoch {"epoch", "train_loss"}


#: Nodes whose seeded acquisition trains the fleet loops' model. The
#: full-batch trainer's pairwise losses hold N x N matrices: 1024
#: nodes' 61,440 executions would need 21 GB of HBM, 256 nodes' 15,360
#: need 1.5 GB of a TPU v5e's 16 GB.
TRAIN_NODES = 256


def fleet_machines(nodes: int) -> dict:
    """The served fleet: ``nodes`` homogeneous e2-medium nodes."""
    return {f"fleet-{i}": "e2-medium" for i in range(nodes)}


def trained_perona(machines, runs_per_type: int,
                   seed: int) -> TrainedPerona:
    """Acquire + fit + train one Perona model for the serving loops
    (shared by --fingerprint, --fleet and --daemon)."""
    from repro.core.graph_data import build_graphs
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.core.trainer import train_perona
    from repro.fingerprint.runner import SuiteRunner

    runner = SuiteRunner(seed=seed)
    frame = runner.run_frame(machines, runs_per_type=runs_per_type,
                             stress_fraction=0.2)
    pre = Preprocessor().fit(frame)
    batch = build_graphs(frame, pre)
    cfg = PeronaConfig(feature_dim=pre.feature_dim,
                       edge_dim=batch.edge.shape[-1])
    model = PeronaModel(cfg)
    # 40 epochs left the anomaly head near its prior (every node
    # scored ~0.4, a degraded one included); 200 separate them
    res = train_perona(model, batch, epochs=200, seed=seed)
    return TrainedPerona(runner, frame, pre, model, res.params,
                         res.history)


def serve_fingerprints(rounds: int, runs_per_type: int = 2,
                       seed: int = 0) -> dict:
    """Fingerprint-scoring service loop: train a small Perona model,
    then stream watchdog rounds through one FleetScoringService (the
    watchdog and the fleet entrypoint share this scoring path)."""
    from repro.fleet import FleetScoringService
    from repro.runtime.watchdog import PeronaWatchdog

    machines = {f"serve-{i}": "e2-medium" for i in range(3)}
    runner, frame, pre, model, params, _ = trained_perona(
        machines, runs_per_type=40, seed=seed)

    service = FleetScoringService(model, params, pre,
                                  context_per_chain=40)
    wd = PeronaWatchdog(model, params, pre, service=service,
                        history_per_chain=40)
    wd.history = frame
    t0 = time.time()
    scored = 0
    for k in range(rounds):
        round_frame = runner.run_frame(machines,
                                       runs_per_type=runs_per_type,
                                       t_offset=(k + 1) * 86400.0)
        wd.observe(round_frame)
        scored += len(round_frame)
    dt = time.time() - t0
    return {"rounds": rounds, "scored": scored, "seconds": dt,
            "traces": service.trace_count,
            "stats": service.stats,
            "excluded": wd.excluded_nodes()}


def serve_fleet(nodes: int = 16, rounds: int = 10,
                runs_per_type: int = 1, seed: int = 0) -> dict:
    """Raw fleet-service loop: per-node requests micro-batched through
    the sharded scoring path, with store-backed drift analytics."""
    from repro.fleet import FleetScoringService, drift_report

    machines = fleet_machines(nodes)
    runner, frame, pre, model, params, _ = trained_perona(
        fleet_machines(min(nodes, TRAIN_NODES)), runs_per_type=10,
        seed=seed)

    service = FleetScoringService(model, params, pre,
                                  context_per_chain=16)
    service.seed_history(frame)
    t0 = time.time()
    for k in range(rounds):
        round_frame = runner.run_frame(machines,
                                       runs_per_type=runs_per_type,
                                       t_offset=(k + 1) * 86400.0)
        service.score_round(round_frame)
    dt = time.time() - t0
    report = drift_report(service.store)
    worst = max(report.values(), key=lambda d: d.anomaly_ewma,
                default=None)
    return {"rounds": rounds, "seconds": dt, "stats": service.stats,
            "drift_nodes": len(report),
            "worst_node": None if worst is None else
            (worst.node, round(worst.anomaly_ewma, 3))}


def serve_daemon(nodes: int = 6, rounds: int = 12,
                 runs_per_type: int = 1, seed: int = 0,
                 faults: bool = False, modelplane: bool = False,
                 registry_dir: Optional[str] = None) -> dict:
    """Streaming ingestion loop: telemetry events through the bounded
    staging ring of an :class:`repro.fleet.IngestionDaemon`, optionally
    perturbed by the seeded fault injector (``faults=True`` also marks
    one node genuinely degraded halfway through the run). With
    ``modelplane=True`` the run exercises the full model lifecycle on
    the live stream: canary + hot-promote of an identical candidate,
    then a forced promote of a NaN-poisoned candidate that the health
    watch rolls back automatically. The model trains on the first
    :data:`TRAIN_NODES` nodes' acquisition, which also seeds the
    store's history."""
    from repro.fleet import (FaultPlan, FleetScoringService,
                             IngestionDaemon, ModelPlane,
                             fleet_telemetry, inject_faults)

    machines = fleet_machines(nodes)
    trained = trained_perona(
        fleet_machines(min(nodes, TRAIN_NODES)),
        runs_per_type=10, seed=seed)
    _, frame, pre, model, params, _ = trained

    service = FleetScoringService(model, params, pre,
                                  context_per_chain=16)
    service.seed_history(frame)
    daemon = IngestionDaemon(service, capacity_rows=64 * nodes,
                             flush_interval=0.5,
                             min_flush_gap=0.05)
    plane = None
    if modelplane:
        if registry_dir is None:
            import tempfile
            registry_dir = tempfile.mkdtemp(prefix="perona-registry-")
        # generous health shift: only the NaN candidate below should
        # trip the watch, not the injected degraded node's drift
        plane = ModelPlane(service, registry_dir, daemon=daemon,
                           canary_flushes=1, watch_flushes=3,
                           min_health_shift=0.5)
        plane.bootstrap(params)
    degraded_node = f"fleet-{nodes - 1}"
    events = fleet_telemetry(
        machines, rounds=rounds, runs_per_type=runs_per_type,
        seed=seed + 1, interval=1.0, jitter=0.25,
        degraded={degraded_node: rounds // 2} if faults else None)
    fault_counts = None
    if faults:
        events, log = inject_faults(events, FaultPlan(
            seed=seed + 2, dropout=0.05, delay=0.2, duplicate=0.2,
            reorder=0.2, corrupt=0.15, burst=0.2, burst_window=3.0))
        fault_counts = log.counts()
    if plane is None:
        daemon.run(events)
    else:
        third = max(len(events) // 3, 1)
        daemon.run(events[:third], drain=False)
        # identical params: divergence-free canary -> zero-downtime
        # promote at a flush boundary mid-stream
        plane.submit_candidate(params, source="cli-demo")
        daemon.run(events[third:2 * third], drain=False)
        bad = jax.tree_util.tree_map(
            lambda x: np.asarray(x) * np.nan, params)
        vid_bad = plane.registry.save_version(bad,
                                              source="cli-demo-bad")
        plane.promote(vid_bad, force=True)
        daemon.run(events[2 * third:], drain=True)
    st = daemon.stats()
    return {"rounds": rounds, "stats": st,
            "faults": fault_counts,
            "degraded_node": degraded_node if faults else None,
            "flagged": daemon.flagged_nodes(),
            "daemon": daemon,
            "trained": trained,
            "modelplane": None if plane is None else plane.status(),
            "registry": registry_dir,
            "versions": (None if plane is None
                         else plane.registry.list_versions()),
            # the daemon's private virtual-clock tracer: --timeline
            # exports THIS recording in daemon mode, so flush spans
            # and ladder instants sit on the same clock as the
            # reported queue latencies
            "tracer": daemon.tracer}


def _start_metrics_dumper(interval: float) -> threading.Event:
    """Background thread printing the metrics registry every
    ``interval`` seconds until the returned event is set."""
    stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            text = obs.registry().render()
            if text:
                print(f"[metrics @ {time.strftime('%H:%M:%S')}]\n"
                      f"{text}", flush=True)

    threading.Thread(target=loop, name="perona-metrics",
                     daemon=True).start()
    return stop


def _export_timeline(path: str,
                     tracer: Optional[obs.Tracer] = None) -> None:
    obs.write_chrome_trace(path, tracer=tracer)
    summary = obs.validate_chrome_trace_file(path)
    print(f"[timeline] wrote {path}: {summary['events']} events, "
          f"{summary['spans']} spans on {summary['threads']} "
          "thread track(s) — load in https://ui.perfetto.dev")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--scale", choices=["full", "small"], default="small")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fingerprint", action="store_true",
                    help="serve Perona fingerprint scoring rounds")
    ap.add_argument("--fleet", action="store_true",
                    help="raw fleet service loop (micro-batched, "
                         "sharded scoring + drift report)")
    ap.add_argument("--daemon", action="store_true",
                    help="streaming ingestion daemon over the fleet "
                         "service (bounded staging, deadline/row "
                         "flushes, rolling drift)")
    ap.add_argument("--faults", action="store_true",
                    help="with --daemon: inject seeded stream faults "
                         "+ one genuinely degraded node")
    ap.add_argument("--modelplane", action="store_true",
                    help="with --daemon: run the model management "
                         "plane demo (canary -> hot promote -> NaN "
                         "candidate -> automatic rollback)")
    ap.add_argument("--registry", metavar="PATH", default=None,
                    help="model registry directory (persisted across "
                         "runs; default: a temp dir)")
    ap.add_argument("--modelplane-cmd", default=None,
                    choices=["status", "list", "promote", "rollback"],
                    help="offline registry operation (requires "
                         "--registry) and exit")
    ap.add_argument("--version", type=int, default=None,
                    help="version id for --modelplane-cmd promote")
    ap.add_argument("--nodes", type=int, default=16,
                    help="fleet size for --fleet")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--timeline", metavar="PATH", default=None,
                    help="export the run's span recording as Chrome "
                         "trace-event JSON (perfetto-loadable)")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the metrics registry periodically and "
                         "at exit")
    ap.add_argument("--metrics-interval", type=float, default=10.0,
                    help="seconds between --metrics dumps")
    args = ap.parse_args()
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()

    dumper = (_start_metrics_dumper(args.metrics_interval)
              if args.metrics else None)
    try:
        tracer, failure = _run(args)
    finally:
        if dumper is not None:
            dumper.set()
        if args.metrics:
            text = obs.registry().render()
            if text:
                print(f"[metrics final]\n{text}", flush=True)
    if args.timeline:
        _export_timeline(args.timeline, tracer=tracer)
    if failure:
        raise SystemExit(failure)


def _modelplane_cmd(args) -> None:
    """Offline registry operations: inspect or re-point the version
    registry without a live service (a daemon started later against
    the same ``--registry`` loads the incumbent this selects)."""
    from repro.fleet import ModelRegistry

    if args.registry is None:
        raise SystemExit("--modelplane-cmd requires --registry PATH")
    reg = ModelRegistry(args.registry)
    cmd = args.modelplane_cmd
    if cmd == "status":
        print(f"[modelplane] incumbent=v{reg.incumbent} "
              f"previous=v{reg.previous} "
              f"versions={len(reg.list_versions())}")
    elif cmd == "list":
        for e in reg.list_versions():
            v = e["verdict"]
            line = (f"  v{e['version']:<3} {e['status']:<12} "
                    f"source={e['source']}")
            if e["tags"]:
                line += f" tags={','.join(e['tags'])}"
            if v is not None:
                line += (" canary="
                         + ("pass" if v["passed"] else
                            "fail:" + ",".join(v["failed_checks"])))
            print(line)
    elif cmd == "promote":
        if args.version is None:
            raise SystemExit("promote requires --version N")
        reg.set_incumbent(args.version)
        print(f"[modelplane] incumbent=v{reg.incumbent} "
              f"(previous=v{reg.previous})")
    elif cmd == "rollback":
        prev = reg.previous
        if prev is None:
            raise SystemExit("no previous version to roll back to")
        cur = reg.incumbent
        reg.set_incumbent(prev)
        if cur is not None:
            reg.set_status(cur, "rolled_back")
        print(f"[modelplane] rolled back v{cur} -> incumbent "
              f"v{reg.incumbent}")


def _run(args) -> Tuple[Optional[obs.Tracer], Optional[str]]:
    """Dispatch one serving mode; returns the tracer whose recording
    ``--timeline`` should export (None -> the process-wide tracer) and
    the failure the process must exit with (None on success)."""
    if args.modelplane_cmd:
        _modelplane_cmd(args)
        return None, None

    if args.fingerprint:
        out = serve_fingerprints(args.rounds, seed=args.seed)
        print(f"[serve-fp] {out['rounds']} rounds, {out['scored']} "
              f"executions, {out['seconds']:.2f}s "
              f"({out['scored'] / max(out['seconds'], 1e-9):.0f} exec/s), "
              f"{out['traces']} compiles, excluded={out['excluded']}")
        return None, None

    if args.daemon:
        out = serve_daemon(args.nodes, args.rounds, seed=args.seed,
                           faults=args.faults,
                           modelplane=args.modelplane,
                           registry_dir=args.registry)
        st = out["stats"]
        svc = st["service"]
        req_s = st["events_seen"] / max(st["run_wall_s"], 1e-9)
        print(f"[serve-daemon] {out['rounds']} rounds, "
              f"{st['events_seen']} events ({st['rows_staged_total']} "
              f"rows), {req_s:.1f} sustained req/s, "
              f"p99 queue latency {st['latency_p99']:.3f}s, "
              f"peak staging {st['peak_staged_rows']}/"
              f"{st['capacity_rows']} rows")
        print(f"[serve-daemon] flushes: {st['deadline_flushes']} "
              f"deadline / {st['row_trigger_flushes']} row-trigger / "
              f"{st['forced_flushes']} forced / "
              f"{st['drain_flushes']} drain; backpressure: "
              f"{st['shed_rows']} shed rows, "
              f"{st['degraded_flushes']} degraded flushes "
              f"({st['degrade_unscored_rows']} sampled-out rows); "
              f"dedup dropped {st['duplicates_dropped']}; "
              f"quarantined {svc['quarantined_rows']} rows; "
              f"{st['flush_failures']} flush failures, "
              f"{st['scorer_retries']} scorer retries")
        if out["faults"] is not None:
            print(f"[serve-daemon] injected faults: {out['faults']}; "
                  f"degraded node {out['degraded_node']} -> "
                  f"flagged={out['flagged']}")
        if out["modelplane"] is not None:
            mp = out["modelplane"]
            print(f"[modelplane] registry={out['registry']} "
                  f"incumbent=v{mp['incumbent']} "
                  f"phase={mp['phase']}; "
                  f"promotions={mp['promotions']} "
                  f"rollbacks={mp['rollbacks']} "
                  f"canary={mp['canary_pass']}/"
                  f"{mp['canary_pass'] + mp['canary_fail']} passed, "
                  f"{mp['shadow_flushes']} shadow flushes, "
                  f"{mp['repaired_rows']} rows repaired")
            for e in out["versions"]:
                print(f"[modelplane]   v{e['version']} "
                      f"{e['status']} ({e['source']})")
        failure = None
        if st["flush_failures"]:
            failure = (f"[serve-daemon] FAILED: {st['flush_failures']} "
                       "flush(es) lost their scores")
        return out["tracer"], failure

    if args.fleet:
        out = serve_fleet(args.nodes, args.rounds, seed=args.seed)
        s = out["stats"]
        print(f"[serve-fleet] {out['rounds']} rounds, "
              f"{s['requests_served']} requests, {s['rows_scored']} "
              f"rows, {s['dispatches']} dispatches on {s['devices']} "
              f"device(s), {s['traces']} compiles, "
              f"{s['requests_per_s']:.0f} req/s; "
              f"drift tracked for {out['drift_nodes']} nodes, "
              f"worst={out['worst_node']}")
        return None, None

    cfg = get_config(args.arch)
    if args.scale == "small":
        cfg = cfg.scaled_down(max_seq=args.max_len)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    rng = np.random.default_rng(args.seed)
    requests = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    rng.integers(4, 17)).astype(np.int32),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    server = SlotServer(model, params, n_slots=args.slots,
                        max_len=args.max_len)
    t0 = time.time()
    with obs.span("slots.serve", args={"requests": len(requests),
                                       "slots": args.slots}):
        out = server.serve(requests)
    dt = time.time() - t0
    n_tokens = sum(len(r.tokens) for r in out["completed"])
    print(f"[serve] {len(out['completed'])} requests, {n_tokens} tokens, "
          f"{out['decode_steps']} decode steps, {dt:.1f}s "
          f"({n_tokens/max(dt,1e-9):.1f} tok/s)")
    return None, None


if __name__ == "__main__":
    main()
