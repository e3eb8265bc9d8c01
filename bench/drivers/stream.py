"""Streaming fleet driver: telemetry events through the program's
served path, ``IngestionDaemon.serve()`` -> ``FleetScoringService`` ->
``FingerprintStore`` -> ``ShardedScorer``, on the wall clock.

Set-up makes the fleet's telemetry and the weights from the seed,
seeds the store with every node's history, and compiles (or loads) the
scoring program of every request-stack shape the traffic can meet.
The window offers each event when its schedule says, stamps when the
flush that took it started and when that flush had attached its rows'
scores, and lasts until every event due in it is scored (at most
``GRACE_S`` past its end).

The check, after the window, with the program's state freed: every
event due in the window was admitted and each of its rows scored once,
finite, at the row the store holds for it; and for a sample of events
drawn from the seed, the stored anomaly scores against the plain
reference (``bench/reference/perona_fleet.py``) on the same telemetry
and weights.
"""

from __future__ import annotations

import time

import numpy as np

#: How long past the window's end the run waits for due events.
GRACE_S = 60.0


def warm_stacks(service, params, tel, pre, cfg: dict) -> int:
    """Dispatch a zero stack of every (requests, rows) shape the window
    can meet: requests 1, 2, 4, ... up to the fleet; rows the buckets
    of one node's context plus 1 to ``max_rounds_per_flush`` rounds."""
    from repro.common.bucketing import next_pow2
    from repro.core.graph_data import chain_structure
    from repro.serving.engine import (MIN_BUCKET, assemble_inputs,
                                      prepare_features)

    frame = tel.event_frame(0, 0)
    feats = prepare_features(pre, frame)
    gs = chain_structure(frame.type_code.astype(np.int64), frame.t)
    n_types, ctx = len(frame.t), cfg["service"]["context_per_chain"]
    buckets = sorted({next_pow2(n_types * (ctx + m), MIN_BUCKET)
                      for m in range(1, cfg["max_rounds_per_flush"] + 1)})
    shapes = 0
    for bucket in buckets:
        one = assemble_inputs(feats, gs.nbr, gs.dt, gs.t_src, bucket)
        r = 1
        while True:
            r_pad = service.scorer.pad_requests(r)
            service.scorer.score_stack(
                params, {k: np.repeat(v[None], r_pad, 0)
                         for k, v in one.items()})
            shapes += 1
            if r >= tel.n_nodes:
                break
            r *= 2
    return shapes


def run(run) -> None:
    import jax

    from bench import flops, peaks, telemetry, traffic, weights
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.fleet import FleetScoringService, IngestionDaemon
    from repro.fleet.faults import TelemetryEvent

    cfg, mcfg = run.config, run.config["model"]
    tel = telemetry.FleetTelemetry(cfg, run.seed)
    sched = traffic.open_schedule(run.mix, run.seed, run.seconds,
                                  tel.n_nodes)
    pre = Preprocessor().fit(tel.fit_frame)
    edge_dim = len(pre.edge_names) + 4
    model = PeronaModel(PeronaConfig(
        feature_dim=pre.feature_dim, edge_dim=edge_dim,
        n_types=mcfg["n_types"], code_dim=mcfg["code_dim"],
        hidden=mcfg["hidden"], tag_hops=mcfg["tag_hops"],
        heads=mcfg["heads"]))
    params = weights.make_params(run.seed, pre.feature_dim, edge_dim, mcfg)
    service = FleetScoringService(
        model, params, pre,
        context_per_chain=cfg["service"]["context_per_chain"])
    service.seed_history(tel.history)
    dcfg = cfg["daemon"]
    daemon = IngestionDaemon(service, capacity_rows=dcfg["capacity_rows"],
                             flush_interval=dcfg["flush_interval"],
                             min_flush_gap=dcfg["min_flush_gap"])
    run.counts["warm_shapes"] = warm_stacks(service, params, tel, pre, cfg)

    n = len(sched)
    due = np.zeros(n)
    taken = np.full(n, np.nan)  # start of the flush that took the event
    scored = np.full(n, np.nan)  # end of that flush: scores attached
    row0 = np.full(n, -1, np.int64)  # store index of its first row
    uid_of = {}  # id(frame) -> event, while the event is in flight
    state = {"next": 0, "scored": 0}
    run.counts.update(rows_real=0, dispatches=0, flushes=0)

    flush = service.flush
    score_stack = service.scorer.score_stack

    def timed_flush():
        pending = list(service._pending)
        start = len(service.store)
        with run.span("flush"):
            t0 = time.perf_counter()
            out = flush()
            t1 = time.perf_counter()
        run.counts["flushes"] += 1
        for f in pending:
            e = uid_of.pop(id(f), None)
            if e is not None:
                taken[e], scored[e], row0[e] = t0, t1, start
                state["scored"] += 1
            start += len(f.t)
        return out

    def timed_score_stack(p, stack):
        run.counts["rows_real"] += int(stack["present"].any(-1).sum())
        run.counts["dispatches"] += 1
        with run.span("score_stack"):
            return score_stack(p, stack)

    def poll(_now):
        now = time.perf_counter()
        i, out = state["next"], []
        while i < n and due[i] <= now:
            node = int(sched.node[i])
            frame = tel.event_frame(node, int(sched.k[i]))
            uid_of[id(frame)] = i
            out.append(TelemetryEvent(uid=i, node=f"fleet-{node}",
                                      arrival=float(sched.offset[i]),
                                      frame=frame))
            i += 1
        state["next"] = i
        return out

    service.flush = timed_flush
    service.scorer.score_stack = timed_score_stack
    daemon.attach_source(poll)
    with run.window() as t0:
        due[:] = t0 + sched.offset
        daemon.serve(poll_interval=dcfg["poll_interval"])
        end = t0 + run.seconds + GRACE_S
        while state["scored"] < n and time.perf_counter() < end:
            time.sleep(0.05)
        daemon.close(drain=True)
        run.window_s = time.perf_counter() - t0

    stats = daemon.stats()
    store = service.store
    anomaly = store.anomaly.copy()
    codes = (np.full((len(anomaly), mcfg["code_dim"]), np.nan)
             if store.codes is None else store.codes.copy())
    store_t = store.frame.t.copy()
    rows_scored = service.stats["rows_scored"]
    del daemon, service, store, flush, score_stack

    ok = ~np.isnan(scored)
    sizes = np.asarray([len(tel.event_frame(int(sched.node[i]),
                                            int(sched.k[i])).t)
                        for i in range(n)])
    for i in np.nonzero(ok)[0]:
        rows = slice(row0[i], row0[i] + sizes[i])
        frame_t = tel.event_frame(int(sched.node[i]), int(sched.k[i])).t
        if (not np.isfinite(anomaly[rows]).all()
                or not np.array_equal(store_t[rows], frame_t)):
            ok[i] = False
    run.attempted, run.failed = n, int(n - ok.sum())
    latency = np.where(ok, scored - due, np.inf)
    run.values.update(latency_s=latency, staging_wait_s=taken - due,
                      due_s=due - t0, scored_s=scored - t0,
                      flops=flops.perona_flops(run.counts["rows_real"],
                                               pre.feature_dim, edge_dim,
                                               mcfg),
                      peak_flops=peaks.peaks(
                          jax.devices()[0].device_kind)["bf16_flops"]
                      if jax.devices()[0].platform == "tpu" else None)
    run.check("unscored_events", n - ok.sum(), 0)
    run.check("rows_scored_minus_admitted",
              abs(rows_scored - stats["rows_staged_total"]), 0)

    sample = _sample(run.seed, np.nonzero(ok)[0],
                     cfg["check"]["events"])
    gaps = reference_gaps(run, tel, params, sched, sample, row0,
                          anomaly, codes)
    # No limit until one is set from chip readings of the program and the
    # control; without one the comparison fails, so no run is correct.
    # The control (``bench/control.py``) reads through the same check.
    limit = cfg["check"]["score_gap"]
    key = "control_score_gap" if cfg["check"].get("control") else "score_gap"
    run.check("score_gap", gaps[key],
              float("nan") if limit is None else limit)
    run.values.update(gaps)


def _sample(seed: int, pool: np.ndarray, size: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 5])
    if len(pool) <= size:
        return pool
    return np.sort(rng.choice(pool, size, replace=False))


def reference_graphs(tel, feats, model: dict, sched, events):
    """The reference's graph of each event's new rows and the chain
    type of each graph's new rows."""
    from bench.reference import perona_fleet as ref

    depth = model["predecessors"] * model["tag_hops"]
    hist = ref.rows(feats, tel.history)
    node_of_code = np.asarray([int(m.rsplit("-", 1)[1])
                               for m in tel.history.machines])
    hist_node = node_of_code[tel.history.machine_code]
    by_chain = {}
    order = np.lexsort((hist["t"], hist["type"], hist_node))
    for j in order:
        by_chain.setdefault((hist_node[j], hist["type"][j]), []).append(j)
    cache = {}

    def round_rows(node, k):
        key = (node, k % tel.pool_rounds)
        if key not in cache:
            cache[key] = ref.rows(feats, tel.pool[key[1]][node])
        r = dict(cache[key])
        r["t"] = np.asarray(tel.event_frame(node, k).t)
        return r

    graphs, new_types = [], []
    for e in events:
        node, k = int(sched.node[e]), int(sched.k[e])
        rounds = [round_rows(node, j) for j in range(max(0, k - depth),
                                                     k + 1)]
        chains = []
        for b in range(len(feats.types)):
            idx = by_chain[(node, b)][-(depth + 1):]
            parts = [{key: v[idx] for key, v in hist.items()}]
            for r in rounds:
                sel = r["type"] == b
                parts.append({key: v[sel] for key, v in r.items()})
            chains.append({key: np.concatenate([p[key] for p in parts])
                           for key in parts[0]})
        cat, starts, new = ref.chain_windows(chains, depth)
        nbr, mask, dt, t_src = ref.graph(cat["t"], starts,
                                         model["predecessors"])
        graphs.append({"raw": cat["raw"].astype(np.float32),
                       "present": cat["present"], "type": cat["type"],
                       "edge_raw": cat["edge_raw"].astype(np.float32),
                       "nbr": nbr, "mask": mask,
                       "dt": dt.astype(np.float32),
                       "t_src": t_src.astype(np.float32)})
        new_types.append(new)
    stacked = {k: np.stack([g[k] for g in graphs]) for k in graphs[0]}
    return stacked, np.asarray(new_types)


def reference_gaps(run, tel, params, sched, sample, row0, anomaly, codes
                   ) -> dict:
    """Widest gaps between the stored scores of the sampled events and
    the reference's; with ``check.control`` also the gaps of the
    control (the reference in bfloat16) to the reference."""
    from bench.reference import perona_fleet as ref

    cfg, mcfg = run.config, run.config["model"]
    if len(sample) == 0:
        return {"score_gap": float("inf"), "code_gap": float("inf")}
    feats = ref.Features.fit(tel.fit_frame)
    graphs, new = reference_graphs(tel, feats, mcfg, sched, sample)
    slot, pos, store_row = [], [], []
    for s, e in enumerate(sample):
        frame = tel.event_frame(int(sched.node[e]), int(sched.k[e]))
        for j, t in enumerate(feats.type_ids(frame)):
            slot.append(s)
            pos.append(new[s][t])
            store_row.append(row0[e] + j)

    def gaps(prefix, p, c, want):
        dp = np.abs(p - want["anomaly_prob"][slot, pos])
        dc = np.abs(c - want["codes"][slot, pos])
        return {f"{prefix}score_gap": float(dp.max()),
                f"{prefix}code_gap": float(dc.max()),
                f"{prefix}score_gap_mean": float(dp.mean()),
                f"{prefix}code_gap_mean": float(dc.mean())}

    want = ref.score(params, graphs, feats, mcfg)
    out = gaps("", anomaly[store_row], codes[store_row], want)
    out["checked_rows"] = len(slot)
    if cfg["check"].get("control"):
        low = ref.score(params, graphs, feats, mcfg, dtype="bfloat16",
                        precision=None)
        out.update(gaps("control_", low["anomaly_prob"][slot, pos],
                        low["codes"][slot, pos], want))
    return out
