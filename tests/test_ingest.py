"""Streaming ingestion daemon + fault injection: no-fault bit parity
with the closed-loop service, exact dedup/quarantine accounting under
injected faults, the backpressure ladder (block -> shed -> degrade),
rolling-drift parity, crash-safe checkpointing, and the
watchdog-under-faults e2e (injected degradation is flagged, clean
nodes stay unflagged)."""

import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.graph_data import build_graphs
from repro.core.model import PeronaConfig, PeronaModel
from repro.core.preprocess import Preprocessor
from repro.fingerprint.runner import SuiteRunner
from repro.fleet import (FaultPlan, FleetScoringService, IngestionDaemon,
                         TelemetryEvent, drift_report, fleet_telemetry,
                         inject_faults, load_staging)

DAY = 86400.0
MACHINES = {"in-0": "e2-medium", "in-1": "n2-standard-4",
            "in-2": "e2-medium"}


@pytest.fixture(scope="module")
def setup():
    runner = SuiteRunner(seed=5)
    frame = runner.run_frame(MACHINES, runs_per_type=10,
                             stress_fraction=0.2)
    pre = Preprocessor().fit(frame)
    batch = build_graphs(frame, pre)
    cfg = PeronaConfig(feature_dim=pre.feature_dim,
                       edge_dim=batch.edge.shape[-1])
    model = PeronaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))  # untrained: scoring only
    return frame, pre, model, params


def _service(setup):
    frame, pre, model, params = setup
    svc = FleetScoringService(model, params, pre, sharded=False)
    svc.seed_history(frame)
    return svc


def _store_is_finite(store):
    f = store.frame
    return bool(
        np.isfinite(np.where(f.metrics_present, f.metrics, 0.0)).all()
        and np.isfinite(np.where(f.node_metrics_present,
                                 f.node_metrics, 0.0)).all()
        and np.isfinite(f.t).all())


# ------------------------------------------------------- no-fault parity

def test_daemon_no_faults_bit_identical_to_closed_loop(setup):
    """A fault-free daemon (one deadline flush per telemetry round)
    reproduces the closed-loop ``score_round`` scores bit for bit, and
    its incremental RollingDrift state equals the batch
    ``drift_report`` over the store exactly."""
    frame, pre, model, params = setup
    rounds = 3

    ref = _service(setup)
    src = SuiteRunner(seed=7)
    ref_results = {}
    for k in range(rounds):
        rnd = src.run_frame(MACHINES, runs_per_type=1,
                            t_offset=(k + 1) * DAY)
        for n, r in ref.score_round(rnd).items():
            ref_results.setdefault(n, []).append(r)

    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=512, flush_interval=0.5,
                             flush_rows=1 << 30, service_time_scale=0.0)
    events = fleet_telemetry(MACHINES, rounds=rounds, runs_per_type=1,
                             seed=7, interval=1.0, jitter=0.01)
    res = daemon.run(events)
    st = daemon.stats()
    assert st["deadline_flushes"] == rounds - 1
    assert st["drain_flushes"] == 1
    assert sorted(res) == sorted(MACHINES)
    for n in MACHINES:
        assert len(res[n]) == rounds
        for got, want in zip(res[n], ref_results[n]):
            np.testing.assert_array_equal(got.anomaly_prob,
                                          want.anomaly_prob)
            np.testing.assert_array_equal(got.codes, want.codes)
            np.testing.assert_array_equal(got.type_logits,
                                          want.type_logits)

    rolling = daemon.drift.report()
    batch = drift_report(svc.store, alpha=daemon.drift.alpha)
    assert sorted(rolling) == sorted(batch)
    for n in batch:
        assert rolling[n].n_scored == batch[n].n_scored
        assert rolling[n].anomaly_ewma == batch[n].anomaly_ewma
        assert rolling[n].anomaly_mean == batch[n].anomaly_mean
        assert rolling[n].aspect_ewma == batch[n].aspect_ewma
        assert rolling[n].aspect_mean == batch[n].aspect_mean
        assert rolling[n].last_t == batch[n].last_t


# -------------------------------------------------- faults + accounting

def test_daemon_dedup_and_quarantine_exact_under_faults(setup):
    """Against the injector's ground-truth FaultLog: every duplicated
    uid is dropped exactly once, every corrupted row is quarantined
    (none reaches the store or the scorer), and surviving rows are
    conserved: store rows = history + deduped stream - corrupted."""
    frame, *_ = setup
    events = fleet_telemetry(MACHINES, rounds=6, runs_per_type=2,
                             seed=11, interval=1.0, jitter=0.2)
    faulty, log = inject_faults(events, FaultPlan(
        seed=3, dropout=0.1, delay=0.3, duplicate=0.3, reorder=0.2,
        corrupt=0.3, burst=0.25, burst_window=2.0,
        stalls=(("in-1", 1.0, 4.0),)))
    assert log.duplicated and log.corrupted and log.dropped

    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=256,
                             flush_interval=0.5, flush_rows=64,
                             service_time_scale=0.0)
    daemon.run(faulty)
    st = daemon.stats()
    assert st["duplicates_dropped"] == len(log.duplicated)
    assert svc.stats["quarantined_nonfinite"] == log.corrupted_rows
    assert svc.stats["quarantined_unknown_type"] == 0
    assert _store_is_finite(svc.store)
    assert st["peak_staged_rows"] <= 256
    # conservation over the deduped stream (duplicates carry the same
    # uid; every surviving row is either quarantined or stored)
    deduped_rows = sum(len(e.frame) for u, e in
                      {e.uid: e for e in faulty}.items())
    assert len(svc.store) == (len(frame) + deduped_rows
                              - log.corrupted_rows - st["shed_rows"])
    # quarantined rows were never scored: all stored rows that carry a
    # score are finite, and the quarantine holds the poisoned ones
    q_rows = sum(len(f) for f in svc.quarantine)
    assert q_rows == log.corrupted_rows


def test_injector_is_deterministic():
    events = fleet_telemetry(MACHINES, rounds=4, seed=19, jitter=0.3)
    plan = FaultPlan(seed=8, dropout=0.2, delay=0.4, duplicate=0.3,
                     reorder=0.3, corrupt=0.4, burst=0.3)
    out1, log1 = inject_faults(events, plan)
    out2, log2 = inject_faults(list(events), plan)
    assert log1.counts() == log2.counts()
    assert [e.uid for e in out1] == [e.uid for e in out2]
    assert [e.arrival for e in out1] == [e.arrival for e in out2]
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a.frame.metrics, b.frame.metrics)


# ---------------------------------------------------- backpressure ladder

def test_backpressure_block_step_forces_flush(setup):
    """Ladder step 1: with an always-available consumer
    (min_flush_gap=0) an arrival that would overflow the ring forces a
    flush instead of shedding — nothing is lost."""
    events = fleet_telemetry(MACHINES, rounds=4, runs_per_type=2,
                             seed=13, interval=0.05, jitter=0.01)
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=48, flush_interval=10.0,
                             flush_rows=1 << 30, min_flush_gap=0.0,
                             service_time_scale=0.0)
    daemon.run(events)
    st = daemon.stats()
    assert st["forced_flushes"] > 0
    assert st["shed_rows"] == 0
    assert st["peak_staged_rows"] <= 48
    rows_in = sum(len(e.frame) for e in events)
    assert svc.stats["store_rows"] == len(setup[0]) + rows_in


def test_backpressure_shed_and_degrade_under_storm(setup):
    """Ladder steps 2+3: a gated consumer (min_flush_gap) under a
    storm sheds oldest-per-chain rows, then enters degraded sampled
    scoring; ring stays bounded and every row is accounted for."""
    frame, *_ = setup
    events = fleet_telemetry(MACHINES, rounds=8, runs_per_type=2,
                             seed=13, interval=0.05, jitter=0.01)
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=48, flush_interval=10.0,
                             flush_rows=1 << 30, min_flush_gap=5.0,
                             degrade_after=2, recover_after=1,
                             degrade_sample_per_chain=1,
                             service_time_scale=0.0)
    daemon.run(events)
    st = daemon.stats()
    assert st["peak_staged_rows"] <= 48
    assert st["shed_rows"] > 0
    assert st["degrade_entries"] > 0 and st["degraded_flushes"] > 0
    assert st["degrade_unscored_rows"] > 0
    rows_in = sum(len(e.frame) for e in events)
    # shed rows are the only loss; degraded-mode unsampled rows are
    # stored (unscored), sampled rows are stored + scored
    assert len(svc.store) == len(frame) + rows_in - st["shed_rows"]
    assert svc.stats["rows_scored"] < rows_in


def test_shed_keeps_newest_rows_per_chain(setup):
    """Shedding drops the *oldest* rows of each (node x type) chain:
    after a storm the newest telemetry timestamps survive in staging
    or the store, the dropped ones are the early ones."""
    frame, *_ = setup
    events = fleet_telemetry(MACHINES, rounds=6, runs_per_type=2,
                             seed=17, interval=0.05)
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=40, flush_interval=1e9,
                             flush_rows=1 << 30, min_flush_gap=1e9,
                             service_time_scale=0.0)
    daemon.run(events, drain=False)
    st = daemon.stats()
    assert st["shed_rows"] > 0 and st["staged_rows"] <= 40
    staged_t = np.concatenate(
        [s.frame.t for s in daemon._staged])
    # the newest round's timestamps all survived the shedding
    newest_round_t0 = 6 * DAY  # t0=DAY + (rounds-1)*DAY
    n_newest = sum(len(e.frame) for e in events
                   if e.frame.t.min() >= newest_round_t0)
    assert (staged_t >= newest_round_t0).sum() == n_newest


def test_degraded_mode_scores_newest_sample_per_chain(setup):
    """Degraded flushes score exactly the newest K rows per chain;
    the rest land in the store unscored (NaN anomaly)."""
    frame, *_ = setup
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=512,
                             flush_interval=1e9, flush_rows=1 << 30,
                             degrade_sample_per_chain=1,
                             service_time_scale=0.0)
    daemon.degraded = True  # force ladder step 3
    events = fleet_telemetry(MACHINES, rounds=1, runs_per_type=3,
                             seed=23)
    for ev in events:
        daemon.offer(ev, now=ev.arrival)
    res = daemon.flush()
    st = daemon.stats()
    assert st["degraded_flushes"] == 1
    n_chains = len(MACHINES) * len(frame.benchmark_types)
    assert svc.stats["rows_scored"] == n_chains
    assert st["degrade_unscored_rows"] == n_chains * 2
    for n, r in res.items():
        assert len(r.anomaly_prob) == len(frame.benchmark_types)


# ------------------------------------------------------- flush triggers

def test_row_trigger_fires_on_pow2_bucket(setup):
    """Row-threshold flushes fire the moment staging reaches
    ``flush_rows`` (a pow2 bucket), before any deadline."""
    events = fleet_telemetry(MACHINES, rounds=4, runs_per_type=2,
                             seed=29, interval=1.0)
    per_round = sum(len(e.frame) for e in events) // 4
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=1024,
                             flush_interval=1e9,
                             flush_rows=per_round,
                             service_time_scale=0.0)
    daemon.run(events)
    st = daemon.stats()
    assert st["row_trigger_flushes"] == 4
    assert st["deadline_flushes"] == 0
    # default flush_rows is a pow2 <= capacity
    d2 = IngestionDaemon(_service(setup), capacity_rows=100)
    assert d2.flush_rows == 64


def test_deadline_bounds_staging_latency(setup):
    """No staged row waits longer than flush_interval (+ service
    time): sparse arrivals still flush on the deadline."""
    events = fleet_telemetry(MACHINES, rounds=3, runs_per_type=1,
                             seed=31, interval=10.0)
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=1024,
                             flush_interval=2.0, flush_rows=1 << 30,
                             service_time_scale=0.0)
    daemon.run(events, drain=False)
    daemon.advance(events[-1].arrival + 2.0 + 1e-6)
    st = daemon.stats()
    assert st["deadline_flushes"] == 3
    assert st["staged_rows"] == 0
    lat = daemon._latency.summary()  # shared obs histogram (exact max)
    assert lat["count"] > 0 and lat["max"] <= 2.0 + 1e-9


# ------------------------------------------------- crash-safe shutdown

def test_checkpoint_restore_resumes_identically(setup, tmp_path):
    """close(drain=False, checkpoint=...) + load_staging on a fresh
    daemon produces the same scores as a daemon that drained directly
    — accepted telemetry survives a restart exactly."""
    events = fleet_telemetry(MACHINES, rounds=2, runs_per_type=1,
                             seed=37, interval=1.0, jitter=0.05)

    svc_a = _service(setup)
    d_a = IngestionDaemon(svc_a, capacity_rows=512, flush_interval=1e9,
                          flush_rows=1 << 30, service_time_scale=0.0)
    res_a = d_a.run(events)  # drains on exit

    svc_b = _service(setup)
    d_b = IngestionDaemon(svc_b, capacity_rows=512, flush_interval=1e9,
                          flush_rows=1 << 30, service_time_scale=0.0)
    d_b.run(events, drain=False)  # crash with rows staged
    path = os.path.join(tmp_path, "staging.npz")
    d_b.close(drain=False, checkpoint=path)
    assert d_b.stats()["staged_rows"] == 0

    restored = load_staging(path)
    assert sorted(e.uid for e in restored) == \
        sorted(e.uid for e in events)
    svc_c = _service(setup)
    d_c = IngestionDaemon(svc_c, capacity_rows=512, flush_interval=1e9,
                          flush_rows=1 << 30, service_time_scale=0.0)
    res_c = d_c.run(restored)
    assert sorted(res_a) == sorted(res_c)
    for n in res_a:
        for ra, rc in zip(res_a[n], res_c[n]):
            np.testing.assert_array_equal(ra.anomaly_prob,
                                          rc.anomaly_prob)
            np.testing.assert_array_equal(ra.codes, rc.codes)


def test_close_drains_staged_rows(setup):
    frame, *_ = setup
    events = fleet_telemetry(MACHINES, rounds=1, runs_per_type=1,
                             seed=41)
    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=512, flush_interval=1e9,
                             flush_rows=1 << 30)
    for ev in events:
        daemon.offer(ev, now=ev.arrival)
    res = daemon.close(drain=True)
    assert sorted(res) == sorted(MACHINES)
    assert svc.stats["store_rows"] == len(frame) + sum(
        len(e.frame) for e in events)
    assert daemon.close() == {}  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        daemon.offer(events[0], now=0.0)


# ------------------------------------------------------ scorer failures

def test_transient_scorer_failure_retried_bit_identical(setup):
    """A scorer dispatch that fails once is retried (bounded, seeded
    backoff) and the run completes bit-identical to a clean one — the
    stacked host buffers survive the failed attempt."""
    ref = _service(setup)
    events = fleet_telemetry(MACHINES, rounds=3, runs_per_type=1,
                             seed=61, interval=1.0, jitter=0.01)
    ref_daemon = IngestionDaemon(ref, capacity_rows=512,
                                 flush_interval=0.5,
                                 flush_rows=1 << 30,
                                 service_time_scale=0.0)
    ref_res = ref_daemon.run(events)

    svc = _service(setup)
    svc.retry_backoff_s = 0.0  # don't sleep in tests
    real = svc.scorer.score_stack
    calls = {"n": 0}

    def flaky(params, stack):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient device loss")
        return real(params, stack)

    svc.scorer.score_stack = flaky
    daemon = IngestionDaemon(svc, capacity_rows=512,
                             flush_interval=0.5, flush_rows=1 << 30,
                             service_time_scale=0.0)
    res = daemon.run(events)
    st = daemon.stats()
    assert svc.stats["scorer_retries"] == 1
    assert st["scorer_retries"] == 1
    assert st["flush_failures"] == 0
    assert sorted(res) == sorted(ref_res)
    for n in ref_res:
        for got, want in zip(res[n], ref_res[n]):
            np.testing.assert_array_equal(got.anomaly_prob,
                                          want.anomaly_prob)
            np.testing.assert_array_equal(got.codes, want.codes)
    np.testing.assert_array_equal(svc.store.anomaly,
                                  ref.store.anomaly)


def test_terminal_scorer_failure_degrades_not_dies(setup):
    """When retries are exhausted the flush loses its scores, not the
    pipeline: the daemon keeps consuming the stream, rows stay durable
    (unscored) in the store, and the failure is counted + traced."""
    frame, *_ = setup
    svc = _service(setup)
    svc.dispatch_retries = 1
    svc.retry_backoff_s = 0.0

    def dead(params, stack):
        raise RuntimeError("device gone")

    svc.scorer.score_stack = dead
    events = fleet_telemetry(MACHINES, rounds=2, runs_per_type=1,
                             seed=62, interval=1.0, jitter=0.01)
    daemon = IngestionDaemon(svc, capacity_rows=512,
                             flush_interval=0.5, flush_rows=1 << 30,
                             service_time_scale=0.0)
    res = daemon.run(events)  # must not raise
    st = daemon.stats()
    assert res == {}
    assert st["flush_failures"] >= 1
    # one retry per failed flush: the first bucket's dispatch burns
    # its single retry, then the raise aborts the flush
    assert svc.stats["scorer_retries"] == st["flush_failures"]
    # every streamed row landed in the store, unscored
    assert len(svc.store) == len(frame) + sum(
        len(e.frame) for e in events)
    assert np.isnan(svc.store.anomaly[len(frame):]).all()
    names = [e.name for e in daemon.tracer.events()]
    assert "ingest.flush_failed" in names
    assert not daemon.degraded  # failure != backpressure degradation


def test_compile_failure_not_retried_and_not_swallowed(setup):
    """A scoring program the compiler refuses fails every attempt the
    same way: the service raises it at once (no retry, no backoff) and
    the daemon counts the lost flush and lets the error out."""
    from repro.fleet import ScorerCompileError

    svc = _refusing_service(setup)
    events = fleet_telemetry(MACHINES, rounds=1, runs_per_type=1,
                             seed=63, interval=1.0, jitter=0.01)
    daemon = IngestionDaemon(svc, capacity_rows=512,
                             flush_interval=0.5, flush_rows=1 << 30,
                             service_time_scale=0.0)
    with pytest.raises(ScorerCompileError, match="block shape refused"):
        daemon.run(events)
    st = daemon.stats()
    assert st["scorer_retries"] == 0
    assert st["flush_failures"] == 1


def _refusing_service(setup):
    """A service whose scoring program the compiler refuses."""
    svc = _service(setup)

    def refused(*args):
        raise ValueError("block shape refused by the TPU lowering")

    svc.scorer._call = jax.jit(refused)
    return svc


# --------------------------------------------------------- threaded mode

def test_threaded_serve_smoke(setup):
    """Wall-clock mode: a poll source drains into the daemon thread,
    rounds get scored, close() joins the thread cleanly."""
    frame, *_ = setup
    events = fleet_telemetry(MACHINES, rounds=2, runs_per_type=1,
                             seed=43, interval=0.05)
    pending = list(events)
    lock = threading.Lock()

    def poll(now):
        with lock:
            due = [e for e in pending if e.arrival <= now]
            for e in due:
                pending.remove(e)
            return due

    svc = _service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=512,
                             flush_interval=0.2, flush_rows=1 << 30,
                             service_time_scale=0.0)
    daemon.attach_source(poll)
    daemon.serve(poll_interval=0.02)
    deadline = time.time() + 30.0
    while time.time() < deadline:
        with lock:
            empty = not pending
        if empty and daemon.stats()["staged_rows"] == 0 \
                and daemon.results():
            break
        time.sleep(0.05)
    daemon.close(drain=True)
    assert daemon._thread is None
    res = daemon.results()
    assert sorted(res) == sorted(MACHINES)
    total = sum(len(r.anomaly_prob) for rs in res.values() for r in rs)
    assert total == sum(len(e.frame) for e in events)


def test_threaded_compile_failure_stops_stream_and_raises(setup):
    """Wall-clock mode: a compile error stops the serve thread, shows
    as a failed flush in stats(), and close() raises it."""
    from repro.fleet import ScorerCompileError

    events = fleet_telemetry(MACHINES, rounds=1, runs_per_type=1,
                             seed=64, interval=0.01)
    pending = list(events)

    def poll(now):
        due, pending[:] = list(pending), []
        return due

    svc = _refusing_service(setup)
    daemon = IngestionDaemon(svc, capacity_rows=512, flush_interval=0.05,
                             flush_rows=1, service_time_scale=0.0)
    daemon.attach_source(poll)
    daemon.serve(poll_interval=0.01)
    deadline = time.time() + 30.0
    while not daemon._stop.is_set() and time.time() < deadline:
        time.sleep(0.02)
    assert daemon._stop.is_set(), "the serve thread kept running"
    assert daemon.stats()["flush_failures"] == 1
    assert daemon.stats()["scorer_retries"] == 0
    with pytest.raises(ScorerCompileError, match="block shape refused"):
        daemon.close(drain=True)
    assert daemon._thread is None
    assert daemon.stats()["flush_failures"] == 1  # close() drained nothing


def test_warm_candidate_concurrent_with_flushes(setup):
    """Warming a candidate from another thread (the model plane's
    promote/rollback path) never leaks its params into live flushes:
    the stored scores equal an incumbent-only run bit for bit, and the
    incumbent's placement survives the candidate's."""
    frame, pre, model, params = setup
    events = fleet_telemetry(MACHINES, rounds=4, runs_per_type=1,
                             seed=65, interval=1.0, jitter=0.01)

    def run(candidate=None):
        svc = _service(setup)
        daemon = IngestionDaemon(svc, capacity_rows=512,
                                 flush_interval=0.5, flush_rows=1 << 30,
                                 service_time_scale=0.0)
        stop = threading.Event()
        warms = []

        def warm_loop():
            while not stop.wait(0.001):
                warms.append(svc.warm(candidate))

        t = threading.Thread(target=warm_loop) if candidate else None
        if t is not None:
            t.start()
        try:
            for ev in events:
                daemon.run([ev], drain=False)
            daemon.close(drain=True)
        finally:
            stop.set()
            if t is not None:
                t.join()
        return svc, warms

    ref, _ = run()
    bad = jax.tree_util.tree_map(lambda x: np.asarray(x) * np.nan, params)
    svc, warms = run(candidate=bad)
    assert sum(warms) > 0, "the candidate was never dispatched"
    np.testing.assert_array_equal(svc.store.anomaly, ref.store.anomaly)
    assert np.isfinite(svc.store.anomaly[len(frame):]).all()
    placed = svc.scorer.place_params(params)
    assert svc.scorer.place_params(bad) is not placed
    assert svc.scorer.place_params(params) is placed


# ------------------------------------------- watchdog under faults (e2e)

@pytest.fixture(scope="module")
def trained():
    from repro.core.trainer import train_perona

    # a deeper history + longer schedule than the scoring-path fixture:
    # the e2e needs a model that actually separates stressed telemetry
    runner = SuiteRunner(seed=11)
    frame = runner.run_frame(MACHINES, runs_per_type=40,
                             stress_fraction=0.2)
    pre = Preprocessor().fit(frame)
    batch = build_graphs(frame, pre)
    cfg = PeronaConfig(feature_dim=pre.feature_dim,
                       edge_dim=batch.edge.shape[-1])
    model = PeronaModel(cfg)
    res = train_perona(model, batch, epochs=120, seed=2)
    return frame, pre, model, res.params


def test_watchdog_flags_injected_degradation_under_faults(trained):
    """E2e: telemetry with one genuinely degraded node (stress-response
    shifted metrics) plus stream faults still drives the daemon's
    rolling drift to flag the degraded node within a few rounds, while
    clean nodes stay unflagged and the store stays finite."""
    frame, pre, model, params = trained
    rounds = 5
    events = fleet_telemetry(MACHINES, rounds=rounds, runs_per_type=2,
                             seed=47, interval=1.0, jitter=0.1,
                             degraded={"in-1": 1})
    faulty, log = inject_faults(events, FaultPlan(
        seed=9, delay=0.2, duplicate=0.2, corrupt=0.15, reorder=0.2))
    svc = FleetScoringService(model, params, pre, sharded=False)
    svc.seed_history(frame)
    daemon = IngestionDaemon(svc, capacity_rows=1024,
                             flush_interval=0.5, flush_rows=1 << 30,
                             service_time_scale=0.0)
    daemon.run(faulty)
    flagged = daemon.flagged_nodes(ewma_threshold=0.5, min_scored=3)
    assert "in-1" in flagged, (
        f"injected degradation not flagged; report="
        f"{ {n: round(d.anomaly_ewma, 3) for n, d in daemon.drift.report().items()} }")
    assert "in-0" not in flagged and "in-2" not in flagged
    assert _store_is_finite(svc.store)
    if log.corrupted:
        assert svc.stats["quarantined_rows"] == log.corrupted_rows
