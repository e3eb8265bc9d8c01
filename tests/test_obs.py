"""Unified telemetry plane: metrics registry semantics, span tracing
over injectable clocks, Chrome trace-event export/validation, JitSite
consolidation of the jit trace counters, disabled-mode no-ops, and the
two end-to-end timelines the PR promises — a daemon fault storm with
named ladder transitions, and a pipelined replay whose host table
builds overlap device block scans on separate thread tracks."""

import contextlib
import json
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.jaxstat import JitSite, instance_site
from repro.obs.timeline import (chrome_trace, validate_chrome_trace,
                                validate_chrome_trace_file,
                                write_chrome_trace)
from repro.obs.trace import (CAT_DEVICE, CAT_HOST, CAT_LADDER,
                             SpanEvent, Tracer)

from _trace_utils import expect_traces


# ---------------------------------------------------------- registry

def test_registry_identity_and_labels():
    reg = obs_metrics.MetricsRegistry()
    c1 = reg.counter("x.hits", site="a")
    c2 = reg.counter("x.hits", site="a")
    c3 = reg.counter("x.hits", site="b")
    assert c1 is c2 and c1 is not c3
    c1.inc()
    c1.inc(3)
    assert c1.value == 4 and c3.value == 0
    g = reg.gauge("x.depth")
    g.set(7.5)
    assert g.value == 7.5
    snap = reg.snapshot()
    assert snap["x.hits{site=a}"] == 4
    assert snap["x.hits{site=b}"] == 0
    assert snap["x.depth"] == 7.5
    assert "x.hits{site=a} 4" in reg.render()
    with pytest.raises(TypeError):
        reg.gauge("x.hits", site="a")  # same key, different type


def test_counter_thread_safety():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("threads.incs")
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


def test_histogram_exact_path_matches_np_quantile():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat", exact_limit=4096)
    rng = np.random.default_rng(0)
    xs = rng.lognormal(0.0, 1.5, size=500)
    for x in xs:
        h.observe(x)
    assert h.exact
    for q in (0.5, 0.9, 0.99):
        assert h.quantile(q) == float(np.quantile(xs, q))
    assert h.count == 500
    assert h.sum == pytest.approx(float(xs.sum()))
    qs = h.quantiles((0.5, 0.99))
    assert set(qs) == {"p50", "p99"}


def test_histogram_folds_past_exact_limit():
    h = obs_metrics.Histogram("lat", exact_limit=64)
    rng = np.random.default_rng(1)
    xs = rng.lognormal(0.0, 1.0, size=1000)
    for x in xs:
        h.observe(x)
    assert not h.exact  # folded to log buckets
    # count/sum/min/max stay exact
    assert h.count == 1000
    assert h.sum == pytest.approx(float(xs.sum()))
    s = h.summary()
    assert s["min"] == float(xs.min()) and s["max"] == float(xs.max())
    # folded quantiles: base-2 buckets -> within a factor of sqrt(2)
    for q in (0.5, 0.99):
        exact = float(np.quantile(xs, q))
        assert h.quantile(q) == pytest.approx(exact, rel=0.5)


def test_histogram_empty_and_nonpositive():
    h = obs_metrics.Histogram("lat", exact_limit=2)
    assert np.isnan(h.quantile(0.5))
    h.observe(0.0)
    h.observe(-1.0)
    h.observe(3.0)  # folds (exact_limit=2 exceeded)
    assert not h.exact
    assert h.count == 3 and h.quantile(0.0) <= 0.0


# ---------------------------------------------------- disabled mode

def test_disabled_mode_noops_everything(monkeypatch):
    entered = _count_annotations(monkeypatch)
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("off.hits")
    h = reg.histogram("off.lat")
    tr = Tracer()
    site = JitSite("off.site", registry=reg, tracer=tr)
    with obs.disabled():
        assert not obs.enabled()
        c.inc(5)
        h.observe(1.0)
        with tr.span("s"):
            assert tr._stack() == []
            with site.dispatch("d"):
                assert tr._stack() == []
        tr.instant("i")
        tr.complete("c", CAT_HOST, 0.0, 1.0)
    assert obs.enabled()  # restored
    assert c.value == 0 and h.count == 0
    assert tr.events() == []
    assert entered == [] and tr._stack() == []
    assert site.dispatches == 0
    assert site.compile_seconds == 0.0 and site.run_seconds == 0.0
    # re-enabled: everything records again
    c.inc()
    with tr.span("s2"):
        pass
    assert c.value == 1 and len(tr.events()) == 1
    assert entered == ["s2"]


# ------------------------------------------------------------ tracer

def _count_annotations(monkeypatch):
    """Replace the profiler annotation the tracer enters with one that
    notes each name entered; returns the list of names."""
    entered = []

    @contextlib.contextmanager
    def note(name):
        entered.append(name)
        yield

    monkeypatch.setattr(obs_trace, "_annotation", note)
    return entered


def test_tracer_spans_and_injectable_clock():
    clock = {"t": 10.0}
    tr = Tracer(clock=lambda: clock["t"])
    with tr.span("work", cat=CAT_HOST, args={"k": 1}):
        clock["t"] = 12.5
    (ev,) = tr.events()
    assert (ev.name, ev.cat, ev.ts, ev.dur) == ("work", CAT_HOST,
                                                10.0, 2.5)
    assert ev.args == {"k": 1}
    assert ev.thread == threading.current_thread().name
    tr.instant("mark", CAT_LADDER, ts=11.0)
    tr.complete("flush", CAT_HOST, ts=10.5, dur=0.25)
    assert [e.name for e in tr.events()] == ["work", "mark", "flush"]
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_nested_spans_name_their_parent(monkeypatch):
    entered = _count_annotations(monkeypatch)
    tr = Tracer()
    seen = {}

    def worker():
        with tr.span("other_thread"):
            pass

    with tr.span("outer"):
        with tr.span("inner"):
            tr.instant("mark")
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        tr.complete("flush", CAT_HOST, ts=0.0, dur=1.0)
        with tr.span("sibling"):
            seen["stack"] = list(tr._stack())
    with tr.span("after"):
        pass
    evs = {e.name: e for e in tr.events()}
    assert len({e.id for e in evs.values()}) == len(evs)
    assert all(e.id > 0 for e in evs.values())
    assert evs["outer"].parent is None and evs["after"].parent is None
    assert evs["inner"].parent == evs["outer"].id
    assert evs["mark"].parent == evs["inner"].id
    assert evs["flush"].parent == evs["outer"].id
    assert evs["sibling"].parent == evs["outer"].id
    assert seen["stack"] == [evs["outer"].id, evs["sibling"].id]
    # a span on another thread starts its own stack
    assert evs["other_thread"].parent is None
    assert tr._stack() == []
    # every context-managed span enters one annotation; complete and
    # instant stay off the profiler's clock
    assert sorted(entered) == sorted(
        ["outer", "inner", "other_thread", "sibling", "after"])
    # the exporter carries both ids in args
    args = {e["name"]: e["args"] for e in chrome_trace(tr.events())
            ["traceEvents"] if e["ph"] != "M"}
    assert args["inner"]["id"] == evs["inner"].id
    assert args["inner"]["parent"] == evs["outer"].id
    assert args["outer"]["parent"] is None


def test_span_exception_unwinds_the_stack():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("fails"):
            raise RuntimeError("boom")
    (ev,) = tr.events()
    assert ev.name == "fails" and tr._stack() == []


def test_tracer_ring_bounds_and_drop_count():
    tr = Tracer(max_events=4)
    for i in range(7):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 4 and evs[0].name == "e3"
    assert tr.dropped == 3


# ---------------------------------------------------------- timeline

def test_chrome_trace_export_is_valid_and_monotonic(tmp_path):
    clock = {"t": 0.0}
    tr = Tracer(clock=lambda: clock["t"])
    with tr.span("outer"):
        clock["t"] = 1.0
    tr.instant("ladder.block", CAT_LADDER, ts=0.5)
    with tr.span("later", cat=CAT_DEVICE):
        clock["t"] = 3.0
    path = str(tmp_path / "t.json")
    obj = write_chrome_trace(path, tracer=tr, process_name="test-proc")
    summary = validate_chrome_trace_file(path)
    assert summary["spans"] == 2 and summary["threads"] == 1
    evs = obj["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name",
            "thread_sort_index"} <= {e["name"] for e in meta}
    timed = [e for e in evs if e["ph"] != "M"]
    # microseconds relative to the earliest event, monotonic per track
    assert [e["ts"] for e in timed] == [0.0, 500_000.0, 1_000_000.0]
    xs = [e for e in timed if e["ph"] == "X"]
    assert xs[0]["dur"] == 1_000_000.0 and xs[1]["dur"] == 2_000_000.0
    inst = next(e for e in timed if e["ph"] == "i")
    assert inst["s"] == "t" and inst["cat"] == CAT_LADDER
    with open(path) as f:
        assert json.load(f) == obj  # artifact round-trips


def test_chrome_trace_interleaves_threads_deterministically():
    events = [
        SpanEvent("a", CAT_HOST, 0.0, 1.0, tid=111, thread="main"),
        SpanEvent("b", CAT_DEVICE, 0.5, 1.0, tid=222, thread="worker"),
        SpanEvent("c", CAT_HOST, 2.0, 0.5, tid=111, thread="main"),
    ]
    obj = chrome_trace(events)
    validate_chrome_trace(obj)
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e.get("name") == "thread_name"}
    assert names == {"main", "worker"}
    # dense tids in first-seen order: main -> 0, worker -> 1
    by_name = {e["name"]: e["tid"] for e in obj["traceEvents"]
               if e["ph"] == "X"}
    assert by_name == {"a": 0, "b": 1, "c": 0}


def test_validator_rejects_malformed_traces():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"nope": []})
    base = [{"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "p"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "t"}}]

    def bad(*evs):
        with pytest.raises(ValueError) as ei:
            validate_chrome_trace({"traceEvents": base + list(evs)})
        return str(ei.value)

    assert "unknown phase" in bad(
        {"ph": "Z", "pid": 1, "tid": 0, "name": "x", "ts": 0})
    assert "goes backwards" in bad(
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": 5, "dur": 1},
        {"ph": "X", "pid": 1, "tid": 0, "name": "y", "ts": 4, "dur": 1})
    assert "dur" in bad(
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": 0})
    assert "no open B" in bad(
        {"ph": "E", "pid": 1, "tid": 0, "name": "x", "ts": 0})
    assert "unclosed B" in bad(
        {"ph": "B", "pid": 1, "tid": 0, "name": "x", "ts": 0})
    assert "does not match" in bad(
        {"ph": "B", "pid": 1, "tid": 0, "name": "x", "ts": 0},
        {"ph": "E", "pid": 1, "tid": 0, "name": "y", "ts": 1})
    assert "thread_name" in bad(
        {"ph": "X", "pid": 1, "tid": 9, "name": "x", "ts": 0, "dur": 0})
    # matched B/E with metadata passes
    validate_chrome_trace({"traceEvents": base + [
        {"ph": "B", "pid": 1, "tid": 0, "name": "x", "ts": 0},
        {"ph": "E", "pid": 1, "tid": 0, "name": "x", "ts": 1}]})


# ------------------------------------------------------------ JitSite

def test_jitsite_attributes_compile_vs_run():
    reg = obs_metrics.MetricsRegistry()
    tr = Tracer()
    site = JitSite("t.site", registry=reg, tracer=tr)
    with site.dispatch("call", args={"n": 1}):
        site.tick()  # traced inside the call -> compile time
    with site.dispatch("call", args={"n": 2}):
        pass  # warm -> run time
    assert site.count == site.trace_count == 1
    assert site.dispatches == 2
    assert site.compile_seconds > 0.0 and site.run_seconds > 0.0
    evs = tr.events()
    assert [e.cat for e in evs] == [CAT_DEVICE, CAT_DEVICE]
    assert evs[0].args == {"n": 1, "traced": True}
    assert evs[1].args == {"n": 2, "traced": False}
    st = site.stats()
    assert st["traces"] == 1 and st["dispatches"] == 2
    assert reg.snapshot()["jax.traces{site=t.site}"] == 1
    # a dispatch is a span: it nests under the span open around it
    with tr.span("caller"):
        with site.dispatch("call"):
            pass
    caller, call = tr.events()[-1], tr.events()[-2]
    assert call.parent == caller.id and call.args["traced"] is False


def test_instance_site_labels_are_unique():
    a, b = instance_site("x.y"), instance_site("x.y")
    assert a != b and a.startswith("x.y/")


def test_expect_traces_reads_jitsite_and_raw_counter():
    reg = obs_metrics.MetricsRegistry()
    site = JitSite("e.site", registry=reg)
    with expect_traces(site, 2):
        site.tick()
        site.tick()
    raw = reg.counter("e.raw")
    with expect_traces(raw, 1):
        raw.inc()


# --------------------------------------- engine/trainer consolidation

def test_engine_trace_count_backed_by_registry():
    """FingerprintEngine's trace_count survives the consolidation: the
    per-instance registry counter advances exactly when the engine
    retraces, and dispatch/compile accounting rides along."""
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.fingerprint.runner import SuiteRunner
    from repro.serving.engine import FingerprintEngine

    runner = SuiteRunner(seed=3)
    frame = runner.run_frame({"m-0": "e2-medium"}, runs_per_type=4)
    pre = Preprocessor().fit(frame)
    from repro.core.graph_data import build_graphs
    batch = build_graphs(frame, pre)
    cfg = PeronaConfig(feature_dim=pre.feature_dim,
                       edge_dim=batch.edge.shape[-1])
    model = PeronaModel(cfg)
    engine = FingerprintEngine(model, model.init(jax.random.PRNGKey(0)),
                               pre)
    assert engine.trace_count == 0
    engine.score(frame)
    assert engine.trace_count == 1
    engine.score(frame)  # same bucket: no retrace
    assert engine.trace_count == 1
    assert engine.jit.dispatches == 2
    assert engine.jit.compile_seconds > 0.0
    key = f"jax.traces{{site={engine.jit.site}}}"
    assert obs.registry().snapshot()[key] == 1


# --------------------------------------------- end-to-end timelines

MACHINES = {"ob-0": "e2-medium", "ob-1": "n2-standard-4",
            "ob-2": "e2-medium"}


@pytest.fixture(scope="module")
def setup():
    from repro.core.graph_data import build_graphs
    from repro.core.model import PeronaConfig, PeronaModel
    from repro.core.preprocess import Preprocessor
    from repro.fingerprint.runner import SuiteRunner

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


def _storm_daemon(setup):
    from repro.fleet import (FleetScoringService, IngestionDaemon,
                             fleet_telemetry)

    frame, pre, model, params = setup
    svc = FleetScoringService(model, params, pre, sharded=False)
    svc.seed_history(frame)
    daemon = IngestionDaemon(svc, capacity_rows=48, flush_interval=10.0,
                             flush_rows=1 << 30, min_flush_gap=5.0,
                             degrade_after=2, recover_after=1,
                             degrade_sample_per_chain=1,
                             service_time_scale=0.0)
    events = fleet_telemetry(MACHINES, rounds=8, runs_per_type=2,
                             seed=13, interval=0.05, jitter=0.01)
    return daemon, events


def test_daemon_fault_storm_timeline(setup, tmp_path):
    """A backpressure storm exports a perfetto-loadable timeline whose
    ladder transitions (block -> shed -> degrade) are named instant
    events on the daemon's virtual clock, alongside the flush spans."""
    from repro.fleet import fleet_telemetry

    daemon, events = _storm_daemon(setup)
    daemon.run(events)  # gated consumer: shed + degrade
    # phase 2: free the consumer so an overflow *blocks* (forces a
    # flush) instead of shedding — all three ladder steps in one run
    daemon.min_flush_gap = 0.0
    import dataclasses
    more = [dataclasses.replace(e, uid=e.uid + 100_000)
            for e in fleet_telemetry(MACHINES, rounds=4,
                                     runs_per_type=2, seed=19,
                                     interval=0.05, jitter=0.01)]
    daemon.run(more)
    st = daemon.stats()
    assert st["shed_rows"] > 0 and st["degrade_entries"] > 0
    assert st["forced_flushes"] > 0

    evs = daemon.tracer.events()
    names = [e.name for e in evs]
    for step in ("ladder.block", "ladder.shed", "ladder.degrade"):
        assert step in names, f"missing {step} in {sorted(set(names))}"
    ladder = [e for e in evs if e.cat == CAT_LADDER]
    assert all(e.ph == "i" for e in ladder)
    flushes = [e for e in evs if e.name == "ingest.flush"]
    assert len(flushes) == (st["forced_flushes"] + st["drain_flushes"]
                            + st["deadline_flushes"]
                            + st["row_trigger_flushes"])
    assert {f.args["trigger"] for f in flushes} >= {"forced", "drain"}
    assert any(f.args["degraded"] for f in flushes)
    # virtual clock: timestamps follow the daemon's `now`, not wall
    assert max(e.ts for e in evs) <= st["virtual_now"] + 1e-9

    path = str(tmp_path / "storm.json")
    write_chrome_trace(path, tracer=daemon.tracer)
    summary = validate_chrome_trace_file(path)
    assert summary["spans"] >= len(flushes)
    with open(path) as f:
        exported = {e.get("name") for e in json.load(f)["traceEvents"]}
    assert {"ladder.block", "ladder.shed", "ladder.degrade",
            "ingest.flush"} <= exported


def test_daemon_latency_histogram_parity(setup):
    """stats() keeps its latency_p50/p99 keys, now read from the shared
    streaming histogram — exact np.quantile over the recorded
    arrival->flush latencies while under the retention limit."""
    daemon, events = _storm_daemon(setup)
    daemon.run(events)
    st = daemon.stats()
    lat = daemon._latency
    assert lat.exact  # small run: exact-quantile regime
    assert st["latency_p50"] == daemon.latency_quantiles()["p50"]
    assert np.isfinite(st["latency_p99"])
    assert st["latency_p50"] <= st["latency_p99"]
    key = f"ingest.queue_latency_s{{daemon={daemon.site}}}"
    snap = obs.registry().snapshot()
    assert snap[key]["count"] == lat.count > 0


def test_daemon_core_stats_survive_disabled_plane(setup):
    """Program-logic counters (shed/dedup/flush accounting) are plain
    ints, NOT registry instruments: the ladder keeps exact counts even
    with the telemetry plane off, while spans/mirrors go quiet."""
    daemon, events = _storm_daemon(setup)
    with obs.disabled():
        daemon.run(events)
    st = daemon.stats()
    assert st["shed_rows"] > 0 and st["degrade_entries"] > 0
    assert st["events_seen"] == len(events)
    assert daemon.tracer.events() == []  # no spans recorded
    assert daemon._m_events.value == 0  # mirror stayed quiet


def test_pipelined_replay_host_device_overlap(tmp_path):
    """replay_pipelined's host table-build spans (main thread) overlap
    the device block-scan spans (per-device worker threads) on the
    process tracer — the pipelining is visible in the exported
    timeline as intersecting intervals on different thread tracks."""
    from repro.optimizer import (HEALTHY, build_scenarios,
                                 replay_pipelined)
    from repro.tuning.scout import ScoutDataset, VM_TYPES, \
        WORKLOAD_NAMES

    ds = ScoutDataset(seed=0)
    rng = np.random.default_rng(3)
    scores = {vm: {a: float(rng.uniform(0.5, 2.0))
                   for a in ("cpu", "memory", "disk", "network")}
              for vm in VM_TYPES}
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    tr = obs.tracer()
    tr.clear()
    traces = replay_pipelined(ds, scens, scores, block_lanes=4)
    assert len(traces) == len(scens)

    evs = tr.events()
    builds = [e for e in evs if e.name == "replay.lane_tables"]
    scans = [e for e in evs if e.name == "replay.block_scan"]
    assert len(builds) == len(scans) == len(scens) // 4
    assert all(e.cat == CAT_DEVICE for e in scans)
    # worker-thread device track(s) distinct from the main host track
    assert {e.tid for e in scans}.isdisjoint({e.tid for e in builds})
    overlap = any(
        b.ts < s.ts + s.dur and s.ts < b.ts + b.dur
        for b in builds for s in scans)
    assert overlap, "no host build span overlapped a device scan span"

    path = str(tmp_path / "pipe.json")
    write_chrome_trace(path, tracer=tr)
    summary = validate_chrome_trace_file(path)
    assert summary["threads"] >= 2


REPLAY_SPANS = ("replay.build_scenarios", "replay.lane_tables",
                "replay.stage", "replay.dispatch", "replay.wait",
                "replay.materialize_traces")


def test_replay_spans_on_the_profiler_clock(tmp_path):
    """Under a CPU profiler trace, every replay span of one search is a
    host annotation nested inside the enclosing span's annotation, and
    the obs events name that span as their parent."""
    from jax.profiler import ProfileData

    from repro.optimizer import (HEALTHY, build_scenarios,
                                 replay_scenarios)
    from repro.tuning.scout import ScoutDataset, VM_TYPES, \
        WORKLOAD_NAMES

    ds = ScoutDataset(seed=0)
    scores = {vm: {a: 1.0 for a in ("cpu", "memory", "disk", "network")}
              for vm in VM_TYPES}
    tr = obs.tracer()
    tr.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.search"):
            scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                                    seeds=(0, 1), conditions=(HEALTHY,))
            replay_scenarios(ds, scens, scores)
    finally:
        jax.profiler.stop_trace()

    evs = tr.events()
    outer = next(e for e in evs if e.name == "test.search")
    replay = [e for e in evs if e.name.startswith("replay.")]
    assert sorted(e.name for e in replay) == sorted(REPLAY_SPANS)
    assert all(e.parent == outer.id for e in replay)

    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    (_, lo, hi) = next(h for h in host if h[0] == "test.search")
    inside = {name for name, a, b in host
              if name.startswith("replay.") and lo <= a and b <= hi}
    assert inside == set(REPLAY_SPANS)


def test_service_stats_traces_through_registry(setup):
    """fleet service stats()['traces'] reads the consolidated JitSite;
    quarantine mirrors land on the registry with kind labels."""
    from repro.fleet import FleetScoringService

    frame, pre, model, params = setup
    svc = FleetScoringService(model, params, pre, sharded=False)
    svc.seed_history(frame)
    assert svc.stats["traces"] == svc.scorer.jit.count
    site = svc.scorer.jit.site
    snap = obs.registry().snapshot()
    assert f"fleet.quarantined{{kind=nonfinite,site={site}}}" in snap
