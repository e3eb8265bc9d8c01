"""The configuration-search path's spans: one of each per matrix and
none per lane, whatever the matrix's width, on the host and the seeded
lowering alike; a matrix split into lane blocks launches each block
before it fetches the one before, and each block's spans stand at the
top level on the calling thread."""

import collections

import pytest

from repro import obs
from repro.optimizer import HEALTHY, build_scenarios, replay_scenarios
from repro.tuning.scout import VM_TYPES, WORKLOAD_NAMES, ScoutDataset


@pytest.fixture(scope="module")
def scout():
    ds = ScoutDataset(seed=0)
    scores = {vm: {a: 1.0 for a in ("cpu", "memory", "disk", "network")}
              for vm in VM_TYPES}
    return ds, scores


@pytest.mark.parametrize("seeded", [False, True])
@pytest.mark.parametrize("n_seeds", [2, 16], ids=["16lanes", "128lanes"])
def test_one_span_of_each_per_matrix(scout, n_seeds, seeded):
    ds, scores = scout
    tr = obs.tracer()
    tr.clear()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=tuple(range(n_seeds)),
                            conditions=(HEALTHY,))
    traces = replay_scenarios(ds, scens, scores, seeded=seeded)
    assert len(traces) == len(scens) == 8 * n_seeds

    counts = collections.Counter(e.name for e in tr.events())
    lower, launch = (("replay.lane_spec", "replay.dispatch_seeded")
                     if seeded else
                     ("replay.lane_tables", "replay.dispatch"))
    assert counts == {"replay.build_scenarios": 1, lower: 1,
                      "replay.stage": 1, launch: 1, "replay.wait": 1,
                      "replay.materialize_traces": 1}
    by_name = {e.name: e for e in tr.events()}
    for name in counts:
        assert by_name[name].args["lanes"] == len(scens)
    stage = by_name["replay.stage"].args
    assert stage["padded"] == by_name[launch].args["padded"] >= len(scens)
    assert stage["bytes"] > 0
    # below two blocks a matrix keeps its one dispatch
    assert (by_name[launch].args["block"],
            by_name[launch].args["blocks"]) == (0, 1)


@pytest.mark.parametrize("seeded", [False, True])
def test_blocks_launch_ahead_of_the_fetch(scout, monkeypatch, seeded):
    """Four blocks of 8 lanes on one thread, two in flight: block i+1
    is lowered and launched before block i is waited for, and block
    i's traces are materialized before block i+2 is waited for."""
    from repro.optimizer import scenarios

    ds, scores = scout
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    tr = obs.tracer()
    tr.clear()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1, 2, 3), conditions=(HEALTHY,))
    replay_scenarios(ds, scens, scores, seeded=seeded)

    lower, launch = (("replay.lane_spec", "replay.dispatch_seeded")
                     if seeded else
                     ("replay.lane_tables", "replay.dispatch"))

    def spans(name):
        own = sorted((e for e in tr.events() if e.name == name),
                     key=lambda e: e.ts)
        assert len(own) == 4 and all(e.args["lanes"] == 8 for e in own)
        return own

    low, disp = spans(lower), spans(launch)
    wait, mat = spans("replay.wait"), spans("replay.materialize_traces")
    assert [(e.args["block"], e.args["blocks"]) for e in disp] == \
        [(i, 4) for i in range(4)]
    for i in range(3):
        assert low[i + 1].ts < disp[i + 1].ts < wait[i].ts
    for i in range(2):
        assert mat[i].ts + mat[i].dur <= wait[i + 2].ts
    assert mat[3].ts >= wait[3].ts + wait[3].dur


def test_pipelined_blocks_nest_their_replay_spans(scout, monkeypatch):
    """Under the block path every per-block span is top-level, one per
    block, all on the calling thread, with no wrapper span around a
    block."""
    from repro.optimizer import scenarios

    ds, scores = scout
    monkeypatch.setattr(scenarios, "REPLAY_BLOCK_LANES", 8)
    tr = obs.tracer()
    tr.clear()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    replay_scenarios(ds, scens, scores)
    evs = tr.events()
    per_block = ("replay.lane_tables", "replay.stage", "replay.dispatch",
                 "replay.wait", "replay.materialize_traces")
    for name in per_block:
        own = [e for e in evs if e.name == name]
        assert len(own) == 2 and all(e.parent is None for e in own)
    assert len({e.tid for e in evs}) == 1
    # no block wrapper span of any name around them
    assert {e.name for e in evs} == {"replay.build_scenarios", *per_block}
