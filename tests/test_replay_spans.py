"""The configuration-search path's spans: one of each per matrix and
none per lane, whatever the matrix's width, on the host and the seeded
lowering alike; under the pipelined replay the per-block staging,
launch and wait nest in the worker's block span."""

import collections

import pytest

from repro import obs
from repro.optimizer import (HEALTHY, build_scenarios, replay_pipelined,
                             replay_scenarios)
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


def test_pipelined_blocks_nest_their_replay_spans(scout):
    ds, scores = scout
    tr = obs.tracer()
    tr.clear()
    scens = build_scenarios(ds, workloads=WORKLOAD_NAMES[:2],
                            seeds=(0, 1), conditions=(HEALTHY,))
    replay_pipelined(ds, scens, scores, block_lanes=8)
    evs = tr.events()
    blocks = {e.id: e for e in evs if e.name == "replay.block_scan"}
    assert len(blocks) == 2
    for name in ("replay.stage", "replay.dispatch", "replay.wait"):
        inner = [e for e in evs if e.name == name]
        assert len(inner) == 2
        for e in inner:
            assert e.parent in blocks
            assert e.tid == blocks[e.parent].tid
    # table building and trace materialization stay on the main thread,
    # once per block, with no wrapper span repeating them
    for name in ("replay.lane_tables", "replay.materialize_traces"):
        own = [e for e in evs if e.name == name]
        assert len(own) == 2 and all(e.parent is None for e in own)
    assert not any(e.name == "replay.build_tables" for e in evs)
