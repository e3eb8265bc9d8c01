"""The per-layer readers of the program's own replay spans, on a tiny
``scout-sec4d.single`` run through the harness on the CPU (the look for
a chip skipped): each reads a positive number, only the measured
window's spans count (not the warm-up matrix's), and the padding share
agrees with the lane counts. Readers of an empty run are covered by
``test_metric_reader_reads_nothing_from_an_empty_run``."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_spans  # noqa: E402
from bench import run as harness  # noqa: E402

READERS = ["scenario_build_ms", "replay_stage_ms", "replay_staged_mb",
           "replay_wait_ms", "replay_pad_share", "trace_materialize_ms",
           "replay_host_gap_ms"]
#: 3 workloads x 1 seed x 4 variants x 2 conditions, padded to 32
LANES, PADDED = 24, 32


@pytest.fixture(scope="module")
def tiny_run():
    bench, c, config, mix = harness.find_cell(ROOT, "scout-sec4d.single")
    config = copy.deepcopy(config)
    config["workloads"] = 3
    config["check"].update(lanes=16)
    out, run = harness.run_cell(ROOT, bench, c, config, mix,
                                seed=2**31 + 11, seconds=0.5, trace=False,
                                require_tpu=False)
    assert out["correct"], out["checks"]
    return run


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_a_positive_number(tiny_run, metric):
    value = harness.load_module(ROOT, "metrics", metric).read(tiny_run)
    assert value is not None and value > 0


def test_window_excludes_the_warm_up(tiny_run):
    matrices = tiny_run.counts["matrices"]
    assert matrices >= 2
    for name in ("replay.build_scenarios", "replay.lane_tables",
                 "replay.stage", "replay.dispatch", "replay.wait",
                 "replay.materialize_traces"):
        assert len(program_spans.in_window(tiny_run, name)) == matrices
    # the warm-up matrix staged once, before the window opened
    from repro import obs

    ((lo, hi),) = tiny_run.spans["setup.warm_up"]
    warm = [e for e in obs.tracer().events()
            if e.name == "replay.stage" and lo <= e.ts <= hi]
    assert len(warm) == 1
    assert hi <= tiny_run.t_process + tiny_run.setup_s


def test_pad_share_agrees_with_the_lane_counts(tiny_run):
    run = tiny_run
    assert run.counts["lanes_done"] == LANES * run.counts["matrices"]
    want = 1 - run.counts["lanes_done"] / (PADDED * run.counts["matrices"])
    got = harness.load_module(ROOT, "metrics", "replay_pad_share").read(run)
    assert got == pytest.approx(want)
