"""The Karasu cell's correctness check and its readers, driven through
the harness at a test size on the CPU (the look for a chip skipped): a
sound run is correct against ``bench/reference/karasu.py``, the
float32 control and a lane perturbed on purpose make ``correct``
false, and the support-table readers read the program's
``replay.support_tables`` span."""

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as harness  # noqa: E402

CELL = "karasu-scout.seeds56"


def run_tiny(seed=2**31 + 7, control=False):
    """3 workloads (2 support models a lane), 2 seeds a matrix, 32
    posterior samples, 4 past searches a workload."""
    bench, c, config, mix = harness.find_cell(ROOT, CELL)
    config = copy.deepcopy(config)
    config.update(workloads=3, support_history=4)
    config["replay"]["samples"] = 32
    config["check"].update(lanes=10**6, control=control)
    mix = dict(mix, seeds_per_matrix=2)
    return harness.run_cell(ROOT, bench, c, config, mix, seed=seed,
                            seconds=0.5, trace=False, require_tpu=False)


@pytest.fixture(scope="module")
def tiny_run():
    return run_tiny()


def test_sound_run_is_correct(tiny_run):
    out, run = tiny_run
    assert out["correct"], out["checks"]
    # 3 workloads x 2 seeds x 2 variants x 2 conditions a matrix
    assert out["attempted"] == run.values["checked_lanes"] > 0
    assert out["attempted"] % 24 == 0 and out["failed"] == 0
    assert run.compiles_in_window == 0
    assert set(out["metrics"]) == {"searches_per_s", "setup_s"}
    gap = out["checks"]["ei_peak_gap"]
    assert gap["value"] < gap["limit"] / 10


def test_control_departs_from_the_reference():
    """The reference in float32 in the program's place picks the same
    configurations here, but its EI values depart: ``ei_peak_gap``
    makes the run incorrect."""
    out, _ = run_tiny(control=True)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["ei_peak_gap"]
    assert gap["value"] > 10 * gap["limit"]


@pytest.mark.parametrize("metric", ["support_tables_ms",
                                    "support_staged_mb"])
def test_support_readers_read_the_window(tiny_run, metric):
    _, run = tiny_run
    value = harness.load_module(ROOT, "metrics", metric).read(run)
    assert value is not None and value > 0
    if metric == "support_staged_mb":
        # ids (32 padded lanes x 2) and seeds, and the 12-row grid
        grid = 12 * (69 + 69 + 69 * 69) * 8
        assert value == pytest.approx((32 * 2 * 4 + 32 * 4 + grid) / 1e6)


def test_perturbed_lane_is_caught(monkeypatch):
    from repro.optimizer import scenarios

    replay = scenarios.replay

    def broken(tables, cfg=None, **kw):
        res = replay(tables, cfg, **kw)
        res.chosen = np.array(res.chosen)
        n = len(tables)
        if tables.n_support:  # the measured matrices, not the history
            lane = int(np.argmax(res.count[:n] > cfg.n_init))
            pick = res.chosen[lane, cfg.n_init]
            others = np.setdiff1d(np.arange(69), res.chosen[lane])
            res.chosen[lane, cfg.n_init] = (others[0] if pick != others[0]
                                            else others[1])
        return res

    monkeypatch.setattr(scenarios, "replay", broken)
    out, _ = run_tiny()
    assert not out["correct"], out["checks"]
    assert out["checks"]["lanes_unlike_reference"]["value"] > 0
