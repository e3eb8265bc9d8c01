"""The configuration-search cells' correctness check, driven through
the harness at a test size on the CPU (the look for a chip skipped): a
sound run is correct; the control (the reference in float32 in the
program's place) departs from the float64 reference on some lanes, so
the exact comparison (limit 0) makes ``correct`` false; and each fault
of the timed path that a one-chip replay can have makes ``correct``
false. (No exchange between chips exists in a one-chip cell, so that
fault has no case here.)"""

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as harness  # noqa: E402


def run_tiny(workloads=2, control=False, seed=2**31 + 5):
    bench, c, config, mix = harness.find_cell(ROOT, "scout-sec4d.single")
    config = copy.deepcopy(config)
    config["workloads"] = workloads
    config["check"].update(lanes=10**6, control=control)
    return harness.run_cell(ROOT, bench, c, config, mix, seed=seed,
                            seconds=0.5, trace=False, require_tpu=False)


def test_sound_run_is_correct():
    out, run = run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] == run.values["checked_lanes"] > 0
    assert out["attempted"] % 16 == 0 and out["failed"] == 0
    assert run.compiles_in_window == 0
    assert set(out["metrics"]) == {"searches_per_s", "setup_s"}


def test_control_departs_from_the_reference():
    out, run = run_tiny(workloads=18, control=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["lanes_unlike_reference"]["value"] > 0
    assert out["failed"] > 0


def _patch_replay(monkeypatch, edit):
    from repro.optimizer import scenarios

    replay = scenarios.replay

    def broken(tables, cfg=None, **kw):
        res = replay(tables, cfg, **kw)
        res.chosen, res.count = np.array(res.chosen), np.array(res.count)
        edit(res, cfg, len(tables))
        return res

    monkeypatch.setattr(scenarios, "replay", broken)


def _unchanged_state(res, cfg, n):
    res.count[:] = cfg.n_init
    res.chosen[:, cfg.n_init:] = -1


def _half_the_lanes(res, cfg, n):
    res.chosen[n // 2:n] = res.chosen[0]
    res.count[n // 2:n] = res.count[0]


def _altered_answer(res, cfg, n):
    lane = int(np.argmax(res.count[:n] > cfg.n_init))
    pick = res.chosen[lane, cfg.n_init]
    others = np.setdiff1d(np.arange(69), res.chosen[lane])
    res.chosen[lane, cfg.n_init] = others[0] if pick != others[0] \
        else others[1]


@pytest.mark.parametrize("edit", [_unchanged_state, _half_the_lanes,
                                  _altered_answer])
def test_fault_is_caught(edit, monkeypatch):
    _patch_replay(monkeypatch, edit)
    out, _ = run_tiny()
    assert not out["correct"], out["checks"]
