"""The streaming cells' correctness check, driven through the harness
at a test size on the CPU (the look for a chip skipped): a sound run is
correct; the control (the reference in bfloat16) departs from the
reference where the program does not; and each fault of the timed
path that a one-chip stream can have makes ``correct`` false. (No
exchange between chips exists in a one-chip cell, so that fault has no
case here.)"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as harness  # noqa: E402


#: The stream cells' end-to-end metrics, as the benchmark reports them.
E2E = [{"name": "score_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]


def tiny(cell="region1024.steady", control=False):
    """The cell from its files, cut to a test size: 8 nodes, 40 events/s
    (steady) or a round every 0.4 s (burst), a 0.2 s deadline, every
    event checked, the score gap held to a test-size limit."""
    config_name, traffic = cell.split(".")
    config = json.loads((ROOT / "bench" / "configs" /
                         f"{config_name}.json").read_text())
    mix = json.loads((ROOT / "bench" / "mixes" /
                      f"{traffic}.json").read_text())
    c = {"name": cell, "config": config_name, "traffic": traffic,
         "chips": 1}
    config["fleet"]["nodes"] = 8
    config["fit"].update(nodes=8, runs_per_type=3)
    config["pool_rounds"] = 2
    config["daemon"]["flush_interval"] = 0.2
    # A limit for this test size on the CPU, where the program's gap is
    # under 1e-5 and the control's over 1e-3: the chip's is not set yet.
    config["check"].update(events=1000, control=control, score_gap=1e-4)
    if mix["kind"] == "poisson":
        mix["events_per_s"] = 40.0
    else:
        mix["round_period_s"] = 0.4
    return {"end_to_end": E2E, "per_layer": []}, c, config, mix


def run_tiny(cell="region1024.steady", control=False, seed=2**31 + 3):
    bench, c, config, mix = tiny(cell, control)
    return harness.run_cell(ROOT, bench, c, config, mix, seed=seed,
                            seconds=1.0, trace=False, require_tpu=False)


@pytest.mark.parametrize("cell", ["region1024.steady", "region1024.burst"])
def test_sound_run_is_correct(cell):
    out, run = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert run.compiles_in_window == 0
    assert run.values["checked_rows"] == 6 * out["attempted"]
    assert set(out["metrics"]) == {"score_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_departs_from_the_reference():
    """On the CPU the program's float32 scores equal the reference's to
    rounding, while the control (the reference in bfloat16, in the
    program's place) departs by thousandths and fails the check; the
    limit between them on the chip is open (PERF.md)."""
    out, run = run_tiny(control=True)
    assert not out["correct"], out["checks"]
    assert run.values["score_gap"] < 1e-5
    assert run.values["control_score_gap"] > 1e-3
    assert run.values["control_score_gap"] > 100 * run.values["score_gap"]


def _fault_unchanged_state(monkeypatch):
    from repro.fleet.store import FingerprintStore

    monkeypatch.setattr(FingerprintStore, "attach",
                        lambda self, idx, anomaly, codes=None: None)


def _fault_half_batch(monkeypatch):
    from repro.fleet.shard import ShardedScorer

    score = ShardedScorer.score_stack

    def half(self, params, stack):
        r = len(stack["raw"])
        keep = max(r // 2, 1)
        out = {k: np.array(v) for k, v in score(self, params, stack).items()}
        for v in out.values():
            v[keep:] = v[:keep].mean(0)
        return out

    monkeypatch.setattr(ShardedScorer, "score_stack", half)


def _fault_altered_answer(monkeypatch):
    from repro.fleet.shard import ShardedScorer

    score = ShardedScorer.score_stack

    def altered(self, params, stack):
        out = {k: np.array(v) for k, v in score(self, params, stack).items()}
        out["anomaly_prob"][0] = np.clip(out["anomaly_prob"][0] + 0.2,
                                         0, 1)
        return out

    monkeypatch.setattr(ShardedScorer, "score_stack", altered)


@pytest.mark.parametrize("fault", [_fault_unchanged_state,
                                   _fault_half_batch,
                                   _fault_altered_answer])
def test_fault_is_caught(fault, monkeypatch):
    fault(monkeypatch)
    out, _ = run_tiny()
    assert not out["correct"], out["checks"]
