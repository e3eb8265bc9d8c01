"""The benchmark's generators: schedules from a mix and a seed, and the
digests that pin the simulated telemetry and Scout data."""

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import scout_inputs, telemetry, traffic  # noqa: E402

DIGESTS = json.loads((ROOT / "bench" / "testdata" /
                      "digests.json").read_text())
MIXES = ROOT / "bench" / "mixes"
BIG = 2**31 + 12345


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["steady", "burst"])
def test_open_schedule_is_deterministic(name):
    a = traffic.open_schedule(_mix(name), BIG, 10.0, 1024)
    b = traffic.open_schedule(_mix(name), BIG, 10.0, 1024)
    for f in ("offset", "node", "k"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert np.all(np.diff(a.offset) >= 0)
    assert a.offset[0] >= 0 and a.offset[-1] < 10.0


@pytest.mark.parametrize("name", ["steady", "burst"])
def test_every_seed_gets_the_same_work(name):
    """Seeds reorder the same events: equal counts, equal multisets of
    gaps (steady) or offsets from the tick (burst), every node's rounds
    numbered 0, 1, 2, ..."""
    a = traffic.open_schedule(_mix(name), 3, 10.0, 1024)
    b = traffic.open_schedule(_mix(name), BIG, 10.0, 1024)
    assert len(a) == len(b) > 0
    if name == "steady":
        mix = _mix(name)
        assert len(a) == round(mix["events_per_s"] * 10.0)
        base = np.random.default_rng([mix["base_seed"]]).exponential(
            1.0, len(a))
        base = np.sort(10.0 * base / base.sum())
        for s in (a, b):  # every gap is one of the base gaps
            gaps = np.sort(np.diff(s.offset))
            near = np.clip(np.searchsorted(base, gaps), 1, len(base) - 1)
            err = np.minimum(np.abs(base[near] - gaps),
                             np.abs(base[near - 1] - gaps))
            assert err.max() < 1e-5
        assert not np.array_equal(a.node, b.node)
    else:
        period = _mix(name)["round_period_s"]
        np.testing.assert_allclose(np.sort(a.offset - a.k * period),
                                   np.sort(b.offset - b.k * period),
                                   atol=1e-6)
    for s in (a, b):
        for node in np.unique(s.node)[:50]:
            ks = s.k[s.node == node]
            assert np.array_equal(np.sort(ks), np.arange(len(ks)))


def test_matrix_seeds():
    mix = _mix("lanes4k")
    seeds = traffic.matrix_seeds(mix, BIG, 0)
    assert len(seeds) == mix["seeds_per_matrix"] == 28
    assert seeds == traffic.matrix_seeds(mix, BIG, 0)
    assert seeds != traffic.matrix_seeds(mix, BIG, 1)
    assert seeds != traffic.matrix_seeds(mix, BIG, -1)
    assert len(traffic.matrix_seeds(_mix("single"), BIG, 5)) == 1


def test_fleet_telemetry_digest():
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "region1024.json").read_text())
    for key, part in DIGESTS["fleet_test_size"].items():
        cfg[key] = (dict(cfg[key], **part) if isinstance(part, dict)
                    else part)
    for seed, want in DIGESTS["fleet"].items():
        tel = telemetry.FleetTelemetry(copy.deepcopy(cfg), int(seed))
        assert tel.digest() == want, f"fleet telemetry changed (seed {seed})"
    frame = tel.event_frame(3, 5)  # pool round 1, two cycles on
    np.testing.assert_array_equal(
        frame.t, tel.pool[1][3].t + 2 * 2 * telemetry.DAY)


def test_scout_data_digest():
    from repro.tuning.scout import VM_TYPES, ScoutDataset

    ds = ScoutDataset(seed=0)
    data = scout_inputs.search_data(ds, list(ds.workloads),
                                    scout_inputs.profile_scores(VM_TYPES),
                                    scout_inputs.conditions(0))
    assert scout_inputs.digest(data) == DIGESTS["scout_search_data"]
