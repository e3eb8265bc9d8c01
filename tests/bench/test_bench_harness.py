"""The harness finds every cell's configuration, mix, driver and metric
readers from files alone, BENCHMARK.json keeps to the benchmark's
contract, and a run without a TPU exits non-zero with no result."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_from_files(cell):
    bench, c, config, mix = harness.find_cell(ROOT, cell)
    assert NAME.match(c["name"]) and c["chips"] in (1, 4)
    assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    driver = harness.load_module(ROOT, "drivers", config["driver"])
    assert callable(driver.run)
    assert mix["kind"] in ("poisson", "fleet_rounds", "matrices")
    reported = [m["name"] for m in harness.cell_metrics(bench, cell, False)]
    assert "setup_s" in reported and len(reported) >= 2
    assert harness.cell_metrics(bench, cell, True)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_reads_nothing_from_an_empty_run(metric):
    reader = harness.load_module(ROOT, "metrics", metric)
    run = harness.Run(cell={}, config={}, mix={}, seed=0, seconds=1.0,
                      trace=False, t_process=0.0)
    assert reader.read(run) is None


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
        moved = next(x for x in BENCH["end_to_end"]
                     if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    assert len(METRICS) == len({m["name"] for m in METRICS})


def test_configs_are_used_and_named_files():
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] \
            == c["name"]


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())
