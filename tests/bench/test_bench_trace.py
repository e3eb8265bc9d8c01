"""The benchmark's trace reduction (bench/trace.py): busy union, idle
share and gap attribution, on synthetic intervals and on a small trace
recorded on a TPU v5e (bench/record_testdata.py)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "trace_small.xplane.pb"


def test_union_merges_and_clips():
    got = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 14)], 1, 13)
    assert got == [(1, 3), (5, 9), (12, 13)]


def test_gaps_are_the_complement():
    assert trace.gaps([(1, 3), (5, 9)], 0, 10) == [(0, 1), (3, 5), (9, 10)]
    assert trace.gaps([], 2, 4) == [(2, 4)]


def test_label_is_the_innermost_span():
    spans = [("bench.window", 0, 10), ("bench.flush", 1, 6),
             ("bench.score_stack", 2, 3)]
    assert trace.label_at(spans, 2.5) == "bench.score_stack"
    assert trace.label_at(spans, 5) == "bench.flush"
    assert trace.label_at(spans, 8) == "outside"


def test_op_name_is_the_instruction_name():
    assert trace.op_name("%while.19 = (s32[]) while(%t), body=%b") \
        == "while.19"
    assert trace.op_name("fusion") == "fusion"


def test_reduce_synthetic():
    t = trace.Trace(
        device_ops={"/device:TPU:0": [("fusion", 1.0, 2.0),
                                      ("fusion", 1.5, 2.5),
                                      ("copy", 6.0, 7.0),
                                      ("late", 11.0, 12.0)]},
        host_spans=[("bench.window", 0.0, 10.0),
                    ("bench.flush", 2.5, 6.0)])
    out = trace.reduce(t)
    assert out["busy_s"] == pytest.approx(2.5)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["idle_share"] == pytest.approx(0.75)
    assert out["device_ops"][0] == ["fusion", pytest.approx(2.0)]
    assert [g[0] for g in out["idle_gaps"]] == ["bench.flush", "outside",
                                                 "outside"]
    assert [g[1] for g in out["idle_gaps"]] == pytest.approx([3.5, 3, 1])


def test_reduce_refuses_a_window_without_device_work():
    t = trace.Trace(device_ops={"/device:TPU:0": [("x", 20.0, 21.0)]},
                    host_spans=[("bench.window", 0.0, 10.0)])
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_reduce_recorded_chip_trace():
    """Five steps of a 20 ms host sleep then a 1024^2 matmul: the device
    is mostly idle, the longest gaps fall in the host spans, and the
    matmul is the top operation."""
    out = trace.reduce(trace.read_xplane(str(RECORDED)))
    assert out["devices"] == 1
    assert 0.1 < out["window_s"] < 1.0
    assert 0 < out["busy_s"] < 0.25 * out["window_s"]
    assert 0.75 < out["idle_share"] < 1.0
    assert sum(t for _, t in out["device_ops"]) <= out["busy_s"] + 1e-9
    assert out["device_ops"][0][0] == "convolution_tanh_fusion"
    labels = [g[0] for g in out["idle_gaps"][:5]]
    assert labels.count("bench.host") >= 4
    assert all(g[1] >= 0.015 for g in out["idle_gaps"][:5])
