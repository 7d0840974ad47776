"""The trace reduction, on hand-made intervals and on a trace recorded on a
TPU v5e (``data/burst_small.xplane.pb.gz``, made by ``record_trace.py``;
``data/burst_small.json`` holds that run's result line)."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import peaks, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(HERE), "data")
RECORDED = os.path.join(DATA, "burst_small.xplane.pb.gz")


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_clip_to_window():
    assert trace.clip([(0, 2), (3, 9), (10, 12)], 1, 10) == [(1, 2), (3, 9)]


def test_idle_gaps_by_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.scheduler", 10, 60),
             ("bench.effects", 40, 60), ("bench.controllers", 60, 100)]
    gaps = trace.idle_gaps([(20, 30)], 0, 100, spans)
    assert dict(gaps) == {"bench.none": 10, "bench.scheduler": 20,
                          "bench.effects": 20, "bench.controllers": 40}
    assert gaps[0] == ("bench.controllers", 40)


def test_module_names():
    assert trace.module_name("jit_solve_allocate_delta(123)") == \
        "solve_allocate_delta"
    assert trace.module_name("fusion.3") == "fusion.3"


def test_peaks_known_and_unknown():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded chip trace in data/")
    with open(os.path.join(DATA, "burst_small.json")) as f:
        result = json.load(f)
    return trace.reduce(trace.load(RECORDED), result["notes"]["turns"]), \
        result


def test_recorded_trace_matches_the_run(recorded):
    red, result = recorded
    assert red["busy_s"] == pytest.approx(result["device"]["busy_s"])
    assert red["window_s"] == pytest.approx(result["device"]["window_s"])
    assert result["metrics"]["device_idle_share.burst"]["value"] == \
        pytest.approx(red["idle_share"] * 100)


def test_recorded_trace_is_sane(recorded):
    red, _ = recorded
    assert 0 < red["busy_s"] < red["window_s"]
    assert any("solve_allocate" in k for k in red["module_s"])
    assert sum(red["module_s"].values()) <= red["window_s"]
    idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert all(n.startswith("bench.")
               for n, _ in red["breakdown"]["idle_gaps"])
