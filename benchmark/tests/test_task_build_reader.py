"""The task build share reader, on a synthetic run: it reads the TaskInfos
the scheduler cache's pod handlers built per pod event they handled, and
reads None from a program that counts neither."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

NAME = "task_build_share"


def _run(timings):
    turns = [harness.Turn(float(i), float(i) + 0.5, t)
             for i, t in enumerate(timings)]
    return harness.Run(seconds=1.0, setup_s=1.0, turns=turns, binds=2000,
                       attempted=1, failed=0, compiles=0)


@pytest.mark.parametrize("timings,expected", [
    # a wave's creates build one each, its binds none
    ([{"total_ms": 100.0, "pod_events": 2000.0,
       "pod_task_builds": 1000.0}], 50.0),
    # a turn of binds alone builds nothing
    ([{"total_ms": 100.0, "pod_events": 2000.0,
       "pod_task_builds": 1000.0},
      {"total_ms": 80.0, "pod_events": 2000.0}], 25.0),
    # every event rebuilding, as a bind did before
    ([{"total_ms": 100.0, "pod_events": 1000.0,
       "pod_task_builds": 2000.0}], 200.0),
])
def test_reader_reads_the_turn_records(timings, expected):
    assert harness.load_reader(NAME)(_run(timings)) == pytest.approx(expected)


def test_reader_reads_nothing_from_a_program_without_the_counters():
    parent = _run([{"total_ms": 100.0, "volcano.controllers": 20.0,
                    "volcano.bind.write": 8.0, "pods_created": 1000.0}])
    assert harness.load_reader(NAME)(parent) is None


@pytest.mark.parametrize("cell", ["basic5k-burst", "mesh100k-burst"])
def test_reader_is_a_benchmark_entry_of_its_cell(cell):
    names = {m["name"] for m in harness.load_cell(cell).per_layer}
    assert NAME in names
