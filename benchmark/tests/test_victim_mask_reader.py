"""The victim mask share reader, on a synthetic run: it reads the share of
the evict solve's claimer rows that the column forms decided alone, and
reads None from a program that counts no rows."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

NAME = "victim_mask_share.preempt"


def _run(timings):
    turns = [harness.Turn(float(i), float(i) + 0.5, t)
             for i, t in enumerate(timings)]
    return harness.Run(seconds=1.0, setup_s=1.0, turns=turns, binds=2000,
                       attempted=1, failed=0, compiles=0)


@pytest.mark.parametrize("timings,expected", [
    ([{"total_ms": 100.0, "victim_rows": 500.0,
       "victim_rows_masked": 500.0}], 100.0),
    ([{"total_ms": 100.0, "victim_rows": 500.0,
       "victim_rows_masked": 500.0},
      {"total_ms": 80.0, "victim_rows": 300.0,
       "victim_rows_masked": 0.0}], 62.5),
    ([{"total_ms": 100.0, "victim_rows": 400.0,
       "victim_rows_masked": 0.0}], 0.0),
])
def test_reader_reads_the_turn_records(timings, expected):
    assert harness.load_reader(NAME)(_run(timings)) == pytest.approx(expected)


def test_reader_reads_nothing_from_a_program_without_the_counters():
    parent = _run([{"total_ms": 100.0, "preempt_ms": 50.0,
                    "preempt_solve_ms": 5.0, "evict_claimers": 500.0}])
    assert harness.load_reader(NAME)(parent) is None


def test_reader_is_a_benchmark_entry_of_its_cell():
    names = {m["name"] for m in harness.load_cell("preempt500-wave").per_layer}
    assert NAME in names
