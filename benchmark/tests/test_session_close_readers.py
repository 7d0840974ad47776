"""The session close readers, on a synthetic run: they read the status
writes' span and the share of jobs written in the scheduler's thread, and
read None from a program that records neither."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import harness  # noqa: E402


def _run(timings):
    turns = [harness.Turn(float(i), float(i) + 0.5, t)
             for i, t in enumerate(timings)]
    return harness.Run(seconds=1.0, setup_s=1.0, turns=turns, binds=2000,
                       attempted=1, failed=0, compiles=0)


TRACED = _run([
    {"total_ms": 100.0, "volcano.session.close": 30.0,
     "volcano.session.close.update": 24.0, "close_update_ms": 24.0,
     "updater_jobs": 1900.0, "updater_inline": 1900.0},
    {"total_ms": 80.0, "volcano.session.close": 9.0,
     "volcano.session.close.update": 6.0, "close_update_ms": 6.0,
     "updater_jobs": 100.0, "updater_inline": 0.0},
])

# per 1,000 of the 2,000 pods bound: half the window's sum
EXPECTED = {
    "close_update_ms.preempt": 15.0,
    "close_update_ms": 15.0,
    "updater_inline_share": 95.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_turn_records(name):
    assert harness.load_reader(name)(TRACED) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_them(name):
    parent = _run([{"total_ms": 100.0, "volcano.session.close": 30.0,
                    "preempt_ms": 50.0, "preempt_solve_ms": 5.0}])
    assert harness.load_reader(name)(parent) is None


@pytest.mark.parametrize("name,cell", [
    ("close_update_ms.preempt", "preempt500-wave"),
    ("updater_inline_share", "preempt500-wave"),
    ("close_update_ms", "basic5k-burst"),
    ("close_update_ms", "mesh100k-burst"),
])
def test_reader_is_a_benchmark_entry_of_its_cell(name, cell):
    assert name in {m["name"] for m in harness.load_cell(cell).per_layer}
