"""The readers of the program's spans and counters, on a synthetic run: each
reads its keys from the turn records, and reads None from a program that
records none of them."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import harness  # noqa: E402


def _run(timings, binds=2000):
    turns = [harness.Turn(float(i), float(i) + 0.5, t)
             for i, t in enumerate(timings)]
    return harness.Run(seconds=1.0, setup_s=1.0, turns=turns, binds=binds,
                       attempted=1, failed=0, compiles=0)


TRACED = _run([
    {"total_ms": 100.0, "volcano.controllers": 40.0,
     "volcano.bind.write": 12.0, "pod_wait_ms_sum": 3000.0,
     "pod_wait_n": 1000.0, "solve_rounds": 6.0,
     "volcano.preempt.collect": 5.0, "volcano.preempt.flatten": 7.0,
     "volcano.preempt.victims": 9.0, "volcano.preempt.replay": 30.0,
     "volcano.preempt.intra_job": 2.0, "evict_claimers": 500.0,
     "evict_scan_steps": 640.0},
    {"total_ms": 80.0, "volcano.controllers": 20.0,
     "volcano.bind.write": 8.0, "pod_wait_ms_sum": 1000.0,
     "pod_wait_n": 1000.0, "solve_rounds": 4.0},
])

# per 1,000 of the 2,000 pods bound: half the window's sum
EXPECTED = {
    "controllers_ms.burst": 30.0,
    "controllers_ms.preempt": 30.0,
    "bind_write_ms.burst": 10.0,
    "sched_wait_ms.burst": 2.0,
    "solve_rounds.burst": 5.0,
    "evict_prep_ms.preempt": 10.5,
    "evict_replay_ms.preempt": 16.0,
    "evict_step_use.preempt": 78.125,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_turn_records(name):
    assert harness.load_reader(name)(TRACED) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_spans(name):
    parent = _run([{"total_ms": 100.0, "preempt_ms": 50.0,
                    "preempt_solve_ms": 5.0}])
    assert harness.load_reader(name)(parent) is None


def test_each_reader_is_a_benchmark_entry_of_its_cell():
    for name in EXPECTED:
        cell = "basic5k-burst" if name.endswith(".burst") \
            else "preempt500-wave"
        assert name in {m["name"] for m in harness.load_cell(cell).per_layer}
