"""The GPU cell's readers, on a synthetic run: the round budget hits and
variant misses the allocate solve counts, the share of task rows that took
the flatten's scalar overlay and the overlay's span; each reads None from a
program that records none of them."""

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
    {"total_ms": 100.0, "solve_rounds": 40.0, "round_cap_hits": 0.0,
     "variant_misses": 1.0, "task_rows": 300.0, "scalar_task_rows": 240.0,
     "volcano.allocate.flatten.scalar": 3.0},
    {"total_ms": 80.0, "solve_rounds": 70.0, "round_cap_hits": 2.0,
     "variant_misses": 0.0, "task_rows": 100.0, "scalar_task_rows": 80.0,
     "volcano.allocate.flatten.scalar": 1.0},
])

# per 1,000 of the 2,000 pods bound: half the window's sum
EXPECTED = {
    "round_cap_hits.gpu": 1.0,
    "variant_misses.gpu": 1.0,
    "scalar_row_share.gpu": 80.0,
    "scalar_overlay_ms.gpu": 2.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_turn_records(name):
    assert harness.load_reader(name)(TRACED) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_them(name):
    parent = _run([{"total_ms": 100.0, "solve_rounds": 64.0,
                    "volcano.allocate.flatten": 9.0}])
    assert harness.load_reader(name)(parent) is None


def test_a_sound_steady_solve_reads_zero():
    run = _run([{"round_cap_hits": 0.0, "variant_misses": 0.0}] * 3)
    for name in ("round_cap_hits.gpu", "variant_misses.gpu"):
        assert harness.load_reader(name)(run) == 0.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_a_benchmark_entry_of_the_gpu_cell(name):
    cell = harness.load_cell("gpu-philly-steady")
    assert name in {m["name"] for m in cell.per_layer}
    assert "binds_per_s" in {m["name"] for m in cell.end_to_end}
