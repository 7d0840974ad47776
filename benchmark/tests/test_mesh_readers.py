"""The ``.mesh`` readers (cell ``mesh100k-burst``) on a synthetic run: each
reads its keys from the turn records and the reduced trace, the device
time is divided by the mesh width the program counted, and a program
that records none of the mesh counters (the sharded path before it
counted them) reads None where the reader needs them."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

TRACE = {"module_s": {"solve_allocate_sharded_arena": 0.8,
                      "_scatter_keep": 0.1},
         "idle_share": 0.9}


def _run(timings, binds=2000, trace=TRACE, compiles=0):
    turns = [harness.Turn(float(i), float(i) + 0.5, t)
             for i, t in enumerate(timings)]
    return harness.Run(seconds=1.0, setup_s=1.0, turns=turns, binds=binds,
                       attempted=1, failed=0, compiles=compiles,
                       trace=trace)


SOLVE = {"volcano.allocate.dispatch": 3.0, "volcano.allocate.readback": 5.0,
         "dispatch_ms": 3.0, "readback_ms": 5.0,
         "volcano.controllers": 400.0, "volcano.session.open": 60.0,
         "open_ms": 60.0, "flatten_ms": 30.0, "order_ms": 10.0,
         "volcano.allocate.replay": 90.0, "replay_ms": 90.0,
         "volcano.bind.write": 70.0, "pod_wait_ms_sum": 9000.0,
         "pod_wait_n": 2000.0}
MESH = dict(SOLVE, solve_rounds=6.0, mesh_devices=4.0,
            shard_bytes_max=300000.0, shard_bytes_total=1000000.0)
QUIET = {"volcano.controllers": 200.0, "volcano.session.open": 40.0,
         "open_ms": 40.0}

CHANGE = _run([MESH, QUIET, dict(MESH, solve_rounds=4.0)])
PARENT = _run([SOLVE, QUIET, SOLVE])

# per 1,000 of the 2,000 pods bound: half the window's sum
EXPECTED = {
    "solve_device_ms.mesh": 800.0 / 4 / 2,
    "solve_wait_ms.mesh": 8.0,
    "solve_rounds.mesh": 5.0,
    "shard_ship_kb.mesh": 300.0,
    "device_idle_share.mesh": 90.0,
    "controllers_ms.mesh": 500.0,
    "open_ms.mesh": 80.0,
    "prep_ms.mesh": 40.0,
    "replay_ms.mesh": 90.0,
    "bind_write_ms.mesh": 70.0,
    "sched_wait_ms.mesh": 18000.0 / 4000.0,
    "window_compiles.mesh": 0.0,
}
NEEDS_MESH_COUNTERS = ("solve_device_ms.mesh", "solve_rounds.mesh",
                       "shard_ship_kb.mesh")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_mesh_run(name):
    assert harness.load_reader(name)(CHANGE) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_program_without_the_mesh_counters(name):
    got = harness.load_reader(name)(PARENT)
    if name in NEEDS_MESH_COUNTERS:
        assert got is None
    else:
        assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["solve_device_ms.mesh",
                                  "device_idle_share.mesh"])
def test_device_readers_need_a_trace(name):
    assert harness.load_reader(name)(_run([MESH], trace=None)) is None
