"""The ``mesh100k-burst`` cell (configuration ``limits5k``, traffic
``mesh_burst``) through the benchmark's harness at a small size on the
virtual 8-device CPU mesh, with the look for a chip skipped: a sound run
is correct and solves on the sharded arena, and each fault planted under
the sharded solve (``benchmark/lib/mesh_faults.py``) makes ``correct``
false through the count it breaks.

The cell keeps its conf (allocate pinned to ``mode: sharded``), its node
and pod templates, queues and prefill shape; nodes, prefill and the wave
are cut to 64 nodes, 64 ten-pod jobs and 170 gangs of 1/3/12 pods (equal
thirds of the pods, as the cell's 5/30/250).
"""

from __future__ import annotations

import copy
import os
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import harness, mesh_faults  # noqa: E402

CELL = "mesh100k-burst"


def small_cell(drain_s: float):
    cell = harness.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["nodes"]["count"] = 64
    cell.config["prefill"]["count"] = 64
    cell.traffic.update({
        "wave_jobs": 170, "drain_s": drain_s,
        "jobs": {"sizes": [[1, 12], [3, 4], [12, 1]],
                 "pods": [["default", 1]], "min": "replicas"}})
    return cell


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def run(cache_dir, drain_s=15.0):
    return harness.run_cell(CELL, 2 ** 31 + 11, 3.0, False,
                            t_proc0=time.monotonic(), require_chip=False,
                            cell=small_cell(drain_s), cache_dir=cache_dir)


def test_sound_run_is_correct_on_the_sharded_arena(cache_dir, monkeypatch):
    arenas = []
    turn = harness.Cluster.turn

    def record(self, log):
        tr = turn(self, log)
        if "dispatch_ms" in tr.timing:
            arenas.append((tr.timing.get("arena_mode"),
                           tr.timing.get("mesh_devices")))
        return tr

    monkeypatch.setattr(harness.Cluster, "turn", record)
    out = run(cache_dir)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["notes"]["window_compiles"] == 0
    assert set(out["metrics"]) == {"binds_per_s", "setup_s"}
    assert arenas and set(arenas) == {("sharded", 8.0)}


@pytest.mark.parametrize("fault,count", [
    ("solve_nothing", "never_started"),
    ("solve_to_node0", "never_started"),
])
def test_mesh_fault_makes_correct_false(fault, count, cache_dir):
    with mesh_faults.planted(fault):
        out = run(cache_dir, drain_s=2.0)
    assert not out["correct"]
    assert out["checks"][count]["value"] > out["checks"][count]["limit"]
