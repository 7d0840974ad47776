"""The harness end to end on the CPU at a tiny size, with the look for a
chip skipped: a sound run is correct, and each fault planted under the
timed path (``lib/faults.py``) makes ``correct`` false through the count
it breaks.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import copy
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from lib import faults, harness  # noqa: E402

SMALL = {
    "basic5k-burst": ({"count": 60}, 10, {"wave_jobs": 30, "drain_s": 15}),
    "preempt500-wave": ({"count": 12}, 48, {"wave_jobs": 12,
                                            "drain_s": 10}),
}


def small_cell(name):
    nodes, prefill, traffic = SMALL[name]
    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["nodes"].update(nodes)
    cell.config["prefill"]["count"] = prefill
    cell.traffic.update(traffic)
    return cell


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def run(name, cache_dir, fault=None, seconds=3.0):
    cell = small_cell(name)
    if fault is None:
        return harness.run_cell(name, 2 ** 31 + 11, seconds, False,
                                t_proc0=time.monotonic(),
                                require_chip=False, cell=cell,
                                cache_dir=cache_dir)
    with faults.planted(fault) as plant:
        return harness.run_cell(name, 2 ** 31 + 11, seconds, False,
                                t_proc0=time.monotonic(),
                                require_chip=False, cell=cell, plant=plant,
                                cache_dir=cache_dir)


@pytest.mark.parametrize("name", list(SMALL))
def test_sound_run_is_correct(name, cache_dir):
    out = run(name, cache_dir)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["notes"]["solved_cycles"] > 0
    want = {e["name"] for e in harness.load_cell(name).end_to_end}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name,fault,count", [
    ("basic5k-burst", "bind_one_node", "over_capacity"),
    ("basic5k-burst", "solve_nothing", "never_started"),
    ("basic5k-burst", "solve_to_node0", "never_started"),
    ("preempt500-wave", "bind_one_node", "over_capacity"),
    ("preempt500-wave", "evict_nothing", "never_started"),
    ("preempt500-wave", "evict_extra", "over_evicted"),
])
def test_fault_makes_correct_false(name, fault, count, cache_dir):
    out = run(name, cache_dir, fault)
    assert not out["correct"]
    assert out["checks"][count]["value"] > out["checks"][count]["limit"]
