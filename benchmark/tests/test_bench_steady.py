"""A steady cell of a GPU cluster, built in code at a small size, through
the harness as ``run.py`` drives it, on the CPU with the look for a chip
skipped: two node pools (CPU-only nodes and nodes of 8 ``nvidia.com/gpu``),
whole-GPU gangs of 1 to 16 GPUs and a CPU-only job shape, lifetimes of 1 to
8 turns, a backlog topped up before every turn.

A sound run is correct, fails no job, binds and finishes jobs by their
lifetimes inside the window; a binder that sends every GPU pod to one GPU
node makes ``correct`` false through ``over_capacity``. ``gpu_cell`` takes
the chip's sizes as well (PERF.md §7 gives those the probe ran).

Run: ``python -m pytest benchmark/tests/test_bench_steady.py -q``.
"""

from __future__ import annotations

import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from lib import harness  # noqa: E402
from lib import reference as ref  # noqa: E402
from lib import traffic as gen  # noqa: E402

GPU = "nvidia.com/gpu"
CONF = """actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
    arguments:
      binpack.resources: nvidia.com/gpu
      binpack.resources.nvidia.com/gpu: 2
"""


def _gpu_pod(n: int) -> dict:
    return {"cpu": str(2 * n), "memory": f"{16 * n}Gi", GPU: str(n)}


def gpu_cell(gpu_nodes=10, cpu_nodes=10, sizes=((1, 4), (2, 2)),
             gpus=((1, 4), (2, 2), (4, 2), (8, 1)), cpu_share=2,
             lifetimes=((1, 4), (2, 3), (4, 2), (8, 1)), backlog=12,
             block=40, lead_in=3, drain_s=20.0) -> harness.Cell:
    """A cell of the steady mode: ``gpu_nodes`` nodes of 8 GPUs after
    ``cpu_nodes`` CPU-only ones; ``sizes`` and ``lifetimes`` as
    ``[value, weight]``; ``gpus`` the GPUs a pod asks for with weights,
    beside a CPU-only pod shape of weight ``cpu_share``."""
    pods = {f"g{n}": _gpu_pod(n) for n, _ in gpus}
    mix = [[f"g{n}", w] for n, w in gpus]
    if cpu_share:
        pods["c"] = {"cpu": "2", "memory": "4Gi"}
        mix.append(["c", cpu_share])
    nodes = [{"count": cpu_nodes, "cpu": "40", "memory": "256Gi",
              "pods": "110"},
             {"count": gpu_nodes, "cpu": "64", "memory": "512Gi",
              "pods": "110", GPU: "8"}]
    config = {"name": "gpu-steady", "nodes": nodes, "pods": pods,
              "queues": [["q0", 1], ["q1", 2], ["q2", 3]],
              "priority_classes": {}, "schedule_period_s": 0,
              "kubelet_grace_s": 0, "scheduler_conf": CONF}
    traffic = {"mode": "steady", "block_jobs": block,
               "backlog_jobs": backlog, "lead_in_turns": lead_in,
               "drain_s": drain_s, "queues": ["q0", "q1", "q2"],
               "jobs": {"sizes": [list(s) for s in sizes],
                        "pods": mix,
                        "lifetime_turns": [list(x) for x in lifetimes],
                        "min": "replicas"}}
    ends = harness.load_cell("basic5k-burst").end_to_end
    return harness.Cell("gpu-steady", 1, config, traffic, ends, [])


def run(cache_dir, plant=None, seed=2 ** 31 + 23, cell=None):
    return harness.run_cell("gpu-steady", seed, 3.0, False,
                            t_proc0=time.monotonic(), require_chip=False,
                            cell=cell or gpu_cell(), plant=plant,
                            cache_dir=cache_dir)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def test_sound_steady_run_is_correct(cache_dir):
    out = run(cache_dir)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["notes"]["binds"] > 0
    assert out["notes"]["jobs_finished"] > 0
    assert out["notes"]["solved_cycles"] > 0
    assert set(out["metrics"]) == {"binds_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


class _GpuToOneNode:
    """Binds every pod that asks for a GPU to ``node``, which is full after
    its first eight GPUs."""

    def __init__(self, inner, node):
        self.inner = inner
        self.node = node

    def bind(self, pod, hostname):
        gpu = any(GPU in (c.get("requests") or {}) for c in pod.containers)
        self.inner.bind(pod, self.node if gpu else hostname)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_gpu_pods_on_a_full_gpu_node_are_over_capacity(cache_dir,
                                                       monkeypatch):
    cell = gpu_cell(drain_s=2.0)
    # GPU pods of one cpu and 2Gi: the node's 64 cpu and 512Gi hold all
    # that go there, and its GPUs overflow first
    for tpl in cell.config["pods"].values():
        if GPU in tpl:
            tpl.update(cpu="1", memory="2Gi")
    first_gpu_node = f"n{cell.config['nodes'][0]['count']}"
    seen = {}
    check, now = ref.check, ref.over_capacity_now

    def keep_log(nodes, jobs, log):
        seen.update(nodes=nodes, jobs=jobs, log=list(log))
        return check(nodes, jobs, log)

    def keep_final(nodes, jobs, bound):
        seen["bound"] = list(bound)
        return now(nodes, jobs, bound)

    def plant(cluster):
        cluster.sa.cache.binder = _GpuToOneNode(cluster.sa.cache.binder,
                                                first_gpu_node)

    monkeypatch.setattr(ref, "check", keep_log)
    monkeypatch.setattr(ref, "over_capacity_now", keep_final)
    out = run(cache_dir, plant=plant, cell=cell)
    assert not out["correct"]
    assert out["checks"]["over_capacity"]["value"] > 0

    def over(nodes, jobs):
        return (check(nodes, jobs, seen["log"])["over_capacity"]
                + now(nodes, jobs, seen["bound"]))

    # the GPUs overflow first: cpu, memory and pod slots alone count fewer
    flat = {j: ref.JobFacts(f.min_available, f.req[:3], f.priority,
                            f.measured) for j, f in seen["jobs"].items()}
    assert over({n: c[:3] for n, c in seen["nodes"].items()}, flat) \
        < over(seen["nodes"], seen["jobs"]) \
        == out["checks"]["over_capacity"]["value"]


def test_two_seeds_draw_the_same_multiset_per_block():
    traffic, pods = gpu_cell().traffic, gpu_cell().config["pods"]

    def shapes(seed, block):
        return collections.Counter(
            (d.size, tuple(sorted(d.requests.items())), d.lifetime_turns)
            for d in gen.steady_block(traffic, seed, block, pods))

    for block in range(3):
        assert shapes(7, block) == shapes(2 ** 31 + 23, block)
    assert [d.name for d in gen.steady_block(traffic, 7, 1, pods)][:2] \
        == ["s1-0", "s1-1"]
    order = [d.size for d in gen.steady_block(traffic, 7, 0, pods)]
    assert order != [d.size for d in gen.steady_block(traffic, 8, 0, pods)]
