"""Named resources in the harness and the reference.

- The existing cells draw the same jobs and build the same nodes as before
  node pools and extended resources came in: the digests below were
  computed with this file's own canonical forms from the commit before
  that change (``JobDraw.cpu``/``.memory`` read as ``requests``), for the
  prefill and waves 0 to 2 of each cell and for its node objects, at two
  seeds.
- A GPU over-booked on a node is ``over_capacity`` where cpu and memory
  alone fit, and an eviction that freed GPUs feeds ``over_evicted``.
- Pools name their nodes with one index, and an extended resource goes
  into a container's requests and its limits.

Run: ``python -m pytest benchmark/tests/test_bench_resources.py -q``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from lib import harness  # noqa: E402
from lib import reference as ref  # noqa: E402
from lib import traffic as gen  # noqa: E402

GPU = "nvidia.com/gpu"

# (jobs digest, nodes digest) of the commit before named resources
PINNED = {
    ("basic5k-burst", 7): ("2dc78d4988de20bb", "6851842b1ed4e552"),
    ("basic5k-burst", 2 ** 31 + 11): ("2dc78d4988de20bb",
                                      "6851842b1ed4e552"),
    ("preempt500-wave", 7): ("51163de6118aa3cf", "4ddf3a2df1d988ee"),
    ("preempt500-wave", 2 ** 31 + 11): ("51163de6118aa3cf",
                                        "4ddf3a2df1d988ee"),
    ("mesh100k-burst", 7): ("c0a40743a493ba19", "6851842b1ed4e552"),
    ("mesh100k-burst", 2 ** 31 + 11): ("596b66b0730fa736",
                                       "6851842b1ed4e552"),
}


class _Store:
    def __init__(self):
        self.nodes = []

    def create(self, kind, obj):
        if kind == "nodes":
            self.nodes.append(obj)

    def watch(self, *a, **kw):
        pass


class _ControlPlane:
    """Stands in for ``Standalone``: keeps the nodes the cluster makes."""

    def __init__(self, **kw):
        self.store = _Store()
        self.controllers = type("C", (), {"controllers": []})()


def build_cluster(config, monkeypatch) -> harness.Cluster:
    import volcano_tpu.standalone as standalone

    monkeypatch.setattr(standalone, "Standalone", _ControlPlane)
    return harness.Cluster(config, harness.Watcher({}))


def job_canon(d: gen.JobDraw) -> list:
    job = harness._job_object(d)
    task = job.spec.tasks[0]
    return [d.name, d.size, d.min_available, dict(d.requests), d.queue,
            d.priority_class, task.replicas, task.template,
            job.spec.min_available, job.spec.queue,
            job.spec.priority_class_name]


def node_canon(node) -> dict:
    o = dataclasses.asdict(node)
    o.pop("uid")
    o.pop("resource_version")
    return o


def digests(workload, seed, monkeypatch):
    cell = harness.load_cell(workload)
    pods = cell.config["pods"]
    jobs = gen.prefill_jobs(cell.config["prefill"], seed, "f", pods)
    for i in range(3):
        jobs += gen.wave(cell.traffic, seed, i, pods)
    h = hashlib.sha256()
    for d in jobs:
        h.update(json.dumps(job_canon(d), sort_keys=True).encode())
    cluster = build_cluster(cell.config, monkeypatch)
    g = hashlib.sha256()
    for n in cluster.sa.store.nodes:
        g.update(json.dumps([node_canon(n), list(cluster.nodes[n.name])],
                            sort_keys=True).encode())
    return h.hexdigest()[:16], g.hexdigest()[:16]


@pytest.mark.parametrize("workload,seed", sorted(PINNED))
def test_existing_cells_draw_and_build_what_they_did(workload, seed,
                                                      monkeypatch):
    assert digests(workload, seed, monkeypatch) == PINNED[workload, seed]


# -- the reference on a GPU node ----------------------------------------------

NAMES = ref.resource_names([GPU])
NODES = {"g0": (40.0, 512.0 * 2 ** 30, 110.0, 8.0)}


def gpu_job(gpus, prio=0, measured=True):
    return ref.JobFacts(1, ref.request_vector(
        {"cpu": "4", "memory": "32Gi", GPU: str(gpus)}, NAMES),
        prio, measured)


def test_resource_vector_order():
    assert NAMES == ("cpu", "memory", "pods", GPU)
    assert ref.resource_names(["b/x", "a/y", "cpu"]) == (
        "cpu", "memory", "pods", "a/y", "b/x")
    assert gpu_job(8).req == (4.0, 32.0 * 2 ** 30, 1.0, 8.0)
    assert ref.request_vector({"cpu": "1"}, NAMES) == (1.0, 0.0, 1.0, 0.0)


def test_two_eight_gpu_pods_on_one_eight_gpu_node_are_over_capacity():
    jobs = {"a": gpu_job(8), "b": gpu_job(8)}
    log = [(ref.ADD, "a-task-0", "", 0.0), (ref.ADD, "b-task-0", "", 0.0),
           (ref.BIND, "a-task-0", "g0", 0.0),
           (ref.BIND, "b-task-0", "g0", 0.0), (ref.TURN, 0.0)]
    assert ref.check(NODES, jobs, log)["over_capacity"] == 1
    assert ref.over_capacity_now(NODES, jobs, [("a-task-0", "g0"),
                                               ("b-task-0", "g0")]) == 1
    # cpu, memory and pod slots alone hold both pods
    cpu_only = {n: c[:3] for n, c in NODES.items()}
    flat = {j: ref.JobFacts(1, f.req[:3], 0, True) for j, f in jobs.items()}
    assert ref.check(cpu_only, flat, log)["over_capacity"] == 0
    assert ref.over_capacity_now(cpu_only, flat, [("a-task-0", "g0"),
                                                  ("b-task-0", "g0")]) == 0


@pytest.mark.parametrize("want,over", [(4, 1), (8, 0)])
def test_eviction_that_freed_gpus_feeds_over_evicted(want, over):
    """Two low pods of 4 GPUs fill g0; both are evicted and a high pod of
    ``want`` GPUs binds there. With one victim kept, 4 GPUs would have
    held it and 8 would not; cpu and memory hold it either way."""
    jobs = {"lo0": gpu_job(4, measured=False),
            "lo1": gpu_job(4, measured=False), "hi": gpu_job(want, 10)}
    log = []
    for i in range(2):
        log += [(ref.ADD, f"lo{i}-task-0", "", 0.0),
                (ref.BIND, f"lo{i}-task-0", "g0", 0.0)]
    log += [(ref.TURN, 0.0), (ref.ADD, "hi-task-0", "", 0.0)]
    for i in range(2):
        log += [(ref.MARK, f"lo{i}-task-0", "g0", 0.0),
                (ref.DELETE, f"lo{i}-task-0", "", 0.0)]
    log += [(ref.TURN, 0.0), (ref.BIND, "hi-task-0", "g0", 0.0),
            (ref.TURN, 0.0)]
    out = ref.check(NODES, jobs, log)
    assert out["over_evicted"] == over
    assert out["over_capacity"] == 0 and out["evict_priority"] == 0


# -- the harness ---------------------------------------------------------------

POOLS = {"nodes": [{"count": 2, "cpu": "40", "memory": "256Gi",
                    "pods": "110"},
                   {"count": 3, "cpu": "40", "memory": "512Gi",
                    "pods": "110", GPU: "8"}],
         "pods": {"g": {"cpu": "4", "memory": "32Gi", GPU: "1"},
                  "c": {"cpu": "2", "memory": "4Gi",
                        "priority_class": "low"}},
         "scheduler_conf": "", "schedule_period_s": 0, "kubelet_grace_s": 0}


def test_pools_name_nodes_with_one_index_and_carry_every_key(monkeypatch):
    cluster = build_cluster(POOLS, monkeypatch)
    nodes = cluster.sa.store.nodes
    assert [n.name for n in nodes] == ["n0", "n1", "n2", "n3", "n4"]
    assert cluster.resources == NAMES
    assert GPU not in nodes[1].allocatable
    assert nodes[2].allocatable == nodes[2].capacity == {
        "cpu": "40", "memory": "512Gi", "pods": "110", GPU: "8"}
    assert nodes[2].allocatable is not nodes[3].allocatable
    assert cluster.nodes["n1"] == (40.0, 256.0 * 2 ** 30, 110.0, 0.0)
    assert cluster.nodes["n4"] == (40.0, 512.0 * 2 ** 30, 110.0, 8.0)


def test_extended_resource_is_a_request_and_a_limit():
    mix = {"sizes": [[2, 1]], "pods": [["g", 1], ["c", 1]]}
    jobs = gen.draw_jobs(random.Random(1), mix, 2, "j", [],
                         POOLS["pods"])
    by_pod = {d.requests["cpu"]: d for d in jobs}
    g, c = by_pod["4"], by_pod["2"]
    assert g.requests == {"cpu": "4", "memory": "32Gi", GPU: "1"}
    assert c.requests == {"cpu": "2", "memory": "4Gi"}
    assert c.priority_class == "low"
    (gc,) = harness._job_object(g).spec.tasks[0].template["spec"][
        "containers"]
    assert gc["requests"] == g.requests and gc["limits"] == {GPU: "1"}
    (cc,) = harness._job_object(c).spec.tasks[0].template["spec"][
        "containers"]
    assert "limits" not in cc


def test_prefill_fits_by_every_resource(monkeypatch):
    cluster = build_cluster(POOLS, monkeypatch)
    binds = []
    cluster.store.get = lambda kind, name, ns: type(
        "P", (), {"node_name": "", "phase": ""})()
    cluster.store.update = lambda kind, pod: binds.append(pod.node_name)
    mix = {"sizes": [[4, 1]], "pods": [["g", 1]]}
    jobs = gen.draw_jobs(random.Random(1), mix, 6, "f", [],
                         POOLS["pods"])
    cluster.bind_directly(jobs, stripe=False, fill=False)
    # 24 one-GPU pods fill the three GPU nodes; none lands on n0 or n1
    assert len(binds) == 24 and set(binds) == {"n2", "n3", "n4"}
    with pytest.raises(harness.CellError):
        cluster.bind_directly(jobs[:1], stripe=False, fill=False)


def test_unknown_traffic_mode_is_refused():
    cell = harness.load_cell("basic5k-burst")
    cell.traffic = dict(cell.traffic, mode="open")
    with pytest.raises(harness.CellError):
        harness.run_cell(cell.name, 1, 1.0, False, t_proc0=0.0,
                         require_chip=False, cell=cell)
