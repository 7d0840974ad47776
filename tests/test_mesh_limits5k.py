"""The ``limits5k`` deployment's scheduler conf on the node-sharded path,
at a small size on the virtual 8-device CPU mesh: 64 nodes of
node-default (4 cpu, 32Gi, 110 pods), pods of pod-default (100m, 500Mi),
gangs of 1, 3 and 12 pods in equal thirds of the pods (CL2's 5/30/250 cut
down), queues q0/q1/q2 of weight 1/2/3.

- The sharded solve (``mode: sharded``, the conf's pin) makes the packed
  solve's binds, cycle by cycle, with ``proportion.workConserving`` true
  and false, in a cluster where q0's cap binds: a q2 job that no node can
  hold keeps q2's deserved share unused, so only the work-conserving
  overflow may hand it to q0.
- Where no cap binds, the sharded run binds the host oracle's pods
  (``mode: host``) every cycle.
- Sharded solving turns count ``solve_rounds``, ``mesh_devices`` and the
  shard bytes; packed turns count no ``mesh_devices``.
"""

from __future__ import annotations

import json
import os

import pytest

from helpers import build_node, build_pod, build_pod_group, build_queue

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_NODES = 64
SIZES = (1, 3, 12)


def limits5k_conf(mode: str, work_conserving: bool = True) -> str:
    """The configuration's scheduler conf with the allocate mode pinned to
    ``mode`` and proportion's ``workConserving`` set."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "limits5k.json")) as f:
        conf = json.load(f)["scheduler_conf"]
    assert "    mode: sharded\n" in conf
    conf = conf.replace("    mode: sharded\n", f"    mode: {mode}\n")
    if not work_conserving:
        conf = conf.replace(
            "  - name: proportion\n",
            "  - name: proportion\n    arguments:\n"
            "      proportion.workConserving: false\n")
    return conf


class Cluster:
    def __init__(self, conf: str):
        from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
        from volcano_tpu.client import ClusterStore
        from volcano_tpu.scheduler import Scheduler

        self.store = ClusterStore()
        self.cache = SchedulerCache(self.store)
        self.cache.binder = FakeBinder()
        self.cache.evictor = FakeEvictor()
        self.cache.run()
        for i, w in enumerate((1, 2, 3)):
            self.store.apply("queues", build_queue(f"q{i}", weight=w))
        for i in range(N_NODES):
            self.store.create("nodes", build_node(
                f"n{i}", {"cpu": "4", "memory": "32Gi"}))
        self.sched = Scheduler(self.cache, scheduler_conf=conf)
        self.k = 0
        self.timings = []

    def job(self, queue: str, size: int, cpu: str = "100m") -> None:
        name = f"j{self.k}"
        self.k += 1
        self.store.create("podgroups", build_pod_group(
            name, "t", min_member=size, queue=queue))
        for i in range(size):
            self.store.create("pods", build_pod(
                "t", f"{name}-{i}", "", "Pending",
                {"cpu": cpu, "memory": "500Mi"}, name))

    def wave(self, queue: str, pods: int) -> None:
        """``pods`` pods of ``queue`` in equal thirds over the gang
        sizes."""
        for size in SIZES:
            for _ in range(pods // 3 // size):
                self.job(queue, size)

    def cycle(self):
        self.sched.run_once()
        self.timings.append(dict(self.sched.last_cycle_timing))
        return sorted(self.cache.binder.binds.items())


def capped_script(c: Cluster):
    """q0 asks for 2,520 pods (252 cpu) and q1 for 180 of the 256 cpu
    there is; q2's one job cannot fit a node, so its deserved share sits
    unused."""
    c.job("q2", 1, cpu="5")
    c.wave("q0", 1800)
    yield c.cycle()
    c.wave("q1", 180)
    c.wave("q0", 720)
    yield c.cycle()
    yield c.cycle()


def open_script(c: Cluster):
    """Every queue's asks fit the cluster: no cap binds."""
    for q in ("q0", "q1", "q2"):
        c.wave(q, 360)
    yield c.cycle()
    c.wave("q1", 360)
    c.wave("q2", 180)
    yield c.cycle()
    yield c.cycle()


def run(script, mode: str, work_conserving: bool = True):
    c = Cluster(limits5k_conf(mode, work_conserving))
    return list(script(c)), c


@pytest.mark.parametrize("work_conserving", [True, False],
                         ids=["work_conserving", "strict"])
def test_sharded_equals_packed_with_a_binding_cap(work_conserving):
    sharded, cs = run(capped_script, "sharded", work_conserving)
    packed, _ = run(capped_script, "solver", work_conserving)
    assert sharded == packed
    assert all(t.get("arena_mode") == "sharded" for t in cs.timings
               if "dispatch_ms" in t)
    # 2,700 pods ask for 2,560 slots
    assert 2400 < len(sharded[-1]) <= 2560


def test_strict_mode_binds_less_than_work_conserving():
    """The strict cap holds q0 below what the overflow gives it."""
    strict, _ = run(capped_script, "sharded", False)
    conserving, _ = run(capped_script, "sharded", True)
    assert len(strict[-1]) < len(conserving[-1])


def test_sharded_binds_the_host_oracles_pods_where_no_cap_binds():
    sharded, _ = run(open_script, "sharded")
    host, _ = run(open_script, "host")
    assert [{p for p, _ in s} for s in sharded] \
        == [{p for p, _ in h} for h in host]
    assert len(sharded[-1]) == 1620


@pytest.mark.parametrize("mode", ["sharded", "solver"])
def test_mesh_counters_only_on_sharded_turns(mode):
    _, c = run(open_script, mode)
    solved = [t for t in c.timings if "dispatch_ms" in t]
    assert solved
    for t in solved:
        assert t["solve_rounds"] >= 1
        if mode == "sharded":
            assert t["mesh_devices"] >= 2
            assert t["shard_bytes_max"] > 0
            assert t["shard_bytes_total"] >= t["shard_bytes_max"]
        else:
            assert not {"mesh_devices", "shard_bytes_max",
                        "shard_bytes_total"} & set(t)
