"""Drive the scheduler's main path once on the chip, and check what it did.

The quickest proof that the system still starts on a TPU. One process, no
children: the chip belongs to one process at a time.

Phases (``python chip_smoke.py``, one chip):

a. device check — the platform must be ``tpu``;
b. main path at the north-star size (BASELINE.json config 3): a
   ``Standalone`` control plane (store, admission, controllers, effectors,
   ``Scheduler``) with 2,000 nodes and 1,000 ten-pod gang Jobs in three
   weighted queues, cycled until every pod is bound. Checks capacity,
   gang atomicity, and that every solve ran on the device;
c. preempt wave (BASELINE.json config 4): 200 nodes running 2,000
   low-priority pods, then one 1,000-pod high-priority gang, with
   ``preempt`` in the actions. The gang must be placed by the evict
   kernel, not the host loop;
d. exactness: the sequential device kernel against the host allocate
   action, bind for bind, at 500 pods / 50 nodes.

``python chip_smoke.py --four-chips`` runs only phase b twice over the
same seeded cluster: node-axis sharded over every chip, then packed on one,
and asserts identical bind decisions with one node slab per device.

The last line of stdout is one JSON object naming the device; any failed
check or exception exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

MAIN_CONF = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: binpack
"""

# tests/test_e2e.py PREEMPT_CONF: nodeorder spreads the victims ten to a
# node, as BASELINE config 4 lays them out (under binpack they pack, and
# the replacement pods re-take the freed room every cycle); the
# overcommit-factor lets the gang's MinResources pass the enqueue gate on
# a cluster its victims already fill
PREEMPT_CONF = """
actions: "enqueue, allocate, preempt, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: predicates
  - name: proportion
  - name: nodeorder
configurations:
- name: enqueue
  arguments:
    overcommit-factor: 2.0
"""


class SmokeFailure(Exception):
    """A check of the smoke failed (raised, never asserted: ``-O`` must
    not turn a check off)."""


def require(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# cluster construction: the objects a user submits
# ---------------------------------------------------------------------------

def _standalone(conf: str, **kw):
    from volcano_tpu.standalone import Standalone

    return Standalone(scheduler_conf=conf, metrics_port=0,
                      async_effectors=False, **kw)


def _add_nodes(store, n: int, cpu: str, mem: str) -> None:
    from volcano_tpu.models import Node

    for i in range(n):
        rl = {"cpu": cpu, "memory": mem, "pods": "110"}
        store.create("nodes", Node(name=f"n{i}", allocatable=rl,
                                   capacity=dict(rl)))


def _job(name: str, replicas: int, min_available: int, cpu: str, mem: str,
         queue: str = "default", priority_class: str = ""):
    """A Job as ``vcctl job run`` submits it: the job controller makes
    its podgroup and pods."""
    from volcano_tpu.models import Job, JobSpec, TaskSpec

    task = TaskSpec(name="task", replicas=replicas, template={"spec": {
        "containers": [{"name": "c",
                        "requests": {"cpu": cpu, "memory": mem}}]}})
    return Job(name=name, namespace="default", spec=JobSpec(
        min_available=min_available, tasks=[task], queue=queue,
        priority_class_name=priority_class))


def _pods(store, prefix: str = ""):
    return [p for p in store.list("pods")
            if p.name.startswith(prefix) and p.deletion_timestamp is None]


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_capacity(store) -> None:
    """No node holds more than its allocatable, by the pods' requests."""
    from volcano_tpu.api.resource import parse_quantity

    alloc = {n.name: n.allocatable for n in store.list("nodes")}
    used = {}
    for p in store.list("pods"):
        if not p.node_name:
            continue
        u = used.setdefault(p.node_name, {"cpu": 0.0, "memory": 0.0,
                                          "pods": 0.0})
        for c in p.containers:
            for k in ("cpu", "memory"):
                u[k] += parse_quantity(c.get("requests", {}).get(k, 0))
        u["pods"] += 1
    for node, u in used.items():
        for k, v in u.items():
            cap = parse_quantity(alloc[node][k])
            require(v <= cap * (1 + 1e-9),
                    f"node {node} over its allocatable {k}: {v} > {cap}")


def check_gangs(store, jobs) -> None:
    """Every job has 0 or at least minAvailable pods bound."""
    bound = {}
    for p in _pods(store):
        if p.node_name:
            job = p.name.rsplit("-", 2)[0]
            bound[job] = bound.get(job, 0) + 1
    for job in jobs:
        n = bound.get(job.name, 0)
        require(n == 0 or n >= job.spec.min_available,
                f"job {job.name}: {n} pods bound, minAvailable "
                f"{job.spec.min_available}")


def check_device_cycles(timings, arena_mode: str) -> int:
    """Every cycle that had pods to place solved them on the device:
    the solve resolved to the expected arena, dispatched and read back,
    and no action failed or fell back to the host oracle."""
    solved = 0
    for i, t in enumerate(timings):
        bad = [k for k in t if k == "host_fallback" or k == "breaker_open"
               or k.endswith("_error") or k.endswith("_timeout")]
        require(not bad, f"cycle {i}: {bad} in {t}")
        if "flatten_ms" not in t:
            continue  # nothing pending this cycle
        solved += 1
        require(t.get("arena_mode") == arena_mode,
                f"cycle {i}: arena_mode {t.get('arena_mode')!r}, "
                f"want {arena_mode!r}")
        for k in ("dispatch_ms", "readback_ms"):
            require(k in t, f"cycle {i}: no {k}: {t}")
    require(solved, "no cycle dispatched a solve")
    return solved


def check_breaker(sa) -> None:
    br = sa.cache.breaker
    require(br.state == "closed", f"breaker {br.state}")
    require(br.failures_total == 0,
            f"breaker recorded {br.failures_total} device failure(s)")
    require(br.fallback_cycles == 0,
            f"{br.fallback_cycles} cycle(s) served by the host fallback")


def _cycle_line(phase: str, i: int, t: dict, bound: int, wall: float):
    keys = ("total_ms", "flatten_ms", "dispatch_ms", "readback_ms",
            "replay_ms", "session_compiles", "session_compile_s",
            "preempt_solve_ms")
    fields = " ".join(f"{k}={t[k]}" for k in keys if k in t)
    log(f"[{phase}] cycle {i}: wall_s={wall} bound={bound} {fields}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_main(n_nodes: int = 2000, n_jobs: int = 1000, tpj: int = 10,
               solver_mode: str = "packed", seed: int = 0,
               max_cycles: int = 8) -> dict:
    """Phase b: the north-star cluster through the full control plane."""
    import numpy as np

    from volcano_tpu.models import Queue, QueueSpec

    arena = "sharded" if solver_mode == "sharded" else "packed"
    rng = np.random.default_rng(seed)
    sa = _standalone(MAIN_CONF, solver_mode=solver_mode)
    try:
        store = sa.store
        for q in range(3):
            store.create("queues", Queue(name=f"q{q}",
                                         spec=QueueSpec(weight=q + 1)))
        _add_nodes(store, n_nodes, "32", "128Gi")
        cpus = rng.integers(1, 4, n_jobs)
        mems = rng.integers(1, 5, n_jobs)
        jobs = [_job(f"j{k}", tpj, tpj, str(cpus[k]), f"{mems[k]}Gi",
                     queue=f"q{k % 3}") for k in range(n_jobs)]
        for job in jobs:
            store.create("jobs", job)
        want = n_jobs * tpj
        timings, stream = [], []
        for i in range(max_cycles):
            t0 = time.perf_counter()
            sa.run_once()
            wall = time.perf_counter() - t0
            t = dict(sa.scheduler.last_cycle_timing)
            timings.append(t)
            binds = sorted((p.name, p.node_name) for p in _pods(store)
                           if p.node_name)
            stream.append(binds)
            _cycle_line(f"main/{arena}", i, t, len(binds), wall)
            if len(binds) == want:
                break
        bound = len(stream[-1])
        require(bound == want,
                f"{bound}/{want} pods bound after {len(timings)} cycles")
        check_capacity(store)
        check_gangs(store, jobs)
        solved = check_device_cycles(timings, arena)
        check_breaker(sa)
        digest = hashlib.sha256(
            json.dumps(stream).encode()).hexdigest()[:16]
        log(f"[main/{arena}] {bound}/{want} pods bound on {n_nodes} nodes "
            f"in {len(timings)} cycles ({solved} solved on the device); "
            f"breaker closed, 0 failures; decisions {digest}")
        return {"bound": bound, "cycles": len(timings), "digest": digest,
                "sharded_device_cache": sa.cache.sharded_device_cache}
    finally:
        sa.stop()


def phase_preempt(n_nodes: int = 200, n_running: int = 2000,
                  n_claim: int = 1000, max_cycles: int = 10) -> dict:
    """Phase c: a high-priority gang preempts its way into a full
    cluster; the evict solve must run on the device."""
    from volcano_tpu.controllers import KubeletStandin
    from volcano_tpu.models import PriorityClass

    sa = _standalone(PREEMPT_CONF)
    try:
        store = sa.store
        # terminations finish at once: the stand-in kubelet's grace would
        # only stretch the wave over wall-clock seconds
        for c in sa.controllers.controllers:
            if isinstance(c, KubeletStandin):
                c.grace_seconds = 0.0
        store.create("priorityclasses", PriorityClass(name="high",
                                                      value=100))
        store.create("priorityclasses", PriorityClass(name="low", value=1))
        _add_nodes(store, n_nodes, "16", "64Gi")
        low = _job("low", n_running, 1, "1", "2Gi", priority_class="low")
        store.create("jobs", low)
        timings = []
        for i in range(max_cycles):
            sa.run_once()
            timings.append(dict(sa.scheduler.last_cycle_timing))
            n = sum(1 for p in _pods(store, "low-") if p.node_name)
            if n == n_running:
                break
        require(n == n_running, f"{n}/{n_running} low pods running")
        hi = _job("hi", n_claim, n_claim, "2", "4Gi",
                  priority_class="high")
        store.create("jobs", hi)
        before = {p.name for p in _pods(store, "low-") if p.node_name}
        placed = 0
        for i in range(max_cycles):
            t0 = time.perf_counter()
            sa.run_once()
            wall = time.perf_counter() - t0
            t = dict(sa.scheduler.last_cycle_timing)
            timings.append(t)
            placed = sum(1 for p in _pods(store, "hi-") if p.node_name)
            _cycle_line("preempt", i, t, placed, wall)
            if placed == n_claim:
                break
        require(placed == n_claim, f"gang placed {placed}/{n_claim}")
        left = {p.name for p in store.list("pods")
                if p.name in before and p.node_name}
        evicted = len(before - left)
        require(evicted > 0, "the gang was placed without an eviction")
        require(any("preempt_solve_ms" in t for t in timings),
                "no preempt cycle ran the evict kernel")
        check_capacity(store)
        check_gangs(store, [low, hi])
        check_device_cycles(timings, "packed")
        check_breaker(sa)
        log(f"[preempt] gang placed {placed}/{n_claim} on {n_nodes} nodes; "
            f"evictions {evicted}; breaker closed, 0 failures")
        return {"placed": placed, "evictions": evicted}
    finally:
        sa.stop()


def phase_exactness(n_nodes: int = 50, n_jobs: int = 100, tpj: int = 5,
                    seed: int = 0) -> dict:
    """Phase d: the sequential device kernel against the host allocate
    action (the reference's per-task loop), bind for bind."""
    import numpy as np

    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.conf import Configuration, PluginOption, Tier
    from volcano_tpu.framework import close_session, get_action, open_session
    from volcano_tpu.metrics import spans
    from volcano_tpu.models import (
        Node, Pod, PodGroup, PodGroupPhase, PodGroupSpec, PodGroupStatus,
    )
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION

    rng = np.random.default_rng(seed)
    node_cpu = rng.integers(8, 17, n_nodes)
    node_mem = rng.integers(16, 65, n_nodes)
    job_cpu = rng.integers(1, 4, n_jobs)
    job_mem = rng.integers(1, 5, n_jobs)
    tiers = [Tier(plugins=[PluginOption(name="priority"),
                           PluginOption(name="gang")]),
             Tier(plugins=[PluginOption(name="predicates"),
                           PluginOption(name="binpack")])]

    def run(mode):
        store = ClusterStore()
        cache = SchedulerCache(store)
        cache.binder = FakeBinder()
        cache.evictor = FakeEvictor()
        cache.run()
        for n in range(n_nodes):
            rl = {"cpu": str(node_cpu[n]), "memory": f"{node_mem[n]}Gi",
                  "pods": "110"}
            store.create("nodes", Node(name=f"n{n}", allocatable=rl,
                                       capacity=dict(rl)))
        for j in range(n_jobs):
            store.create("podgroups", PodGroup(
                name=f"pg{j}", namespace="c1",
                spec=PodGroupSpec(min_member=tpj),
                status=PodGroupStatus(phase=PodGroupPhase.INQUEUE)))
            for i in range(tpj):
                store.create("pods", Pod(
                    name=f"pg{j}-{i}", namespace="c1", phase="Pending",
                    annotations={POD_GROUP_ANNOTATION: f"pg{j}"},
                    containers=[{"requests": {
                        "cpu": str(job_cpu[j]),
                        "memory": f"{job_mem[j]}Gi"}}]))
        ssn = open_session(cache, tiers,
                           [Configuration("allocate", {"mode": mode})])
        with spans.span("volcano.action.allocate", "allocate_ms",
                        root=True) as act:
            get_action("allocate").execute(ssn)
        timing = {**act.record, **(ssn.solver_options.get("timing") or {})}
        close_session(ssn)
        return dict(cache.binder.binds), timing

    host, _ = run("host")
    device, timing = run("sequential")
    require("host_fallback" not in timing and "readback_ms" in timing,
            f"the sequential kernel did not run on the device: {timing}")
    diff = sorted(k for k in set(host) | set(device)
                  if host.get(k) != device.get(k))
    require(not diff,
            f"{len(diff)} binds differ, e.g. "
            f"{[(k, host.get(k), device.get(k)) for k in diff[:5]]}")
    require(host, "the exactness case bound nothing")
    log(f"[exactness] sequential kernel == host action: "
        f"{len(device)}/{n_jobs * tpj} pods, {n_nodes} nodes, "
        f"identical binds")
    return {"binds": len(device)}


def phase_four_chips(n_nodes: int = 2000, n_jobs: int = 1000,
                     tpj: int = 10, seed: int = 0) -> dict:
    """The sharded path and what it is compared with: the same seeded
    cycles sharded over every device, then packed on one."""
    import jax

    sharded = phase_main(n_nodes, n_jobs, tpj, solver_mode="sharded",
                         seed=seed)
    packed = phase_main(n_nodes, n_jobs, tpj, solver_mode="packed",
                        seed=seed)
    require(sharded["digest"] == packed["digest"],
            f"sharded decisions {sharded['digest']} != packed "
            f"{packed['digest']}")
    sdc = sharded["sharded_device_cache"]
    devices = jax.devices()
    require(sdc is not None and sdc.D == len(devices),
            f"sharded arena over {getattr(sdc, 'D', 0)} of "
            f"{len(devices)} devices")
    mesh_devs = set(sdc.mesh.devices.flat)
    for arr in sdc.resident_node_arrays():
        shards = arr.addressable_shards
        held = [s.device for s in shards]
        require(len(set(held)) == len(held) == sdc.D
                and set(held) == mesh_devs,
                f"node slabs on {held}, want one per device of {mesh_devs}")
        for s in shards:
            require(s.data.shape[0] == 1, s.data.shape)
    log(f"[four-chips] sharded == packed decisions ({sharded['digest']}); "
        f"one node slab on each of {sdc.D} devices")
    return {"digest": sharded["digest"], "devices": sdc.D}


def compile_line() -> str:
    from volcano_tpu.ops.precompile import watcher

    count, secs = watcher.session_totals()
    return (f"compiles={count} compile_s={secs} "
            f"cache_hits={watcher.cache_hits}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path over every chip and "
                         "the packed path it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (platform {dev['platform']!r})",
              file=sys.stderr)
        return 2

    from volcano_tpu.ops import precompile

    cache_dir = precompile.configure_compilation_cache(
        default_dir=precompile.ENTRY_POINT_CACHE_DIR)
    precompile.watcher.install()
    log(f"[cache] dir={cache_dir}")

    phases = ([("four-chips", lambda: phase_four_chips(seed=args.seed))]
              if args.four_chips else
              [("main", lambda: phase_main(seed=args.seed)),
               ("preempt", phase_preempt),
               ("exactness", lambda: phase_exactness(seed=args.seed))])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(f"[{name}] ok in {time.perf_counter() - t0} s; "
            f"{compile_line()}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: live daemon threads (controller watches,
    # the XLA runtime) can abort it after the result is already out
    os._exit(rc)
