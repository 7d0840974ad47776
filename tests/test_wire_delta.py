"""Delta-native data path, the acceptance churn matrix: a
delta-negotiated arm and an object-path arm riding ONE live server
through 40 seeded cycles of binds, node drains, priority flips and job
add/remove must stay byte-identical in mirror content, packed solver
arrays, and scheduler decisions, including across a mid-run injected
fallback-and-resume. Negotiation and the typed fallback ladder are
covered in ``test_delta_path.py``, whose server fixture this module
shares."""

import copy
import hashlib
import random
import time

from volcano_tpu.cache import (
    FakeBinder, FakeEvictor, FakeStatusUpdater, SchedulerCache,
)
from volcano_tpu.ops import flatten_snapshot
from volcano_tpu.resilience import faults
from volcano_tpu.scheduler import Scheduler

from helpers import build_node, build_pod, build_pod_group, build_queue
from test_delta_path import served  # noqa: F401 — shared fixture


class TestChurnMatrix:
    """The acceptance matrix: 40 seeded churn cycles through one live
    server; the delta arm and the object arm must be indistinguishable
    — mirror content, packed-array bytes, and scheduler decisions
    bind-for-bind — every cycle, including across a mid-run injected
    delta fallback-and-resume."""

    CYCLES = 40
    FAULT_CYCLE = 20

    @staticmethod
    def _digest(cache):
        sn = cache.snapshot()
        tasks = [t for j in sn.jobs.values() for t in j.tasks.values()]
        if not tasks:
            return "empty"
        fbuf, ibuf, layout = flatten_snapshot(
            sn.jobs, sn.nodes, tasks).packed()
        h = hashlib.sha256()
        h.update(fbuf.tobytes())
        h.update(ibuf.tobytes())
        h.update(repr(layout).encode())
        return h.hexdigest()

    @staticmethod
    def _fingerprint(cache):
        with cache.cluster.locked():
            jobs = {jk: [(tk, t.status.name, t.node_name, t.priority,
                          t.pod.phase, dict(t.pod.labels or {}))
                         for tk, t in job.tasks.items()]
                    for jk, job in cache.jobs.items()}
            # real nodes only: a pod event racing a same-cycle node
            # delete across the two per-kind streams may or may not
            # leave a placeholder NodeInfo (node=None) behind, in either
            # arm — snapshot() skips placeholders, so they are invisible
            # to the packed arrays and the scheduler either way
            return jobs, list(cache.jobs), sorted(
                n for n, ni in cache.nodes.items() if ni.node is not None)

    def test_40_cycles_bind_for_bind_identical(self, served):
        store, server, client = served
        rng = random.Random(1316)
        store.apply("queues", build_queue("q0", weight=1))
        for i in range(4):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "16", "memory": "64Gi"}))
        next_job = 0

        def add_job():
            nonlocal next_job
            name = f"m{next_job}"
            next_job += 1
            store.create("podgroups", build_pod_group(
                name, "churn", min_member=2, queue="q0"))
            for i in range(2):
                store.create("pods", build_pod(
                    "churn", f"{name}-{i}", "", "Pending",
                    {"cpu": "1", "memory": "1Gi"}, name))
            return name

        jobs = [add_job() for _ in range(6)]

        arms = {}
        for label, delta in (("delta", True), ("object", False)):
            cache = SchedulerCache(client(delta_watch=delta))
            cache.binder = FakeBinder()
            cache.evictor = FakeEvictor()
            cache.status_updater = FakeStatusUpdater()
            cache.run()
            cache.wait_for_cache_sync()
            arms[label] = (cache, Scheduler(cache))

        def live_pods():
            return [p for p in store.list("pods", namespace="churn")]

        drained = {}  # node name -> cycles until re-add

        def churn_once(cycle):
            readded = False
            for name in [n for n, left in drained.items() if left == 0]:
                store.create("nodes", build_node(
                    name, {"cpu": "16", "memory": "64Gi"}))
                del drained[name]
                readded = True
            if readded:
                # let both arms apply the node create before any pod op
                # can reference it: a pod event racing ahead of the
                # create would grow a placeholder NodeInfo whose dict
                # slot captures the node's position — same content,
                # different packed-array layout order between the arms
                self._settle(store, arms)
            for name in drained:
                drained[name] -= 1
            for _ in range(4):
                op = rng.choice(["flip", "flip", "priority", "bind",
                                 "drain", "jobs"])
                pods = live_pods()
                if op == "flip" and pods:
                    cur = copy.deepcopy(rng.choice(pods))
                    cur.phase = rng.choice(
                        ["Pending", "Running", "Succeeded"])
                    cur.labels = dict(cur.labels or {},
                                      cycle=str(cycle))
                    store.update("pods", cur)
                elif op == "priority" and pods:
                    cur = copy.deepcopy(rng.choice(pods))
                    cur.priority = rng.randint(1, 3)
                    store.update("pods", cur)
                elif op == "bind" and pods:
                    # an external controller binding/moving a pod —
                    # onto a live node, so neither arm has to invent a
                    # placeholder for it
                    alive = [n for n in (f"n{i}" for i in range(4))
                             if n not in drained]
                    cur = copy.deepcopy(rng.choice(pods))
                    cur.node_name = rng.choice(alive)
                    cur.phase = "Running"
                    store.update("pods", cur)
                elif op == "drain":
                    alive = [n for n in (f"n{i}" for i in range(4))
                             if n not in drained]
                    if len(alive) > 2:
                        victim = rng.choice(alive)
                        # a drain evicts first: unbind every store pod
                        # still referencing the victim BEFORE deleting
                        # the node, so the unbind and the delete commute
                        # across the independent pods/nodes streams
                        # (either order leaves no task-holding
                        # placeholder behind)
                        for p in pods:
                            if p.node_name == victim:
                                cur = copy.deepcopy(p)
                                cur.node_name = ""
                                cur.phase = "Pending"
                                store.update("pods", cur)
                        # settle so no in-flight pod event still naming
                        # the victim can land after the delete and
                        # resurrect it as a placeholder in one arm only
                        self._settle(store, arms)
                        store.delete("nodes", victim)
                        drained[victim] = 2
                elif op == "jobs":
                    if len(jobs) > 4 and rng.random() < 0.5:
                        gone = jobs.pop(rng.randrange(len(jobs)))
                        for i in range(2):
                            try:
                                store.delete("pods", f"{gone}-{i}",
                                             "churn")
                            except Exception:  # noqa: BLE001
                                pass
                        store.delete("podgroups", gone, "churn")
                    elif len(jobs) < 8:
                        jobs.append(add_job())

        for cycle in range(self.CYCLES):
            churn_once(cycle)
            if cycle == self.FAULT_CYCLE:
                # mid-run fallback-and-resume: quiesce first so the
                # armed drop can only land on the first canary frame;
                # the second canary is the gap-detector that forces the
                # typed delta_gap fallback and the object-path resume
                # before this cycle's parity checks run
                self._settle(store, arms)
                faults.arm_once("delta_frame")
                for marker in ("fault-canary", "gap-detector"):
                    cur = copy.deepcopy(live_pods()[0])
                    cur.labels = dict(cur.labels or {}, canary=marker)
                    store.update("pods", cur)
            self._settle(store, arms)
            for _, sched in arms.values():
                sched.run_once()
            d_cache, _ = arms["delta"]
            o_cache, _ = arms["object"]
            assert self._fingerprint(d_cache) == \
                self._fingerprint(o_cache), f"mirror diverged @{cycle}"
            assert self._digest(d_cache) == self._digest(o_cache), \
                f"packed arrays diverged @{cycle}"
            assert d_cache.binder.binds == o_cache.binder.binds \
                and d_cache.binder.channel == o_cache.binder.channel, \
                f"decisions diverged @{cycle}"

        dstats = arms["delta"][0].cluster.delta_stats
        assert dstats["events"] > 0  # the fast path actually ran
        assert dstats["fallbacks"] == {"delta_gap": 1}  # the injection

    @staticmethod
    def _settle(store, arms, timeout=30.0):
        """Quiesce: both arms' mirrors have applied every store event.
        The store is only mutated by the test thread, so per-kind
        key-set + resource_version agreement is a complete settle
        check (no event can still be in flight once the newest rv of
        every object has landed)."""
        def want():
            with store.locked():
                pods = {f"{p.namespace}/{p.name}": p.resource_version
                        for p in store.list("pods")}
                pgs = {pg.name: pg.resource_version
                       for pg in store.list("podgroups")}
                nodes = {n.name: n.resource_version
                         for n in store.list("nodes")}
            return pods, pgs, nodes

        def caught_up(cache, pods, pgs, nodes):
            with cache.cluster.locked():
                have = {f"{t.pod.namespace}/{t.pod.name}":
                        t.pod.resource_version
                        for j in cache.jobs.values()
                        for t in j.tasks.values()}
                if have != pods:
                    return False
                # only REAL nodes count: a task bound to an unknown (or
                # drained) node grows a placeholder NodeInfo with no
                # node object — placeholder parity between the arms is
                # already implied by the pods check above
                real = {name: ni.node.resource_version
                        for name, ni in cache.nodes.items()
                        if ni.node is not None}
                if real != nodes:
                    return False
                # a deleted podgroup's delete has landed too: its job
                # must not linger, podgroup attached, in one arm only
                if any(job.pod_group is not None
                       and job.pod_group.name not in pgs
                       for job in cache.jobs.values()):
                    return False
                for name, rv in pgs.items():
                    job = cache.jobs.get(f"churn/{name}")
                    if job is None or job.pod_group is None \
                            or job.pod_group.resource_version != rv:
                        return False
            return True

        deadline = time.time() + timeout
        while time.time() < deadline:
            pods, pgs, nodes = want()
            if all(caught_up(cache, pods, pgs, nodes)
                   for cache, _ in arms.values()):
                return
            time.sleep(0.005)
        raise AssertionError("arms failed to settle")
