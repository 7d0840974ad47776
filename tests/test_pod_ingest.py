"""The scheduler cache's pod ingest: a pod's TaskInfo is derived once and
re-placed on every later event. A seeded event sequence drives a real
ClusterStore into the cache as it is and into a cache that rebuilds the
TaskInfo on every event; after every event both hold the same tasks,
aggregates and node accounting. A Standalone turn counts the builds."""

import copy
import random

import pytest

from volcano_tpu.api import Resource, TaskInfo, TaskStatus
from volcano_tpu.api.job_info import job_key_of_pod
from volcano_tpu.api.types import POD_GROUP_ANNOTATION
from volcano_tpu.cache import FakeBinder, SchedulerCache
from volcano_tpu.cache.cache import DefaultEvictor
from volcano_tpu.client import ClusterStore
from volcano_tpu.metrics import spans

from helpers import build_node, build_pod, build_pod_group


class RebuildingCache(SchedulerCache):
    """The pod handlers rebuilding a TaskInfo from the pod on every event,
    as an in-process store's events reached them before the stored task
    was re-placed: the reference the re-placing cache is held to."""

    def _on_pod(self, event, obj, old):
        if obj.scheduler_name == self.scheduler_name:
            key = job_key_of_pod(obj)
            self._feed_flatten("pod", event, job=key,
                               node=obj.node_name or None)
            if old is not None and old.node_name \
                    and old.node_name != obj.node_name:
                self._feed_flatten("pod", event, job=key,
                                   node=old.node_name)
        if event == "add":
            if self._stored(TaskInfo(obj)) is not None:
                self._rebuild(obj, obj)
            elif obj.scheduler_name == self.scheduler_name:
                self.add_task(TaskInfo(obj))
        elif event == "update":
            self._rebuild(old, obj)
        else:
            self._delete(obj)

    def _stored(self, ti):
        job = self.jobs.get(ti.job)
        return None if job is None else job.tasks.get(ti.key)

    def _rebuild(self, old_pod, new_pod):
        if new_pod.scheduler_name != self.scheduler_name:
            return
        old_ti = TaskInfo(old_pod)
        stored = self._stored(old_ti)
        try:
            self.delete_task(stored if stored is not None else old_ti)
        except KeyError:
            pass
        self.add_task(TaskInfo(new_pod))

    def _delete(self, pod):
        if pod.scheduler_name != self.scheduler_name:
            return
        ti = TaskInfo(pod)
        stored = self._stored(ti)
        try:
            self.delete_task(stored if stored is not None else ti)
        except KeyError:
            pass
        job = self.jobs.get(ti.job)
        if job is not None and not job.tasks and job.pod_group is None:
            del self.jobs[ti.job]
            self.updater_versions.pop(ti.job, None)
            self._job_clone_cache.pop(ti.job, None)


def _res(r):
    return (r.milli_cpu, r.memory, tuple(sorted(r.scalars.items())),
            r.max_task_num)


def _task(t, ids=True):
    return (t.uid, t.job, t.key, t.status, t.node_name, t.priority,
            _res(t.resreq), _res(t.init_resreq), id(t.pod) if ids else None,
            t.volume_ready, t.sig_cache)


def _state(cache, ids=True):
    """Tasks, aggregates and node accounting; ``ids`` compares which pod
    object each task holds, for caches that share their store's pods."""
    jobs = {jk: ([(k, _task(t, ids)) for k, t in job.tasks.items()],
                 {s: sorted(b) for s, b in job.task_status_index.items()},
                 _res(job.allocated), _res(job.pending_request),
                 _res(job.total_request))
            for jk, job in cache.jobs.items()}
    nodes = {name: (_res(ni.idle), _res(ni.used), _res(ni.releasing),
                    sorted((k, t.status, t.node_name, _res(t.resreq))
                           for k, t in ni.tasks.items()))
             for name, ni in cache.nodes.items()}
    return jobs, nodes


class Twins:
    """The two caches on one store, compared around every pod event: the
    store calls ``before`` ahead of both caches and ``after`` behind them.
    Mismatches are kept, not raised: an event on the effect thread must
    not turn a failed comparison into a bind resync."""

    def __init__(self, async_effectors=False):
        self.store = ClusterStore()
        self.store.watch("pods", self.before)
        self.new = SchedulerCache(self.store,
                                  async_effectors=async_effectors)
        self.old = RebuildingCache(self.store)
        self.old.binder = FakeBinder()
        self.new.run()
        self.old.run()
        self.store.watch("pods", self.after)
        self.versions = None
        self.events = 0
        self.mismatches = []

    def before(self, event=None, obj=None, old=None):
        self.versions = [{k: j.flat_version for k, j in c.jobs.items()}
                         for c in (self.new, self.old)]

    def after(self, event=None, obj=None, old=None):
        self.events += 1
        moved = [{k for k in set(seen) | set(c.jobs)
                  if k not in c.jobs or seen.get(k) != c.jobs[k].flat_version}
                 for c, seen in zip((self.new, self.old), self.versions)]
        where = f"event {self.events} ({event} {getattr(obj, 'name', '')})"
        if moved[0] != moved[1]:
            self.mismatches.append((where, "moved", moved))
        a, b = _state(self.new), _state(self.old)
        if a != b:
            self.mismatches.append((where, "state", a, b))

    def both(self, fn):
        """``fn(cache)`` on each cache, compared as one event."""
        self.before()
        fn(self.new)
        fn(self.old)
        self.after("direct")


NODES = 4


def _pods(rng, job, n, bare=False):
    pods = []
    for i in range(n):
        req = {"cpu": rng.choice(["100m", "250m", "500m", "1"]),
               "memory": rng.choice(["128Mi", "500Mi", "1Gi"])}
        pod = build_pod("ns", f"{job}-{i}", "", "Pending", req,
                        "" if bare else job)
        if rng.random() < 0.3:
            pod.init_containers = [{"requests": {"cpu": "2",
                                                 "memory": "64Mi"}}]
        pods.append(pod)
    return pods


def _fits(cache, pod, node):
    return TaskInfo(pod).resreq.less_equal(cache.nodes[node].idle)


def _builds(sp):
    return sp.record.get("pod_task_builds", 0.0)


class Sequence:
    """A seeded run of pod events. Each op returns None when it found
    nothing to act on, else whether the re-placing cache took the path
    the event calls for: no build for a bind, eviction, priority flip,
    resync or delete of a known pod; a rebuild for a pod that moved jobs,
    changed its requests or came back under a new uid."""

    def __init__(self, seed, async_effectors):
        self.rng = random.Random(seed)
        self.async_effectors = async_effectors
        self.tw = Twins(async_effectors)
        self.store = self.tw.store
        self.nodes = [f"n{i}" for i in range(NODES)]
        for name in self.nodes:
            self.store.create("nodes", build_node(
                name, {"cpu": "16", "memory": "64Gi"}))
        self.made = 0

    def _name(self, prefix):
        self.made += 1
        return f"{prefix}{self.made}"

    def _live(self, pred=lambda p: True):
        return [p for p in self.store.list("pods", namespace="ns")
                if p.scheduler_name == "volcano" and pred(p)]

    def _fresh(self, pred=lambda p: True):
        pods = self._live(pred)
        return copy.deepcopy(self.rng.choice(pods)) if pods else None

    def _update(self, cur):
        with spans.span("t.update", root=True) as sp:
            self.store.update("pods", cur)
        return sp

    def create(self):
        name = self._name("j")
        self.store.create("podgroups", build_pod_group(name, "ns"))
        pods = _pods(self.rng, name, self.rng.randint(2, 4))
        foreign = build_pod("ns", f"{name}-other", "", "Pending",
                            {"cpu": "1"}, name)
        foreign.scheduler_name = "other"
        with spans.span("t.create", root=True) as sp:
            for pod in pods + [foreign]:
                self.store.create("pods", pod)
        return _builds(sp) == sp.record["pod_events"] == len(pods)

    def bind_batch(self):
        """In place through the cache's own bind effect, as a scheduler
        turn binds; the rebuilding cache decides alike but writes
        nothing, so both see the one echo of each write."""
        jobs = sorted({job_key_of_pod(p) for p in self._live(
            lambda p: not p.node_name and p.deletion_timestamp is None)})
        if not jobs:
            return None
        jk, node = self.rng.choice(jobs), self.rng.choice(self.nodes)
        tis = {}
        for cache in (self.tw.new, self.tw.old):
            tis[cache] = [t.clone() for t in cache.jobs[jk].task_status_index
                          .get(TaskStatus.PENDING, {}).values()]
            for ti in tis[cache]:
                ti.node_name = node
        wave = tis[self.tw.new]
        if not wave or not Resource.sum_of(t.resreq for t in wave) \
                .less_equal(self.tw.new.nodes[node].idle):
            return None
        assert self.tw.old.bind_batch(tis[self.tw.old]) == []
        with spans.span("t.bind", root=True) as sp:
            assert self.tw.new.bind_batch(wave) == []
            self.tw.new.wait_for_effects()
        if not self.async_effectors:
            assert sp.record["binds_written"] == len(wave)
        for ti in wave:
            pod = self.store.get("pods", ti.name, "ns")
            assert (pod.node_name, pod.phase) == (node, "Running")
            assert self.tw.new.jobs[jk].tasks[ti.key].status \
                == TaskStatus.RUNNING
        return _builds(sp) == 0

    def bind_fresh(self):
        cur = self._fresh(
            lambda p: not p.node_name and p.deletion_timestamp is None)
        node = self.rng.choice(self.nodes)
        if cur is None or not _fits(self.tw.new, cur, node):
            return None
        cur.node_name, cur.phase = node, "Running"
        return _builds(self._update(cur)) == 0

    def evict(self):
        pods = self._live(
            lambda p: p.node_name and p.deletion_timestamp is None)
        if not pods:
            return None
        with spans.span("t.evict", root=True) as sp:
            DefaultEvictor(self.store).evict(self.rng.choice(pods), "test")
        return _builds(sp) == 0

    def kubelet_delete(self):
        pods = self._live(lambda p: p.deletion_timestamp is not None)
        if not pods:
            return None
        with spans.span("t.delete", root=True) as sp:
            self.store.delete("pods", self.rng.choice(pods).name, "ns")
        return _builds(sp) == 0

    def delete(self):
        pods = self._live()
        if not pods:
            return None
        with spans.span("t.delete", root=True) as sp:
            self.store.delete("pods", self.rng.choice(pods).name, "ns")
        return _builds(sp) == 0

    def resync_add(self):
        pods = self._live()
        if not pods:
            return None
        pod = self.rng.choice(pods)
        # a re-list replays the store's object, or a decoded copy of it
        obj = pod if self.rng.random() < 0.5 else copy.deepcopy(pod)
        with spans.span("t.resync", root=True) as sp:
            self.tw.both(lambda c: c._on_pod("add", obj, None))
        return _builds(sp) == 0

    def priority(self):
        cur = self._fresh()
        if cur is None:
            return None
        cur.priority = self.rng.randint(1, 5)
        return _builds(self._update(cur)) == 0

    def bare_annotate(self):
        """A bare pod, on a node when it fits, gains its podgroup
        annotation: its job key moves, so the task is rebuilt."""
        name = self._name("bare")
        self.store.create("podgroups", build_pod_group(name, "ns"))
        (pod,) = _pods(self.rng, name, 1, bare=True)
        node = self.rng.choice(self.nodes)
        if _fits(self.tw.new, pod, node):
            pod.node_name, pod.phase = node, "Running"
        self.store.create("pods", pod)
        cur = copy.deepcopy(pod)
        cur.annotations = {POD_GROUP_ANNOTATION: name}
        return _builds(self._update(cur)) == 2

    def respec(self):
        """A fresh object with other requests (a few millicores, so a
        bound pod still fits its node): the stored requests no longer
        hold."""
        cur = self._fresh(lambda p: p.deletion_timestamp is None)
        if cur is None:
            return None
        cpu = f"{self._name('')}m"
        cur.containers = [{"requests": {"cpu": cpu, "memory": "32Mi"}}]
        return _builds(self._update(cur)) == 1

    def recreate(self):
        """The pod came back under its name with a new uid."""
        cur = self._fresh(lambda p: p.deletion_timestamp is None)
        if cur is None:
            return None
        cur.uid, cur.resource_version = f"{cur.uid}-again", 0
        return _builds(self._update(cur)) == 1

    OPS = ("bind_batch", "bind_fresh", "evict", "kubelet_delete", "delete",
           "resync_add", "priority", "bare_annotate", "respec", "recreate")

    def run(self, steps=80):
        ran = set()
        order = ["create"] * 3 + list(self.OPS)
        self.rng.shuffle(order)
        order += [self.rng.choice(self.OPS + ("create",))
                  for _ in range(steps)]
        for op in order:
            took = getattr(self, op)()
            assert not self.tw.mismatches, self.tw.mismatches[0]
            if took is not None:
                assert took, f"{op} took the wrong path"
                ran.add(op)
        return ran


@pytest.mark.parametrize("async_effectors", [False, True],
                         ids=["sync", "async"])
@pytest.mark.parametrize("seed", [7, 2026, 2 ** 31 + 5])
def test_replacing_matches_rebuilding_after_every_event(seed,
                                                        async_effectors):
    seq = Sequence(seed, async_effectors)
    ran = seq.run()
    assert seq.tw.events > 100
    assert {"create", "bind_batch", "bind_fresh", "evict", "resync_add",
            "bare_annotate", "respec", "recreate"} <= ran, ran


def test_a_gang_wave_builds_one_task_per_pod_created():
    """Through Standalone: the job controller's creates build a TaskInfo
    each, and the binds that follow build none."""
    from volcano_tpu.models import Job, JobSpec, Node, TaskSpec
    from volcano_tpu.standalone import Standalone

    sa = Standalone(metrics_port=0, async_effectors=False, period=0.0)
    try:
        rl = {"cpu": "4", "memory": "32Gi", "pods": "110"}
        for i in range(3):
            sa.store.create("nodes", Node(name=f"n{i}", allocatable=rl,
                                          capacity=dict(rl)))
        task = TaskSpec(name="task", replicas=4, template={"spec": {
            "containers": [{"name": "c", "requests": {
                "cpu": "1", "memory": "500Mi"}}]}})
        for j in range(2):
            sa.store.create("jobs", Job(name=f"g{j}", namespace="default",
                                        spec=JobSpec(min_available=4,
                                                     tasks=[task])))
        turns = []
        for _ in range(4):
            sa.run_once()
            turns.append(sa.scheduler.last_cycle_timing)
    finally:
        sa.stop()

    def total(key):
        return sum(rec.get(key, 0.0) for rec in turns)

    assert total("pods_created") == 8 and total("binds_written") == 8
    assert total("pod_task_builds") == total("pods_created")
    assert total("pod_events") == total("pods_created") \
        + total("binds_written")
    for rec in turns:
        assert rec.get("pod_task_builds", 0.0) == rec.get("pods_created", 0.0)


@pytest.mark.parametrize("delta_watch", [True, False], ids=["delta", "object"])
def test_a_remote_stream_matches_rebuilding(delta_watch):
    """Over a watch stream: a delta stream patches the pod the cache
    stores in place, so only an update's ``old`` shows that the requests
    changed; an object stream hands in fresh decoded copies. Either way
    the re-placing cache keeps what the rebuilding one derives."""
    from volcano_tpu.client import RemoteClusterStore, StoreServer

    store = ClusterStore()
    server = StoreServer(store).start()
    clients = [RemoteClusterStore(server.address, delta_watch=delta_watch)
               for _ in range(2)]
    try:
        for name in ("n0", "n1"):
            store.create("nodes", build_node(
                name, {"cpu": "16", "memory": "64Gi"}))
        store.create("podgroups", build_pod_group("g", "ns"))
        for i in range(4):
            store.create("pods", build_pod(
                "ns", f"g-{i}", "", "Pending",
                {"cpu": "1", "memory": "1Gi"}, "g"))
        new = SchedulerCache(clients[0])
        old = RebuildingCache(clients[1])
        for cache in (new, old):
            cache.binder = FakeBinder()
            cache.run()

        def write(name, edit):
            cur = copy.deepcopy(store.get("pods", name, "ns"))
            edit(cur)
            store.update("pods", cur)
            for c in clients:
                assert c.wait_stream_applied("pods", store._rv, timeout=30)
            with clients[0].locked(), clients[1].locked():
                assert _state(new, ids=False) == _state(old, ids=False)
            return new.jobs["ns/g"].tasks[f"ns/{name}"]

        def bind(p):
            p.node_name, p.phase = "n0", "Running"

        def respec(p):
            p.containers = [{"requests": {"cpu": "3", "memory": "2Gi"}}]

        def init_respec(p):
            p.init_containers = [{"requests": {"cpu": "5"}}]

        def priority(p):
            p.priority = 4

        for i in range(4):
            write(f"g-{i}", bind)
        assert write("g-0", respec).resreq.milli_cpu == 3000
        assert write("g-1", init_respec).init_resreq.milli_cpu == 5000
        write("g-0", priority)
        assert new.nodes["n0"].used.milli_cpu == 6000
        if delta_watch:
            st = clients[0].delta_stats
            assert st["events"] >= 7 and not st["fallbacks"]
    finally:
        for c in clients:
            c.close()
        server.stop()
