"""Event-sourced flatten: scheduler-level wiring, the quiet-cluster
zero-work contract, and the flatten_event fault-injection ladder.

Byte-identity of the event path itself is proven at the kernel level by
tests/test_solver.py::TestFlattenEventIdentity; this file proves the
SchedulerCache feeds the ledger (watch hooks + snapshot-clone seam), that
the scheduler surfaces flatten_mode/patch counters, and that a genuinely
quiet cluster's cycle start does zero flatten work.
"""

import numpy as np
import pytest

from helpers import build_node, build_pod, build_pod_group, build_queue
from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
from volcano_tpu.client import ClusterStore
from volcano_tpu.models import PodGroupPhase
from volcano_tpu.scheduler import Scheduler


def _rig(n_nodes=12, node_cpu="8"):
    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    for i in range(2):
        store.apply("queues", build_queue(f"q{i}", weight=i + 1))
    for i in range(n_nodes):
        store.create("nodes", build_node(
            f"n{i}", {"cpu": node_cpu, "memory": "32Gi"}))
    return store, cache


def _wave(store, k, cpu="20", members=2):
    """members pods of cpu each; cpu > node capacity => a stable
    unschedulable backlog (pending every cycle, no store churn)."""
    pg = build_pod_group(f"j{k}", "b", min_member=members, queue=f"q{k % 2}")
    pg.status.phase = PodGroupPhase.PENDING
    store.create("podgroups", pg)
    for i in range(members):
        store.create("pods", build_pod(
            "b", f"j{k}-{i}", "", "Pending",
            {"cpu": cpu, "memory": "1Gi"}, f"j{k}"))


class TestSchedulerWiring:
    def test_watch_hooks_feed_ledger(self):
        store, cache = _rig()
        fc = cache.flatten_cache
        assert fc.events_enabled
        before = fc._ev_feed
        _wave(store, 0)
        assert fc._ev_feed > before  # pod/podgroup deliveries marked
        assert "b/j0" in fc._ev_dirty_jobs

    def test_cycle_reports_flatten_mode_and_ladder(self):
        store, cache = _rig()
        for k in range(4):
            _wave(store, k)
        sched = Scheduler(cache)
        sched.run_once()
        assert sched.last_cycle_timing.get("flatten_mode") == "cold"
        sched.run_once()
        t = sched.last_cycle_timing
        # condition writes from cycle 1 arrive as deltas; patched in place
        assert t.get("flatten_mode") == "event"
        assert "flatten_patch_ms" in t
        # a schedulable wave lands: pending membership changes => re-diff
        _wave(store, 10, cpu="1")
        sched.run_once()
        t = sched.last_cycle_timing
        assert t.get("flatten_mode") in ("incremental", "cold")
        assert "flatten_full_ms" in t
        assert t.get("flatten_fallback_reason")

    def test_metrics_family_exported(self):
        from volcano_tpu.metrics import metrics

        store, cache = _rig()
        for k in range(3):
            _wave(store, k)
        sched = Scheduler(cache)
        base_ev = metrics.flatten_cycles_total.get({"mode": "event"})
        base_cold = metrics.flatten_cycles_total.get({"mode": "cold"})
        for _ in range(3):
            sched.run_once()
        assert metrics.flatten_cycles_total.get(
            {"mode": "cold"}) >= base_cold + 1
        assert metrics.flatten_cycles_total.get(
            {"mode": "event"}) >= base_ev + 1
        exposition = metrics.registry.expose()
        assert "volcano_flatten_cycles_total" in exposition
        assert "volcano_flatten_rows_patched" in exposition

    def test_mutating_action_before_allocate_stands_down(self):
        """A conf ordering preempt before allocate mutates the session's
        clones AFTER the snapshot seam ran — deltas the ledger never sees.
        The session mutation odometer must make the event path stand down
        for that cycle instead of trusting stale rows."""
        from volcano_tpu.models import PriorityClass

        conf = """
actions: "enqueue, preempt, allocate"
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
"""
        store, cache = _rig(n_nodes=2, node_cpu="4")
        store.create("priorityclasses", PriorityClass("high-priority", 1000))
        # low-priority pods fill both nodes
        low_pg = build_pod_group("low", "b", min_member=2, queue="q0")
        low_pg.status.phase = PodGroupPhase.RUNNING
        store.create("podgroups", low_pg)
        for i in range(2):
            store.create("pods", build_pod(
                "b", f"low-{i}", f"n{i}", "Running",
                {"cpu": "4", "memory": "1Gi"}, "low"))
        # a backlog wave keeps the flatten non-empty and the ledger warm
        _wave(store, 0, cpu="20")
        sched = Scheduler(cache, scheduler_conf=conf)
        sched.run_once()
        sched.run_once()
        assert sched.last_cycle_timing.get("flatten_mode") == "event"
        # the high-priority job arrives: preempt evicts low pods BEFORE
        # allocate's flatten -> the odometer forces the full re-diff
        high_pg = build_pod_group("high", "b", min_member=1, queue="q0")
        high_pg.spec.priority_class_name = "high-priority"
        high_pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", high_pg)
        store.create("pods", build_pod(
            "b", "high-0", "", "Pending",
            {"cpu": "4", "memory": "1Gi"}, "high", priority=1000))
        sched.run_once()
        t = sched.last_cycle_timing
        assert t.get("flatten_mode") in ("incremental", "cold")
        assert t.get("flatten_fallback_reason") == "session_mutations"


class TestQuietCluster:
    def test_zero_event_cycle_zero_row_writes(self):
        """The quiet-cluster regression contract: a cycle with no mirror
        deltas performs zero row writes (patch counters flat) and reuses
        the prior assembly object identity."""
        store, cache = _rig()
        for k in range(5):
            _wave(store, k)
        sched = Scheduler(cache)
        fc = cache.flatten_cache
        # settle: cold, then the condition-write deltas of cycle 0
        for _ in range(3):
            sched.run_once()
        assert sched.last_cycle_timing.get("flatten_mode") == "event"
        prior_arr = fc._evn["arr"]
        node_buf = cache.flatten_cache._node_buf
        idle_before = node_buf["idle"].copy()
        from volcano_tpu.metrics import metrics
        patched_before = metrics.flatten_rows_patched_total.get()
        for _ in range(3):
            sched.run_once()
            t = sched.last_cycle_timing
            assert t.get("flatten_mode") == "event"
            assert t.get("flatten_rows_patched") == 0.0
            assert t.get("flatten_events_applied") == 0.0
            assert t.get("flatten_patch_ms", 1e9) < 1e9
        # patch counters stayed flat and the assembly object survived
        assert metrics.flatten_rows_patched_total.get() == patched_before
        assert fc._evn["arr"] is prior_arr
        assert np.array_equal(node_buf["idle"], idle_before)

    def test_unschedulable_condition_rewrite_is_deduped(self):
        """The status updater must not churn the store with identical
        Unschedulable conditions every cycle — that churn alone would keep
        a quiet cluster from ever reaching the zero-event fast path."""
        store, cache = _rig()
        _wave(store, 0)
        sched = Scheduler(cache)
        sched.run_once()
        sched.run_once()  # conditions written + delivered
        rv = store._rv
        sched.run_once()
        assert store._rv == rv  # no writes at all


class TestBenchConfig:
    def test_flatten_event_path_smoke(self):
        """CPU-smoke run of the bench config at toy scale: structure,
        byte-identity flags and the quiet-cycle zero-work contract."""
        import os
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        from bench import flatten_event_path

        r = flatten_event_path(n_nodes=40, n_jobs=20, tpj=2,
                               big_shape=False)
        s = r["shape_10k_2k"]
        for level in ("quiet", "steady", "heavy"):
            assert s[level]["identical"], level
            assert set(s[level]["modes"]) == {"event"}, level
        assert s["quiet"]["rows_patched_mean"] == 0.0
        assert s["quiet"]["assembly_reused"]
        assert s["steady"]["rows_patched_mean"] > 0


class TestFaultInjectionLadder:
    def test_dropped_event_detected_and_healed(self):
        """Arm flatten_event to drop one mirror delta: the epoch check
        must detect the skew, the cycle must fall back to the full
        re-diff (restoring byte-identity — asserted against a from-scratch
        flatten of the same snapshot), and the ledger must recover."""
        from volcano_tpu.ops import FlattenCache, flatten_snapshot
        from volcano_tpu.resilience.faultinject import faults

        store, cache = _rig()
        for k in range(4):
            _wave(store, k)
        sched = Scheduler(cache)
        sched.run_once()
        sched.run_once()
        assert sched.last_cycle_timing.get("flatten_mode") == "event"
        fc = cache.flatten_cache
        # the waves' stable backlog grows the arena's task axis ahead:
        # settle that before comparing padded buffers
        dc = cache.device_cache
        if dc.grow_thread is not None:
            dc.grow_thread.join(300)
        try:
            faults.arm_once("flatten_event")
            # a running pod lands on n3: its delivery is DROPPED by the
            # armed point, so the ledger never hears about the node row
            store.create("pods", build_pod(
                "b", "ghost", "n3", "Running",
                {"cpu": "4", "memory": "1Gi"}, "j0"))
            assert faults.fired("flatten_event") == 1
            sched.run_once()
            t = sched.last_cycle_timing
            assert t.get("flatten_fallback_reason") == "epoch_mismatch"
            assert t.get("flatten_mode") in ("incremental", "cold")
            # no silent drift: the post-fallback buffers are bit-identical
            # to a from-scratch flatten of the same snapshot
            evn = fc._evn
            arr = evn["arr"]
            wf, wi, wl = arr.packed()
            sn = cache.snapshot()
            cold = flatten_snapshot(
                {j.uid: j for j in arr.jobs_list},
                {ni.name: ni for ni in arr.nodes_list},
                list(arr.tasks_list), cache=FlattenCache(fc.vocab),
                queues=sn.queues, floor=dc.dims_floor)
            cf, ci, cl = cold.packed()
            assert wl == cl
            assert wi.tobytes() == ci.tobytes()
            # float columns: queue demand rows are overwritten in place by
            # the proportion plugin each session; compare the task/node
            # columns the patch path owns
            for k2 in ("task_init_req", "task_req", "node_idle",
                       "node_used", "node_extra_future", "node_alloc"):
                assert np.array_equal(getattr(arr, k2),
                                      getattr(cold, k2)), k2
            sched.run_once()
            assert sched.last_cycle_timing.get("flatten_mode") == "event"
            from volcano_tpu.metrics import metrics
            assert metrics.flatten_fallbacks_total.get(
                {"reason": "epoch_mismatch"}) >= 1
        finally:
            faults.reset()

    def test_duplicated_event_detected(self):
        from volcano_tpu.resilience.faultinject import faults

        store, cache = _rig()
        for k in range(3):
            _wave(store, k)
        sched = Scheduler(cache)
        sched.run_once()
        sched.run_once()
        try:
            faults.arm_once("flatten_event_dup")
            store.create("pods", build_pod(
                "b", "dup-ghost", "n2", "Running",
                {"cpu": "2", "memory": "1Gi"}, "j0"))
            assert faults.fired("flatten_event_dup") == 1
            sched.run_once()
            assert sched.last_cycle_timing.get(
                "flatten_fallback_reason") == "epoch_mismatch"
            sched.run_once()
            assert sched.last_cycle_timing.get("flatten_mode") == "event"
        finally:
            faults.reset()
