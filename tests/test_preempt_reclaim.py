"""Preempt/reclaim/elect/reserve tests (reference actions/preempt/
preempt_test.go, actions/reclaim/reclaim_test.go patterns)."""

import pytest

from volcano_tpu.api import TaskStatus
from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
from volcano_tpu.client import ClusterStore
from volcano_tpu.conf import Configuration, PluginOption, Tier
from volcano_tpu.framework import close_session, get_action, open_session
from volcano_tpu.models import PriorityClass
from volcano_tpu.utils.scheduler_helper import reservation

from helpers import build_node, build_pod, build_pod_group, build_queue


@pytest.fixture(params=["solver", "host"])
def mode(request):
    return request.param


def open_mode(cache, tiers, mode):
    return open_session(cache, tiers,
                        [Configuration("preempt", {"mode": mode}),
                         Configuration("reclaim", {"mode": mode})])


def make_cluster(nodes, podgroups, pods, queues=(), priority_classes=()):
    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    for pc in priority_classes:
        store.create("priorityclasses", pc)
    for q in queues:
        store.apply("queues", q)
    for n in nodes:
        store.create("nodes", n)
    for pg in podgroups:
        store.create("podgroups", pg)
    for p in pods:
        store.create("pods", p)
    return store, cache


class TestPreempt:
    def test_high_priority_job_preempts_within_queue(self, mode):
        """preempt_test.go case: node full with low-prio job; high-prio job
        with pending tasks evicts victims and pipelines."""
        low_pg = build_pod_group("low", "c1", min_member=1)
        high_pg = build_pod_group("high", "c1", min_member=1)
        high_pg.spec.priority_class_name = "high-priority"
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "2", "memory": "4Gi"})],
            [low_pg, high_pg],
            [build_pod("c1", "low-1", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "low"),
             build_pod("c1", "low-2", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "low"),
             build_pod("c1", "high-1", "", "Pending",
                       {"cpu": "1", "memory": "1Gi"}, "high")],
            priority_classes=[PriorityClass("high-priority", 1000)])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")]),
                 Tier(plugins=[PluginOption(name="predicates"),
                               PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("preempt").execute(ssn)
        assert len(cache.evictor.evicts) >= 1
        assert all(e.startswith("c1/low") for e in cache.evictor.evicts)
        high_job = ssn.jobs["c1/high"]
        assert high_job.waiting_task_num() == 1  # pipelined
        close_session(ssn)

    def test_no_preemption_between_equal_priority(self, mode):
        pg_a = build_pod_group("a", "c1", min_member=1)
        pg_b = build_pod_group("b", "c1", min_member=1)
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "2", "memory": "4Gi"})],
            [pg_a, pg_b],
            [build_pod("c1", "a-1", "n1", "Running",
                       {"cpu": "2", "memory": "1Gi"}, "a"),
             build_pod("c1", "b-1", "", "Pending",
                       {"cpu": "1", "memory": "1Gi"}, "b")])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("preempt").execute(ssn)
        assert cache.evictor.evicts == []
        close_session(ssn)

    def test_conformance_protects_kube_system(self, mode):
        sys_pg = build_pod_group("sys", "kube-system", min_member=1)
        high_pg = build_pod_group("high", "c1", min_member=1)
        high_pg.spec.priority_class_name = "high-priority"
        sys_pod = build_pod("kube-system", "sys-1", "n1", "Running",
                            {"cpu": "2", "memory": "1Gi"}, "sys")
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "2", "memory": "4Gi"})],
            [sys_pg, high_pg],
            [sys_pod,
             build_pod("c1", "high-1", "", "Pending",
                       {"cpu": "1", "memory": "1Gi"}, "high")],
            priority_classes=[PriorityClass("high-priority", 1000)])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("preempt").execute(ssn)
        assert cache.evictor.evicts == []
        close_session(ssn)


class TestGangPreempt:
    """BASELINE config #4 in miniature: a high-priority gang claims room
    held by a low-priority job — all-or-nothing."""

    def _cluster(self, n_nodes, low_pods_per_node, min_member, mode):
        low_pg = build_pod_group("low", "c1", min_member=1)
        high_pg = build_pod_group("high", "c1", min_member=min_member)
        high_pg.spec.priority_class_name = "high-priority"
        pods = []
        for n in range(n_nodes):
            for i in range(low_pods_per_node):
                pods.append(build_pod(
                    "c1", f"low-{n}-{i}", f"n{n}", "Running",
                    {"cpu": "1", "memory": "1Gi"}, "low"))
        for i in range(min_member):
            pods.append(build_pod("c1", f"high-{i}", "", "Pending",
                                  {"cpu": "1", "memory": "1Gi"}, "high"))
        store, cache = make_cluster(
            [build_node(f"n{n}", {"cpu": "2", "memory": "8Gi"})
             for n in range(n_nodes)],
            [low_pg, high_pg], pods,
            priority_classes=[PriorityClass("high-priority", 1000)])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")]),
                 Tier(plugins=[PluginOption(name="predicates"),
                               PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, mode)
        return store, cache, ssn

    def test_gang_preempts_across_nodes(self, mode):
        # 2 full nodes (2x2 low pods); high gang of 3 must evict 3 victims
        # spread over both nodes and pipeline all 3
        store, cache, ssn = self._cluster(2, 2, 3, mode)
        get_action("preempt").execute(ssn)
        assert len(cache.evictor.evicts) == 3
        assert all(e.startswith("c1/low") for e in cache.evictor.evicts)
        assert ssn.jobs["c1/high"].waiting_task_num() == 3
        close_session(ssn)

    def test_nonuniform_gang_uses_scan_kernel(self, mode):
        # mixed task sizes disqualify the per-job closed-form fast path;
        # the scan kernel must produce the same gang preemption
        low_pg = build_pod_group("low", "c1", min_member=1)
        high_pg = build_pod_group("high", "c1", min_member=2)
        high_pg.spec.priority_class_name = "high-priority"
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "8Gi"})],
            [low_pg, high_pg],
            [build_pod("c1", f"low-{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "low")
             for i in range(4)]
            + [build_pod("c1", "high-big", "", "Pending",
                         {"cpu": "2", "memory": "1Gi"}, "high"),
               build_pod("c1", "high-small", "", "Pending",
                         {"cpu": "1", "memory": "1Gi"}, "high")],
            priority_classes=[PriorityClass("high-priority", 1000)])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")]),
                 Tier(plugins=[PluginOption(name="predicates"),
                               PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("preempt").execute(ssn)
        assert len(cache.evictor.evicts) == 3  # 3 cpu freed for 2+1
        assert ssn.jobs["c1/high"].waiting_task_num() == 2
        close_session(ssn)

    def test_gang_unsatisfiable_reverts_all_evictions(self, mode):
        # high gang of 5 can never fit 2x2-CPU nodes: NOTHING may be evicted
        store, cache, ssn = self._cluster(2, 2, 5, mode)
        get_action("preempt").execute(ssn)
        assert cache.evictor.evicts == []
        assert ssn.jobs["c1/high"].waiting_task_num() == 0
        close_session(ssn)


class TestReclaim:
    def test_cross_queue_reclaim(self, mode):
        """reclaim_test.go:44-177: q2's starving high-priority job reclaims
        from q1's low-priority job. One tier [conformance, gang], victims
        come from gang's priority comparison — reclaim across equal-priority
        jobs yields no victims in this reference version (the dispatch's
        intersection accumulator persists across tiers)."""
        from volcano_tpu.models import PriorityClass
        queues = [build_queue("q1", weight=1), build_queue("q2", weight=1)]
        pg1 = build_pod_group("pg1", "c1", min_member=1, queue="q1")
        pg1.spec.priority_class_name = "low-priority"
        pg2 = build_pod_group("pg2", "c1", min_member=1, queue="q2")
        pg2.spec.priority_class_name = "high-priority"
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "4Gi"})],
            [pg1, pg2],
            [build_pod("c1", f"a{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "pg1")
             for i in range(4)]
            + [build_pod("c1", "b0", "", "Pending",
                         {"cpu": "1", "memory": "1Gi"}, "pg2")],
            queues=queues,
            priority_classes=[PriorityClass(name="high-priority", value=100),
                              PriorityClass(name="low-priority", value=1)])
        tiers = [Tier(plugins=[PluginOption(name="conformance"),
                               PluginOption(name="gang")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("reclaim").execute(ssn)
        assert len(cache.evictor.evicts) == 1
        assert cache.evictor.evicts[0].startswith("c1/a")
        job2 = ssn.jobs["c1/pg2"]
        assert job2.waiting_task_num() == 1
        close_session(ssn)

    def test_equal_priority_no_cross_queue_reclaim(self, mode):
        """With gang registered and equal job priorities, the victim
        intersection is empty and stays empty through later tiers
        (session_plugins.go:121-160 `init` persists across tiers)."""
        queues = [build_queue("q1", weight=1), build_queue("q2", weight=1)]
        pg1 = build_pod_group("pg1", "c1", min_member=1, queue="q1")
        pg2 = build_pod_group("pg2", "c1", min_member=1, queue="q2")
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "4Gi"})],
            [pg1, pg2],
            [build_pod("c1", f"a{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "pg1")
             for i in range(4)]
            + [build_pod("c1", "b0", "", "Pending",
                         {"cpu": "1", "memory": "1Gi"}, "pg2")],
            queues=queues)
        tiers = [Tier(plugins=[PluginOption(name="gang"),
                               PluginOption(name="conformance")]),
                 Tier(plugins=[PluginOption(name="proportion"),
                               PluginOption(name="predicates")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("reclaim").execute(ssn)
        assert cache.evictor.evicts == []
        close_session(ssn)

    def test_non_reclaimable_queue_protected(self, mode):
        queues = [build_queue("q1", weight=1, reclaimable=False),
                  build_queue("q2", weight=1)]
        pg1 = build_pod_group("pg1", "c1", min_member=1, queue="q1")
        pg2 = build_pod_group("pg2", "c1", min_member=1, queue="q2")
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "4Gi"})],
            [pg1, pg2],
            [build_pod("c1", f"a{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "pg1")
             for i in range(4)]
            + [build_pod("c1", "b0", "", "Pending",
                         {"cpu": "1", "memory": "1Gi"}, "pg2")],
            queues=queues)
        tiers = [Tier(plugins=[PluginOption(name="gang")]),
                 Tier(plugins=[PluginOption(name="proportion"),
                               PluginOption(name="predicates")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("reclaim").execute(ssn)
        assert cache.evictor.evicts == []
        close_session(ssn)


class TestElectReserve:
    def test_elect_then_reserve_locks_node(self):
        reservation.reset()
        from volcano_tpu.models import PodGroupPhase
        pg = build_pod_group("pg1", "c1", min_member=1,
                             phase=PodGroupPhase.PENDING)
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "8Gi"}),
             build_node("n2", {"cpu": "8", "memory": "16Gi"})],
            [pg],
            [build_pod("c1", "p1", "", "Pending",
                       {"cpu": "1", "memory": "1Gi"}, "pg1")])
        tiers = [Tier(plugins=[PluginOption(name="reservation"),
                               PluginOption(name="gang")])]
        ssn = open_session(cache, tiers)
        get_action("elect").execute(ssn)
        assert reservation.target_job is not None
        assert reservation.target_job.name == "pg1"
        get_action("reserve").execute(ssn)
        # max-idle node locked
        assert "n2" in reservation.locked_nodes
        close_session(ssn)
        reservation.reset()


class TestEvictionMinimality:
    """BENCH config #4 shape, scaled down: eviction count must track the
    analytic minimum (spend free capacity everywhere before killing)."""

    def test_uniform_gang_near_minimal_evictions(self):
        import numpy as np

        from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION
        from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
        from volcano_tpu.ops import bucket, flatten_snapshot
        from volcano_tpu.ops.evict import (
            decode_evict_compact, solve_evict_uniform,
        )
        from volcano_tpu.ops.arrays import ScoreParams

        # 20 nodes x 16 cpu; 10 x 1-cpu victims each (future idle = 6);
        # 100 claimers of 2 cpu. Analytic minimum: 5 claimers/node =
        # 3 free + 2 via evicting 4 victims -> 20 x 4 = 80 evictions.
        n_nodes, n_victims, n_claim = 20, 200, 100
        nodes = {}
        for i in range(n_nodes):
            rl = {"cpu": "16", "memory": "64Gi", "pods": 110}
            nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                           capacity=dict(rl)))
        low = JobInfo("ns/low", PodGroup(name="low", namespace="ns",
                                         spec=PodGroupSpec(min_member=1)))
        victims = []
        for i in range(n_victims):
            pod = Pod(name=f"low-{i}", namespace="ns",
                      node_name=f"n{i % n_nodes}", phase="Running",
                      annotations={POD_GROUP_ANNOTATION: "low"},
                      containers=[{"requests": {"cpu": "1",
                                                "memory": "2Gi"}}])
            t = TaskInfo(pod)
            t.status = TaskStatus.RUNNING
            low.add_task_info(t)
            nodes[f"n{i % n_nodes}"].add_task(t)
            victims.append(t)
        hi = JobInfo("ns/hi", PodGroup(name="hi", namespace="ns",
                                       spec=PodGroupSpec(min_member=n_claim)))
        claimers = []
        for i in range(n_claim):
            pod = Pod(name=f"hi-{i}", namespace="ns",
                      annotations={POD_GROUP_ANNOTATION: "hi"},
                      containers=[{"requests": {"cpu": "2",
                                                "memory": "4Gi"}}])
            t = TaskInfo(pod)
            hi.add_task_info(t)
            claimers.append(t)

        arr = flatten_snapshot({hi.uid: hi}, nodes, claimers)
        sp = ScoreParams(least_req_weight=1.0).resolved(arr.R, arr.N)
        params = {
            "binpack_weight": np.float32(sp.binpack_weight),
            "binpack_res_weights": sp.binpack_res_weights,
            "least_req_weight": np.float32(sp.least_req_weight),
            "most_req_weight": np.float32(sp.most_req_weight),
            "balanced_weight": np.float32(sp.balanced_weight),
            "node_static": sp.node_static,
        }
        node_index = {n.name: i for i, n in enumerate(arr.nodes_list)}
        ordered = sorted(victims, key=lambda t: node_index[t.node_name])
        V = bucket(len(ordered))
        J = arr.job_min.shape[0]
        v_req = np.zeros((V, arr.R), np.float32)
        v_node = np.zeros(V, np.int32)
        v_valid = np.zeros(V, bool)
        for i, t in enumerate(ordered):
            v_req[i] = t.resreq.to_vector(arr.vocab)
            v_node[i] = node_index[t.node_name]
            v_valid[i] = True
        elig = np.zeros((J, V), bool)
        elig[0, :len(ordered)] = True
        need = np.zeros(J, np.int32)
        need[0] = n_claim
        job_req = np.zeros((J, arr.R), np.float32)
        job_req[0] = arr.task_init_req[0]
        job_acct = np.zeros((J, arr.R), np.float32)
        job_acct[0] = arr.task_req[0]
        job_count = np.zeros(J, np.int32)
        job_count[0] = n_claim
        varrays = {"v_req": v_req, "v_node": v_node, "v_valid": v_valid,
                   "elig": elig, "job_need": need, "job_req": job_req,
                   "job_acct": job_acct, "job_count": job_count}
        res = solve_evict_uniform(arr.device_dict(), varrays, params)
        assigned, evicted_by = decode_evict_compact(
            res.compact, arr.task_init_req.shape[0])
        placed = int((assigned[:n_claim] >= 0).sum())
        evictions = int((evicted_by >= 0).sum())
        assert placed == n_claim
        # capacity check: per node, demand must fit idle + freed
        demand = np.zeros(arr.N)
        for i in range(n_claim):
            demand[assigned[i]] += 2000.0
        freed = np.zeros(arr.N)
        for v in np.nonzero(evicted_by >= 0)[0]:
            freed[v_node[v]] += v_req[v][0]
        idle0 = arr.node_idle[:, 0]
        assert (demand <= idle0 + freed + 1e-3).all()
        # minimality: analytic minimum is 80; allow 10% slack
        assert evictions <= 88, f"evictions {evictions} vs minimum 80"


class TestPerJobHostRouting:
    """ADVICE r2 #3: a host-only claimer (PVC/affinity/GPU) must not
    downgrade the whole preempt/reclaim action — other claimers keep the
    device solver path."""

    def test_preempt_keeps_solver_for_other_claimers(self, monkeypatch):
        import volcano_tpu.actions.evict_solver as es
        from volcano_tpu.actions.preempt import PreemptAction

        calls = {}
        orig = es.run_evict_solver

        def spy(ssn, mode, skip_jobs=()):
            calls["skip"] = set(skip_jobs)
            return orig(ssn, mode, skip_jobs=skip_jobs)

        monkeypatch.setattr(es, "run_evict_solver", spy)

        high_pg = build_pod_group("high", min_member=1)
        high_pg.spec.priority_class_name = "high-priority"
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "2", "memory": "4Gi"})],
            [build_pod_group("low", min_member=1), high_pg],
            [build_pod("default", "low-0", "n1", "Running",
                       {"cpu": "2", "memory": "2Gi"}, "low"),
             build_pod("default", "high-0", "", "Pending",
                       {"cpu": "2", "memory": "2Gi"}, "high")],
            queues=[build_queue("default", 1)],
            priority_classes=[PriorityClass("high-priority", 1000)])
        tiers = [Tier(plugins=[PluginOption(name="priority"),
                               PluginOption(name="gang"),
                               PluginOption(name="conformance")]),
                 Tier(plugins=[PluginOption(name="predicates"),
                               PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, "solver")
        # simulate a host-only claimer job alongside the real one
        ssn.solver_options["host_only_jobs"] = {"default/other"}
        PreemptAction().execute(ssn)
        close_session(ssn)
        # the solver ran (not a whole-cycle downgrade) and skipped exactly
        # the host-only set
        assert calls["skip"] == {"default/other"}
        assert len(cache.evictor.evicts) == 1  # high evicted low via solver


class TestHierarchicalReclaim:
    def test_reclaim_victims_follow_the_weighted_tree(self, mode):
        """drf.go:348-408 (hierarchy reclaimableFn): with
        drf.enableHierarchy, reclaim victims are gated by the hdrf
        comparator AFTER the hypothetical reclaim — a starving
        heavy-weight queue reclaims from an over-share light-weight
        sibling, and both action modes agree."""
        queues = [
            build_queue("q-heavy", annotations={
                "volcano.sh/hierarchy": "root/heavy",
                "volcano.sh/hierarchy-weights": "10/8"}),
            build_queue("q-light", annotations={
                "volcano.sh/hierarchy": "root/light",
                "volcano.sh/hierarchy-weights": "10/2"}),
        ]
        pg_l = build_pod_group("pgl", "c1", min_member=1, queue="q-light")
        pg_h = build_pod_group("pgh", "c1", min_member=1, queue="q-heavy")
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "4", "memory": "4Gi"})],
            [pg_l, pg_h],
            # light's job occupies the whole node; heavy starves
            [build_pod("c1", f"l{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "pgl")
             for i in range(4)]
            + [build_pod("c1", "h0", "", "Pending",
                         {"cpu": "1", "memory": "1Gi"}, "pgh")],
            queues=queues)
        # gang's reclaimable requires a strictly higher-priority claimer
        # (gang.go:74-98) and would empty the tier intersection for these
        # equal-priority jobs: disable it, as hierarchy confs do
        # (enabledReclaimable: false), so the hdrf comparator rule decides
        tiers = [Tier(plugins=[
            PluginOption(name="drf",
                         arguments={"drf.enableHierarchy": True}),
            PluginOption(name="gang", enabled_reclaimable=False),
            PluginOption(name="predicates"),
            PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("reclaim").execute(ssn)
        assert len(cache.evictor.evicts) == 1, cache.evictor.evicts
        assert cache.evictor.evicts[0].startswith("c1/l")
        close_session(ssn)

    def test_no_reclaim_when_claimer_is_the_over_share_queue(self, mode):
        """The mirror case: the LIGHT-weight queue starving while the
        heavy-weight queue holds its deserved share must NOT reclaim —
        after a hypothetical reclaim the light queue's weighted key would
        overtake the heavy one's (comparator > 0), so the hdrf rule
        yields no victims."""
        queues = [
            build_queue("q-heavy", annotations={
                "volcano.sh/hierarchy": "root/heavy",
                "volcano.sh/hierarchy-weights": "10/8"}),
            build_queue("q-light", annotations={
                "volcano.sh/hierarchy": "root/light",
                "volcano.sh/hierarchy-weights": "10/2"}),
        ]
        pg_l = build_pod_group("pgl", "c1", min_member=1, queue="q-light")
        pg_h = build_pod_group("pgh", "c1", min_member=1, queue="q-heavy")
        store, cache = make_cluster(
            [build_node("n1", {"cpu": "10", "memory": "10Gi"})],
            [pg_h, pg_l],
            # heavy runs 8 of 10 cpu = exactly its 8/10 weighted share
            [build_pod("c1", f"h{i}", "n1", "Running",
                       {"cpu": "1", "memory": "1Gi"}, "pgh")
             for i in range(8)]
            + [build_pod("c1", "l0", "n1", "Running",
                         {"cpu": "1", "memory": "1Gi"}, "pgl")]
            + [build_pod("c1", "l1", "", "Pending",
                         {"cpu": "2", "memory": "2Gi"}, "pgl")],
            queues=queues)
        tiers = [Tier(plugins=[
            PluginOption(name="drf",
                         arguments={"drf.enableHierarchy": True}),
            PluginOption(name="gang", enabled_reclaimable=False),
            PluginOption(name="predicates"),
            PluginOption(name="nodeorder")])]
        ssn = open_mode(cache, tiers, mode)
        get_action("reclaim").execute(ssn)
        # light is ENTITLED to 2/10; it already holds 1 and wants 2 more:
        # reclaiming from heavy would push heavy below ITS weighted share
        # -> the comparator refuses; nothing is evicted
        assert cache.evictor.evicts == [], cache.evictor.evicts
        close_session(ssn)


def _tiers(*groups):
    return [Tier(plugins=[PluginOption(name=n) for n in g]) for g in groups]


#: (mode, tiers): the e2e preempt conf, the default conf and conformance
#: deciding alone (fully masked), drf in the deciding tier (mixed: drf's
#: fn is called per claimer), and reclaim masked and mixed (proportion
#: deciding)
VICTIM_CONFS = {
    "preempt_conf": ("preempt", _tiers(
        ["priority", "gang", "conformance"],
        ["predicates", "proportion", "nodeorder"])),
    "default_conf": ("preempt", _tiers(
        ["priority", "gang"],
        ["drf", "predicates", "proportion", "nodeorder"])),
    "conformance_first": ("preempt", _tiers(
        ["conformance"], ["priority", "gang"])),
    "drf_first": ("preempt", _tiers(
        ["drf", "priority", "gang", "conformance"],
        ["predicates", "nodeorder"])),
    "reclaim": ("reclaim", _tiers(
        ["priority", "gang", "conformance"],
        ["drf", "predicates", "proportion", "nodeorder"])),
    "reclaim_proportion_first": ("reclaim", _tiers(
        ["proportion", "gang", "conformance"],
        ["predicates", "nodeorder"])),
}


def _random_victim_session(seed, tiers):
    """Four queues (q3 not reclaimable, q-lonely holding one claimer and
    no running pod), jobs of three priorities with running pods spread
    over six nodes, kube-system and system-node-critical victims, and
    claimer jobs with pending pods; q1's "big" claimer holds more than any
    low job, so drf refuses it the low jobs that priority allows, and a
    gang of nine it is still a preempt claimer beside its own pods."""
    import random

    rng = random.Random(seed)
    classes = [PriorityClass("low", 1), PriorityClass("mid", 10),
               PriorityClass("high", 100)]
    queues = [build_queue("q1"), build_queue("q2"),
              build_queue("q3", reclaimable=False), build_queue("q-lonely")]
    nodes = [build_node(f"n{i}", {"cpu": "16", "memory": "64Gi"})
             for i in range(6)]
    pgs, pods = [], []
    slot = 0

    def job(name, ns, queue, running, pending, pclass="", pod_class="",
            min_member=None):
        nonlocal slot
        pg = build_pod_group(name, ns, queue=queue,
                             min_member=min_member or rng.randint(1, 2))
        pg.spec.priority_class_name = pclass
        pgs.append(pg)
        for k in range(running + pending):
            node = ""
            if k < running:
                node, slot = f"n{slot % 6}", slot + 1
            pod = build_pod(ns, f"{name}-{k}", node,
                            "Running" if node else "Pending",
                            {"cpu": "1", "memory": "1Gi"}, name)
            pod.priority_class_name = pod_class
            pods.append(pod)

    for q in ("q1", "q2", "q3"):
        for i in range(rng.randint(2, 3)):
            job(f"{q}-j{i}", "c1", q, running=rng.randint(1, 4),
                pending=rng.choice([0, 0, 1, 2]),
                pclass=rng.choice(["low", "mid", "high"]))
    job("big", "c1", "q1", running=8, pending=1, pclass="high",
        min_member=9)
    job("sys", "kube-system", "q1", running=2, pending=0, pclass="low")
    job("crit", "c1", "q2", running=2, pending=0, pclass="low",
        pod_class="system-node-critical")
    job("lonely", "c1", "q-lonely", running=0, pending=2, pclass="high")
    store, cache = make_cluster(nodes, pgs, pods, queues=queues,
                                priority_classes=classes)
    return cache, open_session(cache, tiers)


def _reference_victim_arrays(ssn, victims, job_order, mode):
    """Eligibility rows and needs from one ssn.preemptable /
    ssn.reclaimable call per claimer job over its queue-scoped list."""
    rows, need = [], []
    for job, tasks in job_order:
        if mode == "preempt":
            cands = [t for t in victims
                     if ssn.jobs[t.job].queue == job.queue
                     and t.job != job.uid]
            allowed = {v.uid for v in ssn.preemptable(tasks[0], cands)}
            need.append(max(0, job.min_available
                            - (job.ready_task_num()
                               + job.waiting_task_num())))
        else:
            cands = []
            for t in victims:
                vq = ssn.queues.get(ssn.jobs[t.job].queue)
                if (ssn.jobs[t.job].queue != job.queue
                        and vq is not None and vq.reclaimable):
                    cands.append(t)
            allowed = {v.uid for v in ssn.reclaimable(tasks[0], cands)}
            need.append(len(tasks))
        rows.append([t.uid in allowed for t in victims])
    return rows, need


@pytest.mark.parametrize("seed", [3, 17, 2024])
@pytest.mark.parametrize("conf", sorted(VICTIM_CONFS))
def test_victim_arrays_match_per_claimer_verdicts(conf, seed):
    """build_victim_arrays' column-mask eligibility equals, entry for
    entry, the per-claimer plugin dispatch over the same candidates."""
    import numpy as np

    from volcano_tpu.actions.evict_solver import (
        build_victim_arrays, collect_claimer_jobs, collect_victims)
    from volcano_tpu.ops import flatten_snapshot

    mode, tiers = VICTIM_CONFS[conf]
    cache, ssn = _random_victim_session(seed, tiers)
    preempt = mode == "preempt"
    job_order = collect_claimer_jobs(ssn, require_not_pipelined=preempt,
                                     skip_overused=not preempt)
    assert job_order
    arr = flatten_snapshot(
        {j.uid: j for j, _ in job_order}, ssn.nodes,
        [t for _, tasks in job_order for t in tasks], queues=ssn.queues,
        grouped=job_order)
    victims = collect_victims(ssn, arr.nodes_list)
    assert any(t.pod.namespace == "kube-system" for t in victims)
    assert any(t.pod.priority_class_name == "system-node-critical"
               for t in victims)
    out = build_victim_arrays(ssn, arr, victims, job_order, mode)
    rows, need = _reference_victim_arrays(ssn, victims, job_order, mode)
    nj, n = len(job_order), len(victims)
    elig = out["elig"]
    assert elig.dtype == bool and elig.shape == (arr.job_min.shape[0],
                                                 out["v_valid"].shape[0])
    np.testing.assert_array_equal(elig[:nj, :n], np.array(rows, dtype=bool))
    assert not elig[nj:].any() and not elig[:, n:].any()
    np.testing.assert_array_equal(out["job_need"][:nj], need)
    assert not out["job_need"][nj:].any()
    if preempt:
        lonely = [j for j, (job, _) in enumerate(job_order)
                  if job.queue == "q-lonely"]
        assert lonely and not elig[lonely[0]].any()
    close_session(ssn)
