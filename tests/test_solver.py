"""Solver kernel tests: feasibility/gang/pipeline semantics on CPU mesh."""

import numpy as np
import pytest

from volcano_tpu.api import NodeInfo, JobInfo, TaskInfo, TaskStatus
from volcano_tpu.ops import (
    ScoreParams, flatten_snapshot, solve_allocate, solve_allocate_sequential,
)

from helpers import build_node, build_pod, build_pod_group


def make_problem(node_specs, job_specs):
    """node_specs: [(name, cpu, mem)]; job_specs: [(name, min_member,
    [(cpu, mem)])] -> (jobs, nodes, tasks_in_order)."""
    nodes = {}
    for name, cpu, mem in node_specs:
        nodes[name] = NodeInfo(build_node(name, {"cpu": cpu, "memory": mem}))
    jobs = {}
    tasks = []
    for jname, min_member, reqs in job_specs:
        pg = build_pod_group(jname, "ns", min_member=min_member)
        job = JobInfo(f"ns/{jname}", pg)
        for i, (cpu, mem) in enumerate(reqs):
            p = build_pod("ns", f"{jname}-{i}", "", "Pending",
                          {"cpu": cpu, "memory": mem}, jname)
            t = TaskInfo(p)
            job.add_task_info(t)
            tasks.append(t)
        jobs[job.uid] = job
    return jobs, nodes, tasks


def params_dict(arr, **kw):
    sp = ScoreParams(**kw).resolved(arr.R, arr.N)
    return {
        "binpack_weight": np.float32(sp.binpack_weight),
        "binpack_res_weights": sp.binpack_res_weights,
        "least_req_weight": np.float32(sp.least_req_weight),
        "most_req_weight": np.float32(sp.most_req_weight),
        "balanced_weight": np.float32(sp.balanced_weight),
        "node_static": sp.node_static,
    }


@pytest.fixture(params=["rounds", "sequential"])
def solver(request):
    if request.param == "rounds":
        return lambda arr, p: solve_allocate(arr.device_dict(), p)
    return lambda arr, p: solve_allocate_sequential(arr.device_dict(), p)


class TestSolveAllocate:
    def test_simple_gang_fits(self, solver):
        jobs, nodes, tasks = make_problem(
            [("n1", "4", "8Gi"), ("n2", "4", "8Gi")],
            [("j1", 4, [("1", "1Gi")] * 4)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assigned = np.asarray(res.assigned)[:4]
        assert (assigned >= 0).all()
        assert np.asarray(res.job_ready)[0]
        assert (np.asarray(res.kind)[:4] == 0).all()

    def test_gang_unsatisfiable_reverts(self, solver):
        # 4-replica gang, cluster only fits 2 -> nothing assigned
        jobs, nodes, tasks = make_problem(
            [("n1", "2", "8Gi")],
            [("j1", 4, [("1", "1Gi")] * 4)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assert (np.asarray(res.assigned)[:4] == -1).all()
        assert not np.asarray(res.job_ready)[0]

    def test_partial_gang_with_min_available(self, solver):
        # 4 replicas, min_member=2, room for 2 -> 2 assigned, job ready
        jobs, nodes, tasks = make_problem(
            [("n1", "2", "8Gi")],
            [("j1", 2, [("1", "1Gi")] * 4)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assigned = np.asarray(res.assigned)[:4]
        assert (assigned >= 0).sum() == 2
        assert np.asarray(res.job_ready)[0]

    def test_discarded_job_frees_resources_for_next(self, solver):
        # j1 (min 3) can't fit; j2 (min 2) can use the space j1 released
        jobs, nodes, tasks = make_problem(
            [("n1", "2", "8Gi")],
            [("j1", 3, [("1", "1Gi")] * 3),
             ("j2", 2, [("1", "1Gi")] * 2)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assigned = np.asarray(res.assigned)
        ready = np.asarray(res.job_ready)
        assert not ready[0] and ready[1]
        assert (assigned[:3] == -1).all()
        assert (assigned[3:5] >= 0).all()

    def test_respects_node_selector_mask(self, solver):
        nodes = {
            "n1": NodeInfo(build_node("n1", {"cpu": "4", "memory": "8Gi"},
                                      labels={"zone": "a"})),
            "n2": NodeInfo(build_node("n2", {"cpu": "4", "memory": "8Gi"},
                                      labels={"zone": "b"})),
        }
        pg = build_pod_group("j1", "ns", min_member=1)
        job = JobInfo("ns/j1", pg)
        p = build_pod("ns", "p0", "", "Pending", {"cpu": "1", "memory": "1Gi"},
                      "j1", node_selector={"zone": "b"})
        t = TaskInfo(p)
        job.add_task_info(t)
        arr = flatten_snapshot({"ns/j1": job}, nodes, [t])
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        node_idx = int(np.asarray(res.assigned)[0])
        assert arr.nodes_list[node_idx].name == "n2"

    def test_pipeline_only_job_stays_pipelined_but_unready(self, solver):
        # node full but releasing: the task pipelines onto FutureIdle; the
        # job is not gang-ready (pipelined doesn't count), but the pipeline
        # reservation survives — ssn.Pipeline is outside the Statement in
        # the reference, so Discard doesn't undo it
        ni = NodeInfo(build_node("n1", {"cpu": "2", "memory": "8Gi"}))
        running = TaskInfo(build_pod("ns", "old", "n1", "Running",
                                     {"cpu": "2", "memory": "1Gi"}, "oldpg"))
        running.status = TaskStatus.RELEASING
        ni.add_task(running)
        assert ni.idle.milli_cpu == 0
        pg = build_pod_group("j1", "ns", min_member=1)
        job = JobInfo("ns/j1", pg)
        t = TaskInfo(build_pod("ns", "p0", "", "Pending",
                               {"cpu": "2", "memory": "1Gi"}, "j1"))
        job.add_task_info(t)
        arr = flatten_snapshot({"ns/j1": job}, {"n1": ni}, [t])
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assert int(np.asarray(res.assigned)[0]) == 0
        assert int(np.asarray(res.kind)[0]) == 1
        assert not np.asarray(res.job_ready)[0]

    def test_pipeline_survives_when_job_ready_via_running(self, solver):
        # job already ready via a running task; the extra pending task that
        # fits only FutureIdle pipelines and survives commit
        ni = NodeInfo(build_node("n1", {"cpu": "4", "memory": "8Gi"}))
        releasing = TaskInfo(build_pod("ns", "victim", "n1", "Running",
                                       {"cpu": "4", "memory": "1Gi"}, "oldpg"))
        releasing.status = TaskStatus.RELEASING
        ni.add_task(releasing)
        assert ni.idle.milli_cpu == 0 and ni.future_idle().milli_cpu == 4000
        pg = build_pod_group("j1", "ns", min_member=1)
        job = JobInfo("ns/j1", pg)
        runner = TaskInfo(build_pod("ns", "r0", "n2", "Running",
                                    {"cpu": "1", "memory": "1Gi"}, "j1"))
        job.add_task_info(runner)  # ready_base = 1 >= min_member
        t = TaskInfo(build_pod("ns", "p0", "", "Pending",
                               {"cpu": "2", "memory": "1Gi"}, "j1"))
        job.add_task_info(t)
        arr = flatten_snapshot({"ns/j1": job}, {"n1": ni}, [t])
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assert int(np.asarray(res.assigned)[0]) == 0
        assert int(np.asarray(res.kind)[0]) == 1  # pipelined, survives
        assert np.asarray(res.job_ready)[0]

    def test_binpack_prefers_used_node(self, solver):
        # with binpack, the second task lands on the same node as the first
        jobs, nodes, tasks = make_problem(
            [("n1", "4", "8Gi"), ("n2", "4", "8Gi")],
            [("j1", 1, [("1", "1Gi")]), ("j2", 1, [("1", "1Gi")])])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solver(arr, params_dict(arr, binpack_weight=1.0))
        assigned = np.asarray(res.assigned)[:2]
        assert assigned[0] == assigned[1]

    def test_least_requested_spreads(self):
        # spreading under ties needs intra-round state visibility: the
        # sequential solver has it natively; the rounds solver gets it in
        # fidelity mode (per_node_cap=1)
        jobs, nodes, tasks = make_problem(
            [("n1", "4", "8Gi"), ("n2", "4", "8Gi")],
            [("j1", 1, [("1", "1Gi")]), ("j2", 1, [("1", "1Gi")])])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, least_req_weight=1.0)
        for res in (solve_allocate_sequential(arr.device_dict(), p),
                    solve_allocate(arr.device_dict(), p, per_node_cap=1)):
            assigned = np.asarray(res.assigned)[:2]
            assert assigned[0] != assigned[1]

    def test_best_effort_task_counts_ready_without_assignment(self, solver):
        # a best-effort (zero-request) task counts toward min_member even
        # while pending; job with min=1 and only a best-effort task is ready
        pg = build_pod_group("j1", "ns", min_member=1)
        job = JobInfo("ns/j1", pg)
        t = TaskInfo(build_pod("ns", "be", "", "Pending", {}, "j1"))
        job.add_task_info(t)
        nodes = {"n1": NodeInfo(build_node("n1", {"cpu": "1", "memory": "1Gi"}))}
        arr = flatten_snapshot({"ns/j1": job}, nodes, [t])
        res = solver(arr, params_dict(arr, least_req_weight=1.0))
        assert np.asarray(res.job_ready)[0]


class TestSolverScale:
    def test_many_tasks_many_nodes(self):
        # 200 tasks over 20 nodes, all should fit exactly
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "10", "100Gi") for i in range(20)],
            [(f"j{k}", 10, [("1", "1Gi")] * 10) for k in range(20)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        res = solve_allocate(arr.device_dict(),
                             params_dict(arr, least_req_weight=1.0))
        assigned = np.asarray(res.assigned)[:200]
        assert (assigned >= 0).all()
        assert np.asarray(res.job_ready)[:20].all()
        # capacity respected per node
        counts = np.bincount(assigned, minlength=arr.N)
        assert counts.max() <= 10

    def test_rounds_and_sequential_agree_on_low_contention(self):
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(4)],
            [(f"j{k}", 2, [("1", "2Gi")] * 2) for k in range(6)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, binpack_weight=1.0)
        r1 = solve_allocate(arr.device_dict(), p)
        r2 = solve_allocate_sequential(arr.device_dict(), p)
        assert np.asarray(r1.job_ready).tolist() == np.asarray(r2.job_ready).tolist()
        # both fully place every job (assignments may differ in order)
        assert (np.asarray(r1.assigned)[:12] >= 0).all()
        assert (np.asarray(r2.assigned)[:12] >= 0).all()


class TestFlattenCache:
    """Incremental flatten must be indistinguishable from a full flatten."""

    def _assert_same(self, arr, jobs, nodes, tasks):
        ref = flatten_snapshot(jobs, nodes, tasks)
        for k, v in arr.device_dict().items():
            ref_v = ref.device_dict()[k]
            # cached vocab may be wider (it only grows); compare the
            # common prefix of resource dims
            if v.ndim == 2 and v.shape[1] >= ref_v.shape[1] > 0 \
                    and k != "sig_masks":
                assert np.array_equal(v[:, :ref_v.shape[1]], ref_v), k
            else:
                assert np.array_equal(v, ref_v), k

    def test_warm_reuse_and_invalidation(self):
        from volcano_tpu.ops import FlattenCache

        jobs, nodes, tasks = make_problem(
            [("n1", "8", "16Gi"), ("n2", "8", "16Gi")],
            [("j1", 2, [("1", "1Gi")] * 2), ("j2", 1, [("2", "2Gi")])])
        fc = FlattenCache()
        arr0 = flatten_snapshot(jobs, nodes, tasks, cache=fc)
        self._assert_same(arr0, jobs, nodes, tasks)

        # warm, nothing changed: wholesale reuse, same contents
        arr1 = flatten_snapshot(jobs, nodes, tasks, cache=fc)
        assert arr1.task_init_req is arr0.task_init_req
        self._assert_same(arr1, jobs, nodes, tasks)

        # bind one task: job status + node accounting both change
        job = jobs["ns/j1"]
        t0 = tasks[0]
        job.update_task_status(t0, TaskStatus.ALLOCATED)
        nodes["n1"].add_task(t0)
        remaining = [t for t in tasks if t is not t0]
        arr2 = flatten_snapshot(jobs, nodes, remaining, cache=fc)
        self._assert_same(arr2, jobs, nodes, remaining)
        n1_idx = [n.name for n in arr2.nodes_list].index("n1")
        assert arr2.node_idle[n1_idx, 0] == 7000.0  # 8 cores - 1 allocated

    def test_diverged_clone_cannot_alias_cache_key(self):
        """A session clone and the live cache object mutated independently
        after the clone must never share a flat_version (the flatten cache
        would silently serve one's rows for the other). Versions come from a
        global counter, so any two post-clone mutations produce distinct
        versions."""
        from volcano_tpu.ops import FlattenCache

        jobs, nodes, tasks = make_problem(
            [("n1", "8", "16Gi")],
            [("j1", 2, [("1", "1Gi"), ("2", "2Gi")])])
        live = nodes["n1"]
        session = live.clone()
        assert session.flat_version == live.flat_version  # warm reuse OK

        # session (e.g. a preempt-first conf) allocates the 1-CPU task...
        tasks_by_cpu = sorted(tasks, key=lambda t: t.resreq.milli_cpu)
        t0, t1 = tasks_by_cpu[0], tasks_by_cpu[1]
        session.add_task(t0.clone())
        # ...while the live object later takes a different mutation
        live.add_task(t1.clone())
        assert session.flat_version != live.flat_version
        # and flattening one then the other never reuses the stale row
        # (note: a flatten's arrays alias the cache's internal buffers and
        # are only valid until the next flatten against the same cache —
        # the session consumes them before the next cycle)
        fc = FlattenCache()
        arr_s = flatten_snapshot(jobs, {"n1": session}, tasks, cache=fc)
        assert arr_s.node_idle[0, 0] == 7000.0  # 8 - 1
        arr_l = flatten_snapshot(jobs, {"n1": live}, tasks, cache=fc)
        assert arr_l.node_idle[0, 0] == 6000.0  # 8 - 2, not a stale 7000

    def test_vocab_growth_on_new_scalar(self):
        from volcano_tpu.ops import FlattenCache
        from volcano_tpu.api import JobInfo, TaskInfo

        jobs, nodes, tasks = make_problem(
            [("n1", "8", "16Gi")], [("j1", 1, [("1", "1Gi")])])
        fc = FlattenCache()
        flatten_snapshot(jobs, nodes, tasks, cache=fc)

        # a GPU job arrives later: vocab must grow, blocks recompute
        pg = build_pod_group("jg", "ns", min_member=1)
        gjob = JobInfo("ns/jg", pg)
        p = build_pod("ns", "jg-0", "", "Pending",
                      {"cpu": "1", "memory": "1Gi", "nvidia.com/gpu": 2},
                      "jg")
        gt = TaskInfo(p)
        gjob.add_task_info(gt)
        jobs2 = dict(jobs)
        jobs2[gjob.uid] = gjob
        arr = flatten_snapshot(jobs2, nodes, tasks + [gt], cache=fc)
        gi = arr.vocab.index("nvidia.com/gpu")
        assert gi is not None
        assert arr.task_init_req[1, gi] == 2000.0  # scalars are milli-units


class TestFlattenIncrementalIdentity:
    """The delta-driven flatten (persistent buffers, prefix/suffix reuse,
    cached signature/queue tables) must produce byte-identical packed
    buffers to a cold flatten across every churn pattern: job
    rotation/addition/removal, task-status mutation, node accounting and
    spec changes, signature-table changes mid-sequence, queue changes and
    bucket transitions."""

    def _build(self, n_jobs, tpj=3, first_pod_extra=None):
        from types import SimpleNamespace

        nodes = {}
        for i in range(4):
            nodes[f"n{i}"] = NodeInfo(
                build_node(f"n{i}", {"cpu": "32", "memory": "64Gi"},
                           labels={"zone": f"z{i % 2}"}))
        jobs, tasks_by_job = {}, {}
        for k in range(n_jobs):
            pg = build_pod_group(f"j{k}", "ns", min_member=tpj,
                                 queue=f"q{k % 3}")
            job = JobInfo(f"ns/j{k}", pg)
            ts = []
            for i in range(tpj):
                p = build_pod("ns", f"j{k}-{i}", "", "Pending",
                              {"cpu": str(1 + k % 2),
                               "memory": f"{1 + i % 2}Gi"}, f"j{k}")
                t = TaskInfo(p)
                job.add_task_info(t)
                ts.append(t)
            jobs[job.uid] = job
            tasks_by_job[job.uid] = ts
        queues = {f"q{i}": SimpleNamespace(weight=i + 1, capability=None)
                  for i in range(4)}
        return jobs, nodes, tasks_by_job, queues

    def _assert_packed_identical(self, fc, jobs_s, nodes, tasks_s, queues):
        from volcano_tpu.ops import FlattenCache

        warm = flatten_snapshot(jobs_s, nodes, tasks_s, cache=fc,
                                queues=queues)
        wf, wi, wl = warm.packed()
        # cold reference shares the vocab object so R (and the packed
        # layout) line up; everything else recomputes from scratch
        cold = flatten_snapshot(jobs_s, nodes, tasks_s,
                                cache=FlattenCache(fc.vocab), queues=queues)
        cf, ci, cl = cold.packed()
        assert wl == cl
        assert wf.tobytes() == cf.tobytes()
        assert wi.tobytes() == ci.tobytes()

    def test_identity_across_churn_patterns(self):
        from volcano_tpu.ops import FlattenCache

        jobs, nodes, tasks_by_job, queues = self._build(8)
        fc = FlattenCache()
        uids = list(jobs)

        def snap(excl=()):
            jobs_s = {u: j for u, j in jobs.items() if u not in excl}
            tasks_s = [t for u in jobs_s
                       for t in tasks_by_job[u]
                       if t.status == TaskStatus.PENDING]
            return jobs_s, tasks_s

        def check(excl=()):
            jobs_s, tasks_s = snap(excl)
            self._assert_packed_identical(fc, jobs_s, nodes, tasks_s,
                                          queues)

        check()                      # cold baseline
        check()                      # wholesale reuse
        check(excl={uids[3]})        # remove a middle job
        check(excl={uids[5]})        # rotate: re-add 3, drop 5
        # mutate: one task leaves the pending set (job version bump)
        j0 = jobs[uids[0]]
        t0 = tasks_by_job[uids[0]][0]
        j0.update_task_status(t0, TaskStatus.ALLOCATED)
        nodes["n1"].add_task(t0)     # node accounting churn rides along
        check()
        # spec churn: relabel one node (spec_version bump)
        n2 = nodes["n2"]
        n2.set_node(build_node("n2", {"cpu": "32", "memory": "64Gi"},
                               labels={"zone": "z9"}))
        check()
        # signature-table change mid-sequence: a selector job appears...
        pg = build_pod_group("jsel", "ns", min_member=1, queue="q3")
        jsel = JobInfo("ns/jsel", pg)
        ps = build_pod("ns", "jsel-0", "", "Pending",
                       {"cpu": "1", "memory": "1Gi"}, "jsel",
                       node_selector={"zone": "z0"})
        tsel = TaskInfo(ps)
        jsel.add_task_info(tsel)
        jobs[jsel.uid] = jsel
        tasks_by_job[jsel.uid] = [tsel]
        check()
        check(excl={jsel.uid})       # ...and departs (table shrinks back)
        # bucket transition: enough new jobs to cross the T/J buckets
        for k in range(8, 20):
            pg = build_pod_group(f"j{k}", "ns", min_member=2,
                                 queue=f"q{k % 3}")
            job = JobInfo(f"ns/j{k}", pg)
            ts = []
            for i in range(2):
                p = build_pod("ns", f"j{k}-{i}", "", "Pending",
                              {"cpu": "1", "memory": "1Gi"}, f"j{k}")
                t = TaskInfo(p)
                job.add_task_info(t)
                ts.append(t)
            jobs[job.uid] = job
            tasks_by_job[job.uid] = ts
        check()
        # node add + remove (node-axis relayout)
        nodes["n9"] = NodeInfo(
            build_node("n9", {"cpu": "16", "memory": "32Gi"}))
        check()
        del nodes["n0"]
        check(excl={uids[1]})

    def test_vocab_growth_keeps_identity(self):
        from volcano_tpu.ops import FlattenCache

        jobs, nodes, tasks_by_job, queues = self._build(4)
        fc = FlattenCache()
        tasks = [t for u in jobs for t in tasks_by_job[u]]
        self._assert_packed_identical(fc, jobs, nodes, tasks, queues)
        # a GPU job grows the vocab: full re-assembly, identical results
        pg = build_pod_group("jg", "ns", min_member=1, queue="q0")
        gjob = JobInfo("ns/jg", pg)
        p = build_pod("ns", "jg-0", "", "Pending",
                      {"cpu": "1", "memory": "1Gi", "nvidia.com/gpu": 1},
                      "jg")
        gt = TaskInfo(p)
        gjob.add_task_info(gt)
        jobs[gjob.uid] = gjob
        tasks_by_job[gjob.uid] = [gt]
        tasks = [t for u in jobs for t in tasks_by_job[u]]
        self._assert_packed_identical(fc, jobs, nodes, tasks, queues)


class TestFlattenEventIdentity(TestFlattenIncrementalIdentity):
    """The event-sourced flatten (dirty rows marked by a fed ledger,
    patched in place at cycle start) must stay byte-identical to a cold
    flatten across seeded churn — adds, deletes, binds, node drains,
    job-layout crossings, bucket resizes — including the cycle after a
    deliberately dropped/duplicated ledger delta forces the epoch-check
    fallback. Inherits the incremental matrix's builders; every mutation
    here is paired with the feed the SchedulerCache hooks would emit."""

    def _fed_cache(self):
        from volcano_tpu.ops import FlattenCache

        fc = FlattenCache()
        fc.enable_events()
        return fc

    def test_identity_across_seeded_churn(self):
        import random

        from volcano_tpu.ops import FlattenCache

        rng = random.Random(11)
        jobs, nodes, tasks_by_job, queues = self._build(8)
        fc = self._fed_cache()
        held = {}

        def snap():
            jobs_s = dict(jobs)
            tasks_s = [t for u in jobs_s
                       for t in tasks_by_job[u]
                       if t.status == TaskStatus.PENDING]
            return jobs_s, tasks_s

        modes = []

        def check():
            jobs_s, tasks_s = snap()
            self._assert_packed_identical(fc, jobs_s, nodes, tasks_s,
                                          queues)
            modes.append(fc.last_flatten_mode)

        check()                     # cold baseline
        check()                     # quiet: event mode, zero rows
        assert fc.last_flatten_mode == "event"
        assert fc.last_rows_patched == 0

        next_job = [100]

        def churn_once():
            op = rng.choice(["bind", "acct", "acct", "minavail", "quiet",
                             "add_job", "del_job", "drain", "spec"])
            if op == "bind":
                uid = rng.choice(list(jobs))
                pend = [t for t in tasks_by_job[uid]
                        if t.status == TaskStatus.PENDING]
                if not pend:
                    return
                t, node = pend[0], rng.choice(list(nodes.values()))
                jobs[uid].update_task_status(t, TaskStatus.ALLOCATED)
                node.add_task(t)
                fc.feed_event("pod", "update", job=uid, node=node.name)
            elif op == "acct":
                name = rng.choice(list(nodes))
                ni = nodes[name]
                t = held.pop(name, None)
                if t is not None:
                    ni.remove_task(t)
                    fc.feed_event("pod", "delete", job="ns/held",
                                  node=name)
                else:
                    p = build_pod("ns", f"held-{name}-{rng.random()}",
                                  name, "Running",
                                  {"cpu": "2", "memory": "1Gi"}, "held")
                    t = TaskInfo(p)
                    t.status = TaskStatus.RUNNING
                    ni.add_task(t)
                    held[name] = t
                    fc.feed_event("pod", "add", job="ns/held", node=name)
            elif op == "minavail":
                uid = rng.choice(list(jobs))
                pg = jobs[uid].pod_group
                pg.spec.min_member = 1 + rng.randrange(3)
                jobs[uid].set_pod_group(pg)
                fc.feed_event("podgroup", "update", job=uid)
            elif op == "add_job":
                k = next_job[0]
                next_job[0] += 1
                pg = build_pod_group(f"j{k}", "ns", min_member=2,
                                     queue=f"q{k % 3}")
                job = JobInfo(f"ns/j{k}", pg)
                ts = []
                for i in range(2):
                    p = build_pod("ns", f"j{k}-{i}", "", "Pending",
                                  {"cpu": "1", "memory": "1Gi"}, f"j{k}")
                    t = TaskInfo(p)
                    job.add_task_info(t)
                    ts.append(t)
                jobs[job.uid] = job
                tasks_by_job[job.uid] = ts
                fc.feed_event("pod", "add", job=job.uid)
            elif op == "del_job":
                if len(jobs) < 3:
                    return
                uid = rng.choice(list(jobs))
                del jobs[uid]
                fc.feed_event("pod", "delete", job=uid)
            elif op == "drain":
                # drain: running pods leave, then the node itself does
                if len(nodes) < 3:
                    return
                name = rng.choice(list(nodes))
                t = held.pop(name, None)
                if t is not None:
                    nodes[name].remove_task(t)
                    fc.feed_event("pod", "delete", job="ns/held",
                                  node=name)
                del nodes[name]
                fc.feed_event("node", "delete", node=name)
            elif op == "spec":
                name = rng.choice(list(nodes))
                nodes[name].set_node(build_node(
                    name, {"cpu": "32", "memory": "64Gi"},
                    labels={"zone": f"z{rng.randrange(4)}"}))
                fc.feed_event("node", "update", node=name)

        for cycle in range(40):
            for _ in range(rng.randrange(3)):
                churn_once()
            check()
        # bucket resize: a burst of jobs crosses the T/J buckets
        for _ in range(14):
            next_job[0] += 1
            k = next_job[0]
            pg = build_pod_group(f"j{k}", "ns", min_member=3,
                                 queue=f"q{k % 3}")
            job = JobInfo(f"ns/j{k}", pg)
            ts = []
            for i in range(3):
                p = build_pod("ns", f"j{k}-{i}", "", "Pending",
                              {"cpu": "1", "memory": "1Gi"}, f"j{k}")
                t = TaskInfo(p)
                job.add_task_info(t)
                ts.append(t)
            jobs[job.uid] = job
            tasks_by_job[job.uid] = ts
            fc.feed_event("pod", "add", job=job.uid)
        check()
        check()
        # the ladder must have exercised every rung across the matrix
        assert "event" in modes and "cold" in modes, modes
        assert any(m in ("incremental", "cold") for m in modes[2:]), modes

    def test_dropped_event_falls_back_then_recovers(self):
        from volcano_tpu.resilience.faultinject import faults

        jobs, nodes, tasks_by_job, queues = self._build(6)
        fc = self._fed_cache()

        def check():
            tasks = [t for u in jobs for t in tasks_by_job[u]
                     if t.status == TaskStatus.PENDING]
            self._assert_packed_identical(fc, jobs, nodes, tasks, queues)

        check()
        check()
        assert fc.last_flatten_mode == "event"
        try:
            # drop exactly one delta on the feed's floor: a node-row
            # accounting change the ledger never hears about
            faults.arm_once("flatten_event")
            ni = nodes["n1"]
            p = build_pod("ns", "ghost", "n1", "Running",
                          {"cpu": "4", "memory": "2Gi"}, "ghost")
            t = TaskInfo(p)
            t.status = TaskStatus.RUNNING
            ni.add_task(t)
            fc.feed_event("pod", "add", job="ns/ghost", node="n1")
            check()  # byte-identity held BY THE FALLBACK, not the patch
            assert fc.last_flatten_mode in ("incremental", "cold")
            assert fc.last_fallback_reason == "epoch_mismatch"
            check()  # ledger re-baselined: event mode resumes
            assert fc.last_flatten_mode == "event"

            # duplicated delivery skews the epoch the other way
            faults.arm_once("flatten_event_dup")
            ni.remove_task(t)
            fc.feed_event("pod", "delete", job="ns/ghost", node="n1")
            check()
            assert fc.last_fallback_reason == "epoch_mismatch"
            check()
            assert fc.last_flatten_mode == "event"
        finally:
            faults.reset()


class TestFusedDelta:
    """solve_allocate_delta (scatter fused into the solve dispatch) must
    match solve_allocate on the same snapshot, across churned sessions."""

    def test_fused_matches_plain_across_sessions(self):
        from volcano_tpu.ops import FlattenCache, PackedDeviceCache
        from volcano_tpu.ops.solver import solve_allocate_delta

        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "16Gi") for i in range(6)],
            [(f"j{k}", 2, [("1", "1Gi")] * 3) for k in range(5)])
        fc, dc = FlattenCache(), PackedDeviceCache(chunk=64)
        node_list = list(nodes.values())

        for s in range(3):
            # churn: dirty one node row via real accounting
            if s:
                from volcano_tpu.api import TaskInfo
                p = build_pod("ns", f"runner-{s}", node_list[s].name,
                              "Running", {"cpu": "1", "memory": "1Gi"}, "j0")
                t = TaskInfo(p)
                t.status = TaskStatus.RUNNING
                node_list[s].add_task(t)
            arr = flatten_snapshot(jobs, nodes, tasks, cache=fc)
            p = params_dict(arr, least_req_weight=1.0)
            ref = solve_allocate(arr.device_dict(), p)
            fbuf, ibuf, layout = arr.packed()
            f2d, i2d, fi, fv, ii, iv = dc.plan_delta(fbuf, ibuf, layout)
            res, nf, ni = solve_allocate_delta(
                f2d, i2d, fi, fv, ii, iv, layout, p,
                score_families=("binpack", "kube"))
            dc.commit(nf, ni)
            np.testing.assert_array_equal(np.asarray(res.assigned),
                                          np.asarray(ref.assigned))
            np.testing.assert_array_equal(np.asarray(res.kind),
                                          np.asarray(ref.kind))
            if s:
                # steady state ships a delta, not the full buffers
                total = (dc._host_f.size + dc._host_i.size) // dc.chunk
                assert dc.last_shipped_chunks < total


class TestFusedChoiceParity:
    """ops.pallas_kernels.fused_choice must be observationally identical
    to the dense fits_matrix/score_matrix/argmax path: solve_allocate with
    fused="on" (pallas; interpret mode on CPU) vs "off" on randomized
    aligned problems, across herd modes, score families and queue caps."""

    def _problem(self, seed):
        import numpy as np

        from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION
        from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
        from volcano_tpu.ops import flatten_snapshot

        rng = np.random.default_rng(seed)
        nodes = {}
        for i in range(128):  # buckets to N=128 (lane-aligned)
            rl = {"cpu": str(int(rng.integers(2, 9))),
                  "memory": f"{int(rng.integers(4, 17))}Gi", "pods": 110}
            nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                           capacity=dict(rl)))
        jobs, tasks = {}, []
        for k in range(10):
            tpj = 4  # fixed: total 40 tasks buckets to 40 (8-aligned)
            pg = PodGroup(name=f"j{k}", namespace="f",
                          spec=PodGroupSpec(min_member=tpj))
            job = JobInfo(f"f/j{k}", pg)
            for i in range(tpj):
                pod = Pod(name=f"j{k}-{i}", namespace="f",
                          annotations={POD_GROUP_ANNOTATION: f"j{k}"},
                          containers=[{"requests": {
                              "cpu": str(int(rng.integers(1, 4))),
                              "memory": f"{int(rng.integers(1, 5))}Gi"}}])
                t = TaskInfo(pod)
                job.add_task_info(t)
                tasks.append(t)
            jobs[job.uid] = job
        arr = flatten_snapshot(jobs, nodes, tasks)
        return arr

    @pytest.mark.parametrize("herd,families,qcap,seed", [
        ("pack", ("binpack",), False, 11),
        ("spread", ("kube",), False, 12),
        ("pack", ("binpack", "kube"), True, 0),
        ("spread", ("binpack", "kube"), True, 37),
    ])
    def test_fused_matches_dense(self, herd, families, qcap, seed):
        import numpy as np

        from volcano_tpu.ops.pallas_kernels import fused_choice_supported
        from volcano_tpu.ops.solver import (
            NEG, fits_matrix, score_matrix, solve_allocate,
        )

        arr = self._problem(seed=seed)
        assert fused_choice_supported(arr.T, arr.N), (arr.T, arr.N)
        if qcap:
            arr.queue_request[:] = 1e12
            arr.queue_weight[:1] = 1.0
        p = params_dict(arr, binpack_weight=1.0 if "binpack" in families
                        else 0.0,
                        least_req_weight=1.0 if "kube" in families else 0.0)
        d = arr.device_dict()
        r_off = solve_allocate(d, p, herd_mode=herd,
                               score_families=families,
                               use_queue_cap=qcap, fused="off")
        r_on = solve_allocate(d, p, herd_mode=herd,
                              score_families=families,
                              use_queue_cap=qcap, fused="on")
        a_off = np.asarray(r_off.assigned)
        a_on = np.asarray(r_on.assigned)
        # outcome parity: same jobs satisfied, same task fate partition.
        # On the real TPU the assignments are bitwise identical (a
        # 40-seed on-device corpus verified this); the CPU interpret
        # path can differ by 1 ulp of score through XLA FMA contraction,
        # which may flip argmax TIES — so divergent choices are accepted
        # only between equal-score nodes.
        assert (np.asarray(r_off.kind) == np.asarray(r_on.kind)).all()
        assert (np.asarray(r_off.job_ready)
                == np.asarray(r_on.job_ready)).all()
        assert ((a_off >= 0) == (a_on >= 0)).all()
        diff = np.nonzero((a_off != a_on) & (a_off >= 0))[0]
        if len(diff):
            import jax.numpy as jnp
            sig = (np.asarray(d["sig_masks"])[np.asarray(d["task_sig"])]
                   & np.asarray(d["node_valid"])[None, :])
            feas = np.asarray(fits_matrix(
                jnp.asarray(d["task_init_req"]),
                jnp.asarray(d["node_idle"]),
                jnp.asarray(d["thresholds"]),
                jnp.asarray(d["scalar_dim_mask"]))) & sig
            score = np.asarray(score_matrix(
                jnp.asarray(d["task_init_req"]),
                jnp.asarray(d["node_idle"]),
                jnp.asarray(d["node_used"]),
                jnp.asarray(d["node_alloc"]), p, families))
            for t in diff:
                s1, s2 = score[t, a_off[t]], score[t, a_on[t]]
                assert feas[t, a_off[t]] and feas[t, a_on[t]]
                assert abs(s1 - s2) <= 1e-4 * max(abs(s1), 1.0), (
                    t, a_off[t], a_on[t], s1, s2)

    def test_shape_support_rule(self):
        from volcano_tpu.ops.pallas_kernels import fused_choice_supported

        assert fused_choice_supported(64, 16)      # small: full-axis blocks
        assert fused_choice_supported(10240, 2048)  # headline: 512-tiles
        # huge axis with no 128-divisor: no clean tiling -> dense path
        assert not fused_choice_supported(10240, 3000)

    def test_fused_matches_dense_hdrf(self):
        """The hdrf branch takes an EXTRA fused pass per round (the
        placeability prefilter) — exercise fused="on" with the
        hierarchical rank+cap so that path can't regress silently (it
        once hit a NameError reachable only on TPU/forced-fused runs)."""
        import numpy as np
        from types import SimpleNamespace

        from volcano_tpu.api import Resource
        from volcano_tpu.ops.hdrf import build_hdrf
        from volcano_tpu.ops.solver import solve_allocate

        arr = self._problem(seed=5)
        queues = {}
        hier = [("root/a", "10/8"), ("root/b", "10/2"),
                ("root/c/x", "10/5/6"), ("root/c/y", "10/5/2")]
        for k, job in enumerate(arr.jobs_list):
            h, w = hier[k % 4]
            qn = f"q{k % 4}"
            job.queue = qn
            queues[qn] = SimpleNamespace(
                name=qn, weight=1, capability=None, hierarchy=h,
                weights=w)
        arr.drf_total = (arr.node_alloc
                         * arr.node_valid[:, None]).sum(axis=0).astype(
            np.float32)
        build_hdrf(arr, queues, {}, Resource())
        p = params_dict(arr, binpack_weight=1.0)
        d = arr.device_dict()
        kw = dict(herd_mode="pack", score_families=("binpack",),
                  use_drf_order=True, use_hdrf_order=True)
        r_off = solve_allocate(d, p, fused="off", **kw)
        r_on = solve_allocate(d, p, fused="on", **kw)
        assert (np.asarray(r_off.kind) == np.asarray(r_on.kind)).all()
        assert (np.asarray(r_off.job_ready)
                == np.asarray(r_on.job_ready)).all()
        a_off, a_on = np.asarray(r_off.assigned), np.asarray(r_on.assigned)
        assert ((a_off >= 0) == (a_on >= 0)).all()


def _result(assigned, kind, n_nodes, compact=True, rounds=4):
    """A SolveResult as the solve entries return it: device arrays, the
    int16 compact form when ``compact`` (packed for ``n_nodes``)."""
    import jax.numpy as jnp

    from volcano_tpu.ops.solver import SolveResult, _compact

    a = jnp.asarray(assigned, jnp.int32)
    k = jnp.asarray(kind, jnp.int32)
    return SolveResult(
        assigned=a, kind=k, job_ready=jnp.ones(1, bool),
        rounds=jnp.int32(rounds),
        compact=_compact(a, k, n_nodes) if compact else None)


class TestCollectAssignment:
    """ops.solver.collect_assignment: one host collect for every solve
    entry's result, checked by AllocateAction._check_solver_output."""

    @pytest.mark.parametrize("case", [
        "compact", "wide_nodes", "sharded", "out_of_range"])
    def test_collect(self, case):
        from volcano_tpu.actions.allocate import AllocateAction
        from volcano_tpu.ops.solver import (
            COMPACT_KIND_SHIFT, collect_assignment,
        )

        assigned, kind = [3, -1, 0, 7], [0, -1, 1, 0]
        n_nodes = 8
        if case == "wide_nodes":
            # more nodes than the int16 packing holds: the compact form
            # is the unavailable sentinel, the int32 arrays are read
            n_nodes = (1 << COMPACT_KIND_SHIFT) + 8
            assigned[3] = n_nodes - 1
        if case == "out_of_range":
            assigned[0] = n_nodes
        res = _result(assigned, kind, n_nodes,
                      compact=case != "sharded")
        got_a, got_k, rounds = collect_assignment(res, n_nodes)
        assert rounds == 4 and isinstance(rounds, int)
        assert isinstance(got_a, np.ndarray)
        if case == "out_of_range":
            with pytest.raises(RuntimeError, match="sanity"):
                AllocateAction._check_solver_output(
                    got_a, got_k, len(assigned), n_nodes)
            return
        np.testing.assert_array_equal(got_a, assigned)
        np.testing.assert_array_equal(got_k, kind)
        AllocateAction._check_solver_output(got_a, got_k, len(assigned),
                                            n_nodes)


# ---------------------------------------------------------------------------
# the round budget under contention: whole-GPU gangs on an empty cluster
# ---------------------------------------------------------------------------

_GIB = float(1 << 30)

#: (pods, GPUs per pod, queue) of the gangs a steady GPU cluster had
#: pending when its device solve placed nothing: 33 gangs, more than
#: max_gang_iters (12), asking about 3,900 GPUs of the 128 here
_STALLED_GANGS = [
    (4, 8, 0), (16, 8, 0), (32, 2, 0), (32, 2, 0), (32, 1, 0), (32, 2, 0),
    (8, 8, 0), (16, 4, 0), (8, 8, 0), (16, 4, 0), (16, 4, 0), (4, 8, 0),
    (4, 8, 0), (2, 8, 0), (32, 1, 0), (8, 8, 0), (32, 8, 0), (32, 4, 1),
    (8, 4, 1), (32, 1, 1), (32, 4, 1), (32, 4, 1), (16, 4, 1), (32, 4, 1),
    (8, 8, 2), (32, 8, 2), (16, 8, 2), (32, 8, 2), (16, 8, 2), (8, 8, 2),
    (8, 8, 2), (4, 8, 2), (32, 8, 2)]


def _gpu_gangs(n_nodes, gangs, T=640):
    """Snapshot arrays of ``n_nodes`` empty nodes of 64 cpu, 512Gi and 8
    nvidia.com/gpu (a scalar dimension) and ``gangs`` of whole-GPU pods
    (2 cpu and 16Gi per GPU, minAvailable = replicas) in queues of weight
    1:2:3, as the allocate action flattens them under the default conf."""
    N, J, Q = n_nodes, len(gangs) + 1, 4
    idle = np.zeros((N, 3), np.float32)
    idle[:] = (64000.0, 512 * _GIB, 8000.0)
    task_job = np.full(T, J - 1, np.int32)
    req = np.zeros((T, 3), np.float32)
    valid = np.zeros(T, bool)
    job_min = np.zeros(J, np.int32)
    job_valid = np.zeros(J, bool)
    job_queue = np.zeros(J, np.int32)
    off = 0
    for j, (pods, gpus, q) in enumerate(gangs):
        req[off:off + pods] = (2000.0 * gpus, 16 * _GIB * gpus,
                               1000.0 * gpus)
        task_job[off:off + pods] = j
        valid[off:off + pods] = True
        job_min[j], job_valid[j], job_queue[j] = pods, True, q
        off += pods
    qreq = np.zeros((Q, 3), np.float32)
    for j in range(len(gangs)):
        qreq[job_queue[j]] += req[task_job == j].sum(axis=0)
    return {
        "node_idle": idle, "node_extra_future": np.zeros_like(idle),
        "node_used": np.zeros_like(idle), "node_alloc": idle.copy(),
        "node_npods": np.zeros(N, np.int32),
        "node_max_pods": np.full(N, 110, np.int32),
        "node_valid": np.ones(N, bool), "sig_masks": np.ones((1, N), bool),
        "task_init_req": req, "task_req": req.copy(), "task_job": task_job,
        "task_rank": np.arange(T, dtype=np.int32),
        "task_sig": np.zeros(T, np.int32),
        "task_counts_ready": valid.copy(), "task_valid": valid,
        "job_min": job_min, "job_ready_base": np.zeros(J, np.int32),
        "job_queue": job_queue, "job_valid": job_valid,
        "job_drf_prerank": np.zeros(J, np.int32),
        "job_drf_allocated": np.zeros((J, 3), np.float32),
        "drf_total": idle.sum(axis=0),
        "queue_weight": np.array([1.0, 2.0, 3.0, 0.0], np.float32),
        "queue_capability": np.full((Q, 3), np.inf, np.float32),
        "queue_allocated": np.zeros((Q, 3), np.float32),
        "queue_request": qreq,
        "thresholds": np.array([10.0, 1.0, 10.0], np.float32),
        "scalar_dim_mask": np.array([False, False, True]),
    }


def _default_conf_params(n_nodes):
    # binpack (nvidia.com/gpu weighted 2), least-requested and balanced at
    # weight 1: the conf's spread herd
    return {"binpack_weight": np.float32(1.0),
            "binpack_res_weights": np.array([1.0, 1.0, 2.0], np.float32),
            "least_req_weight": np.float32(1.0),
            "most_req_weight": np.float32(0.0),
            "balanced_weight": np.float32(1.0),
            "node_static": np.zeros(n_nodes, np.float32)}


def _default_conf_solve(a, params, sequential=False):
    if sequential:
        return solve_allocate_sequential(
            a, params, score_families=("binpack", "kube"),
            use_queue_cap=True, overflow_pass=True)
    return solve_allocate(a, params, herd_mode="spread",
                          score_families=("binpack", "kube"),
                          use_queue_cap=True, use_drf_order=True)


def _ready_jobs(a, res):
    """The jobs the solve made ready, after checking what a sound solve
    keeps: no node over any resource, no partial gang, and job_ready as
    the allocations count it."""
    assigned = np.asarray(res.assigned)
    kind = np.asarray(res.kind)
    ready = np.asarray(res.job_ready)
    placed = (assigned >= 0) & (kind == 0)
    used = np.zeros_like(a["node_idle"])
    np.add.at(used, assigned[placed], a["task_req"][placed])
    assert (used <= a["node_idle"] + a["thresholds"]).all()
    per_job = np.bincount(a["task_job"][placed],
                          minlength=a["job_min"].shape[0])
    for j in np.nonzero(a["job_valid"])[0]:
        assert bool(ready[j]) == (per_job[j] >= a["job_min"][j])
        assert ready[j] or per_job[j] == 0, f"gang {j} partly placed"
    return set(np.nonzero(ready & a["job_valid"])[0].tolist())


class TestRoundBudget:
    """Oversubscribed whole-GPU gangs on an empty cluster. With one round
    budget for the whole solve, the parallel gang iterations placed
    partial gangs, reverted them and spent every round, so the deferred
    jobs' solo retries ran none: the device solve placed nothing where the
    sequential oracle places gangs, and the next turn saw the same
    snapshot. Every admission phase now has a budget of its own."""

    def test_oversubscribed_gangs_place_a_job(self):
        a = _gpu_gangs(16, _STALLED_GANGS)
        params = _default_conf_params(16)
        assert _ready_jobs(a, _default_conf_solve(a, params, True))
        res = _default_conf_solve(a, params)
        assert _ready_jobs(a, res)
        assert int(res.cap_hits) == 0

    def test_gangs_and_one_pod_jobs_place_against_the_oracle(self):
        # three one-pod 8-GPU jobs behind the gangs, which any empty node
        # holds: the device solve starts one at least, and places no
        # fewer pods than the parity fuzz's slack of the sequential
        # oracle's
        gangs = _STALLED_GANGS + [(1, 8, 0), (1, 8, 1), (1, 8, 2)]
        a = _gpu_gangs(16, gangs)
        params = _default_conf_params(16)
        res = _default_conf_solve(a, params)
        oracle = _default_conf_solve(a, params, sequential=True)
        assert _ready_jobs(a, res) & set(range(len(_STALLED_GANGS),
                                              len(gangs)))
        _ready_jobs(a, oracle)

        def pods(r):
            return int((np.asarray(r.assigned) >= 0).sum())
        assert pods(res) >= 0.65 * pods(oracle)

    def test_gangs_that_cannot_fit_alone_yield_their_retries(self):
        # 16 gangs of 20 four-GPU pods ahead of 4 small gangs, on nodes
        # half in use (4 GPUs free each, 64 in all): each big gang places
        # part of itself in the parallel iterations and reverts, twice,
        # and more of them are deferred than the solve has iterations for
        # solo retries; none can reach 20 pods alone, so each is excluded
        # unretried and the small gangs start, as under the oracle
        gangs = [(20, 4, j % 3) for j in range(16)] \
            + [(4, 2, j % 3) for j in range(4)]
        a = _gpu_gangs(16, gangs)
        half = (32000.0, 256 * _GIB, 4000.0)
        a["node_idle"][:] = half
        a["node_used"][:] = half
        params = _default_conf_params(16)
        ready = _ready_jobs(a, _default_conf_solve(a, params))
        oracle = _ready_jobs(a, _default_conf_solve(a, params, True))
        assert ready == oracle == set(range(16, 20))

    def test_rounds_count_every_phase(self):
        # with a budget of 4 rounds a phase, SolveResult.rounds stays the
        # solve's total, past one phase's budget, and cap_hits counts the
        # phases the budget stopped while they still admitted
        a = _gpu_gangs(16, _STALLED_GANGS)
        res = solve_allocate(a, _default_conf_params(16), max_rounds=4,
                             herd_mode="spread",
                             score_families=("binpack", "kube"),
                             use_queue_cap=True, use_drf_order=True)
        assert int(res.rounds) > 4
        assert int(res.cap_hits) >= 1
        _ready_jobs(a, res)


_STEADY_CONF = """actions: "enqueue, allocate, backfill"
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


def _turns_until_every_gang_started(mode, gangs, n_nodes=16, turns=40):
    """Drive the whole control plane over ``n_nodes`` nodes of 8 GPUs
    with ``gangs`` submitted at once; a started job runs one turn and
    ends, freeing its room. Returns the turn by which every job had
    started, or None."""
    from volcano_tpu.client.store import NotFoundError
    from volcano_tpu.controllers import KubeletStandin
    from volcano_tpu.models import (
        Job, JobSpec, Node, Queue, QueueSpec, TaskSpec,
    )
    from volcano_tpu.standalone import Standalone

    conf = _STEADY_CONF
    if mode != "device":
        conf += ("configurations:\n- name: allocate\n  arguments:\n"
                 f"    mode: {mode}\n")
    s = Standalone(scheduler_conf=conf, metrics_port=0,
                   async_effectors=False, period=0.0)
    try:
        for c in s.controllers.controllers:
            if isinstance(c, KubeletStandin):
                c.grace_seconds = 0.0
        for q, w in (("q0", 1), ("q1", 2), ("q2", 3)):
            s.store.create("queues", Queue(name=q, spec=QueueSpec(weight=w)))
        size = {"cpu": "64", "memory": "512Gi", "pods": "110",
                "nvidia.com/gpu": "8"}
        for i in range(n_nodes):
            s.store.create("nodes", Node(name=f"n{i}", allocatable=dict(size),
                                         capacity=dict(size)))
        for j, (pods, gpus, q) in enumerate(gangs):
            gpu = {"nvidia.com/gpu": str(gpus)}
            container = {"name": "c", "requests": {
                "cpu": str(2 * gpus), "memory": f"{16 * gpus}Gi", **gpu},
                "limits": gpu}
            s.store.create("jobs", Job(
                name=f"g{j}", namespace="default", spec=JobSpec(
                    min_available=pods, queue=f"q{q}", tasks=[TaskSpec(
                        name="t", replicas=pods,
                        template={"spec": {"containers": [container]}})])))
        waiting = {f"g{j}": pods for j, (pods, _, _) in enumerate(gangs)}
        running = []
        for turn in range(1, turns + 1):
            s.run_once()
            bound = {}
            for p in s.store.list("pods", namespace="default"):
                if p.node_name:
                    job = p.name.rsplit("-t-", 1)[0]
                    bound[job] = bound.get(job, 0) + 1
            for name in running:
                for kind, obj in ([("jobs", name), ("podgroups", name)]
                                  + [("pods", f"{name}-t-{i}")
                                     for i in range(bound.get(name, 0))]):
                    try:
                        s.store.delete(kind, obj, "default")
                    except NotFoundError:
                        pass
            running = [n for n, k in waiting.items() if bound.get(n) == k]
            for name in running:
                del waiting[name]
            if not waiting:
                return turn
        return None
    finally:
        s.stop()


#: gangs that fit the 128 GPUs of 16 nodes one at a time, three times
#: the cluster in all, with one-pod 8-GPU jobs among them
_STEADY_GANGS = [g for g in _STALLED_GANGS if g[0] * g[1] <= 64] + [
    (1, 8, 0), (1, 8, 1), (1, 8, 2)]


class TestSteadyGpuTurns:
    """Turn after turn on the device path, as on the host path, every
    whole-GPU gang starts: a solve that places nothing would leave the
    next turn the same snapshot."""

    @pytest.mark.parametrize("mode", ["device", "host"])
    def test_every_gang_starts_within_bounded_turns(self, mode):
        assert _turns_until_every_gang_started(mode, _STEADY_GANGS) \
            is not None
