"""Sharded solver tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax

from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
from volcano_tpu.ops import flatten_snapshot, solve_allocate
from volcano_tpu.parallel import make_mesh, solve_allocate_sharded

from helpers import build_node, build_pod, build_pod_group
from test_solver import make_problem, params_dict


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    return make_mesh()


class TestShardedSolver:
    def test_matches_single_chip_pack(self, mesh):
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(16)],
            [(f"j{k}", 4, [("1", "2Gi")] * 4) for k in range(8)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, binpack_weight=1.0)
        single = solve_allocate(arr.device_dict(), p, herd_mode="pack",
                                score_families=("binpack",))
        sharded = solve_allocate_sharded(arr.device_dict(), p, mesh,
                                         herd_mode="pack",
                                         score_families=("binpack",))
        s1 = np.asarray(single.assigned)[:32]
        s2 = np.asarray(sharded.assigned)[:32]
        assert (s1 >= 0).all() and (s2 >= 0).all()
        assert np.asarray(sharded.job_ready)[:8].all()
        # same pack shape: identical per-node occupancy
        c1 = np.bincount(s1, minlength=arr.N)
        c2 = np.bincount(s2, minlength=arr.N)
        assert (c1 == c2).all()

    def test_gang_revert_across_shards(self, mesh):
        # cluster of 16 nodes x 2cpu; j1 needs 40 cpus (min 20): impossible;
        # j2 (min 4) must still fit after j1's revert
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "2", "8Gi") for i in range(16)],
            [("j1", 20, [("2", "1Gi")] * 20),
             ("j2", 4, [("1", "1Gi")] * 4)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, least_req_weight=1.0)
        res = solve_allocate_sharded(arr.device_dict(), p, mesh,
                                     herd_mode="spread",
                                     score_families=("kube",))
        ready = np.asarray(res.job_ready)
        assigned = np.asarray(res.assigned)
        assert not ready[0] and ready[1]
        assert (assigned[:20] == -1).all()
        assert (assigned[20:24] >= 0).all()

    def test_spread_striping(self, mesh):
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(8)],
            [(f"j{k}", 1, [("1", "1Gi")]) for k in range(16)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, least_req_weight=1.0)
        res = solve_allocate_sharded(arr.device_dict(), p, mesh,
                                     herd_mode="spread",
                                     score_families=("kube",))
        assigned = np.asarray(res.assigned)[:16]
        counts = np.bincount(assigned[assigned >= 0], minlength=arr.N)
        assert counts[:8].max() == 2  # 16 tasks striped over 8 nodes

    def test_queue_caps_match_single_chip(self, mesh):
        """In-kernel proportional fair share on the mesh: a 3:1 weight
        split of a saturated 8-cpu cluster yields 6:2, identical to the
        single-device kernel (deserved is water-filled from a psum'd
        cluster total; queue bookkeeping is replicated)."""
        nodes = {f"n{i}": NodeInfo(build_node(
            f"n{i}", {"cpu": "1", "memory": "100Gi"})) for i in range(8)}
        jobs, tasks = {}, []
        for q, jname in (("q1", "jA"), ("q2", "jB")):
            pg = build_pod_group(jname, "ns", min_member=1, queue=q)
            job = JobInfo(f"ns/{jname}", pg)
            for i in range(8):
                p = build_pod("ns", f"{jname}-{i}", "", "Pending",
                              {"cpu": "1", "memory": "1Gi"}, jname)
                t = TaskInfo(p)
                job.add_task_info(t)
                tasks.append(t)
            jobs[job.uid] = job
        from types import SimpleNamespace
        queues = {"q1": SimpleNamespace(weight=3, capability=None),
                  "q2": SimpleNamespace(weight=1, capability=None)}
        arr = flatten_snapshot(jobs, nodes, tasks, queues=queues)
        arr.fill_queue_demand()
        p = params_dict(arr, least_req_weight=1.0)
        single = solve_allocate(arr.device_dict(), p, herd_mode="spread",
                                score_families=("kube",),
                                use_queue_cap=True)
        sharded = solve_allocate_sharded(arr.device_dict(), p, mesh,
                                         herd_mode="spread",
                                         score_families=("kube",),
                                         use_queue_cap=True)
        for res in (single, sharded):
            a = np.asarray(res.assigned)
            placed_q1 = int((a[:8] >= 0).sum())
            placed_q2 = int((a[8:16] >= 0).sum())
            assert (placed_q1, placed_q2) == (6, 2), (placed_q1, placed_q2)

    def test_drf_order_matches_single_chip(self, mesh):
        """Live DRF ordering on the mesh: two equal jobs split a saturated
        8-cpu cluster 4:4, matching the single-device kernel."""
        nodes = {f"n{i}": NodeInfo(build_node(
            f"n{i}", {"cpu": "1", "memory": "100Gi"})) for i in range(8)}
        jobs, tasks = {}, []
        for jname in ("jA", "jB"):
            pg = build_pod_group(jname, "ns", min_member=1)
            job = JobInfo(f"ns/{jname}", pg)
            for i in range(8):
                p = build_pod("ns", f"{jname}-{i}", "", "Pending",
                              {"cpu": "1", "memory": "1Gi"}, jname)
                t = TaskInfo(p)
                job.add_task_info(t)
                tasks.append(t)
            jobs[job.uid] = job
        arr = flatten_snapshot(jobs, nodes, tasks)
        # drf inputs: nothing allocated yet, total = cluster capacity
        arr.drf_total[:] = 0.0
        arr.drf_total[0] = 8000.0
        arr.drf_total[1] = 800 * (1 << 30)
        p = params_dict(arr, least_req_weight=1.0)
        single = solve_allocate(arr.device_dict(), p, herd_mode="spread",
                                score_families=("kube",),
                                use_drf_order=True)
        sharded = solve_allocate_sharded(arr.device_dict(), p, mesh,
                                         herd_mode="spread",
                                         score_families=("kube",),
                                         use_drf_order=True)
        for res in (single, sharded):
            a = np.asarray(res.assigned)
            placed = (int((a[:8] >= 0).sum()), int((a[8:16] >= 0).sum()))
            assert placed == (4, 4), placed


class TestShardedD1ZeroCost:
    """A 1-device mesh must compile to a collective-free program (the
    shard_map constant factor every multi-chip deployment inherits): the
    collectives are skipped at trace time when D == 1, and the results
    stay identical to the multi-device mesh."""

    _COLLECTIVES = ("all_gather", "psum", "pmax", "pmin", "all_to_all",
                    "ppermute")

    def test_no_collectives_and_same_result(self, mesh):
        from types import SimpleNamespace

        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(16)],
            [(f"j{k}", 4, [("1", "2Gi")] * 4) for k in range(8)])
        queues = {"default": SimpleNamespace(weight=1, capability=None)}
        arr = flatten_snapshot(jobs, nodes, tasks, queues=queues)
        arr.fill_queue_demand()
        p = params_dict(arr, binpack_weight=1.0)
        d = arr.device_dict()
        mesh1 = make_mesh(jax.devices()[:1])
        kw = dict(herd_mode="pack", score_families=("binpack",),
                  use_queue_cap=True)
        txt = str(jax.make_jaxpr(
            lambda dd, pp: solve_allocate_sharded(dd, pp, mesh1, **kw)
        )(d, p))
        for prim in self._COLLECTIVES:
            assert prim not in txt, f"D=1 jaxpr contains {prim}"
        r1 = solve_allocate_sharded(d, p, mesh1, **kw)
        r8 = solve_allocate_sharded(d, p, mesh, **kw)
        np.testing.assert_array_equal(np.asarray(r1.assigned),
                                      np.asarray(r8.assigned))
        np.testing.assert_array_equal(np.asarray(r1.job_ready),
                                      np.asarray(r8.job_ready))

    def test_packed2d_entry_matches(self):
        """Device-resident packed buffers feed the sharded solver without
        a host re-upload; the unpack fuses into the solve."""
        from volcano_tpu.ops import PackedDeviceCache
        from volcano_tpu.parallel import solve_allocate_sharded_packed2d

        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(8)],
            [(f"j{k}", 2, [("1", "2Gi")] * 2) for k in range(6)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        p = params_dict(arr, binpack_weight=1.0)
        mesh1 = make_mesh(jax.devices()[:1])
        kw = dict(herd_mode="pack", score_families=("binpack",))
        ref = solve_allocate_sharded(arr.device_dict(), p, mesh1, **kw)
        fbuf, ibuf, layout = arr.packed()
        dc = PackedDeviceCache()
        f2d, i2d = dc.update(fbuf, ibuf, layout)
        res = solve_allocate_sharded_packed2d(f2d, i2d, layout, p, mesh1,
                                              **kw)
        np.testing.assert_array_equal(np.asarray(res.assigned),
                                      np.asarray(ref.assigned))
        np.testing.assert_array_equal(np.asarray(res.job_ready),
                                      np.asarray(ref.job_ready))

    def test_evict_d1_no_collectives(self):
        from volcano_tpu.api import TaskStatus
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION
        from volcano_tpu.models import Pod, PodGroup, PodGroupSpec
        from volcano_tpu.ops.evict import pack_victim_arrays
        from volcano_tpu.parallel.sharded_evict import (
            _solve_sharded, shard_victims,
        )

        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(4)], [])
        low = JobInfo("ns/low", PodGroup(name="low", namespace="ns",
                                         spec=PodGroupSpec(min_member=1)))
        victims = []
        for i in range(8):
            pod = Pod(name=f"low-{i}", namespace="ns",
                      node_name=f"n{i % 4}", phase="Running",
                      annotations={POD_GROUP_ANNOTATION: "low"},
                      containers=[{"requests": {"cpu": "1",
                                                "memory": "2Gi"}}])
            t = TaskInfo(pod)
            t.status = TaskStatus.RUNNING
            low.add_task_info(t)
            nodes[f"n{i % 4}"].add_task(t)
            victims.append(t)
        hi = JobInfo("ns/hi", PodGroup(name="hi", namespace="ns",
                                       spec=PodGroupSpec(min_member=4)))
        claimers = []
        for i in range(4):
            pod = Pod(name=f"hi-{i}", namespace="ns",
                      annotations={POD_GROUP_ANNOTATION: "hi"},
                      containers=[{"requests": {"cpu": "2",
                                                "memory": "4Gi"}}])
            t = TaskInfo(pod)
            hi.add_task_info(t)
            claimers.append(t)
        arr = flatten_snapshot({hi.uid: hi}, nodes, claimers)
        params = params_dict(arr, least_req_weight=1.0)
        varrays = pack_victim_arrays(arr, victims, 4)
        sharded_v, _perm = shard_victims(varrays, arr.N, 1)
        mesh1 = make_mesh(jax.devices()[:1])
        txt = str(jax.make_jaxpr(
            lambda aa, vv, pp: _solve_sharded(aa, vv, pp, mesh1,
                                              ("kube",), False, True)
        )(arr.device_dict(), sharded_v, params))
        for prim in self._COLLECTIVES:
            assert prim not in txt, f"D=1 evict jaxpr contains {prim}"


class TestShardedEvict:
    """solve_evict_uniform_sharded vs the single-device kernel on the
    config-4 shape (scaled down): same placements count, same (minimal)
    eviction count, capacity respected."""

    def test_matches_single_device(self, mesh):
        from volcano_tpu.api import TaskStatus
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION
        from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
        from volcano_tpu.ops.evict import (
            decode_evict_compact, pack_victim_arrays, solve_evict_uniform,
        )
        from volcano_tpu.parallel import solve_evict_uniform_sharded

        n_nodes, n_victims, n_claim = 16, 160, 80
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
        params = params_dict(arr, least_req_weight=1.0)
        varrays = pack_victim_arrays(arr, victims, n_claim)
        v_req, v_node = varrays["v_req"], varrays["v_node"]

        assert arr.N % 8 == 0, arr.N
        r1 = solve_evict_uniform(arr.device_dict(), varrays, params)
        a1, e1 = decode_evict_compact(r1.compact, arr.T)
        r2 = solve_evict_uniform_sharded(arr.device_dict(), varrays,
                                         params, mesh)
        a2, e2 = np.asarray(r2.assigned), np.asarray(r2.evicted_by)

        assert int((a2[:n_claim] >= 0).sum()) == n_claim
        assert int((e2 >= 0).sum()) == int((e1 >= 0).sum())
        # capacity: per node, claimer demand fits idle + freed
        for assigned, evby, label in ((a1, e1, "single"), (a2, e2, "mesh")):
            demand = np.zeros(arr.N)
            for i in range(n_claim):
                demand[assigned[i]] += 2000.0
            freed = np.zeros(arr.N)
            for vi in np.nonzero(evby >= 0)[0]:
                freed[v_node[vi]] += v_req[vi][0]
            assert (demand <= arr.node_idle[:, 0] + freed + 1e-3).all(), \
                label


class TestShardedScale:
    """VERDICT r2 #6(a): the sharded solver at the shapes that motivate
    sharding — 10k tasks x 2k nodes on the virtual 8-device mesh
    (250-node shards) — validating placements + per-node capacity."""

    def test_10k_by_2k(self, mesh):
        rng = np.random.default_rng(7)
        T_, N_ = 10240, 2048
        R = 2
        a = {
            "task_init_req": np.zeros((T_, R), np.float32),
            "task_req": None,
            "task_job": np.zeros(T_, np.int32),
            "task_rank": np.arange(T_, dtype=np.int32),
            "task_sig": np.zeros(T_, np.int32),
            "task_counts_ready": np.ones(T_, bool),
            "task_valid": np.ones(T_, bool),
        }
        n_jobs = 1024
        per = T_ // n_jobs
        for j in range(n_jobs):
            req = (float(rng.integers(1, 4)) * 1000.0,
                   float(rng.integers(1, 5)) * (1 << 30))
            a["task_init_req"][j * per:(j + 1) * per] = req
            a["task_job"][j * per:(j + 1) * per] = j
        a["task_req"] = a["task_init_req"].copy()
        a["job_min"] = np.full(n_jobs, per, np.int32)
        a["job_ready_base"] = np.zeros(n_jobs, np.int32)
        a["job_queue"] = (np.arange(n_jobs) % 3).astype(np.int32)
        a["job_valid"] = np.ones(n_jobs, bool)
        idle = np.zeros((N_, R), np.float32)
        idle[:, 0] = 32000.0
        idle[:, 1] = 128.0 * (1 << 30)
        a["node_idle"] = idle
        a["node_extra_future"] = np.zeros((N_, R), np.float32)
        a["node_used"] = np.zeros((N_, R), np.float32)
        a["node_alloc"] = idle.copy()
        a["node_npods"] = np.zeros(N_, np.int32)
        a["node_max_pods"] = np.full(N_, 110, np.int32)
        a["node_valid"] = np.ones(N_, bool)
        a["sig_masks"] = np.ones((1, N_), bool)
        a["thresholds"] = np.array([10.0, 1.0], np.float32)
        a["scalar_dim_mask"] = np.zeros(R, bool)
        qw = np.array([1.0, 2.0, 3.0], np.float32)
        a["queue_weight"] = qw
        a["queue_capability"] = np.full((3, R), np.inf, np.float32)
        a["queue_allocated"] = np.zeros((3, R), np.float32)
        qreq = np.zeros((3, R), np.float32)
        for j in range(n_jobs):
            qreq[a["job_queue"][j]] += \
                a["task_init_req"][a["task_job"] == j].sum(axis=0)
        a["queue_request"] = qreq

        params = {"binpack_weight": np.float32(1.0),
                  "binpack_res_weights": np.ones(R, np.float32),
                  "least_req_weight": np.float32(0.0),
                  "most_req_weight": np.float32(0.0),
                  "balanced_weight": np.float32(0.0),
                  "node_static": np.zeros(N_, np.float32)}
        res = solve_allocate_sharded(a, params, mesh, herd_mode="pack",
                                     score_families=("binpack",),
                                     use_queue_cap=True)
        assigned = np.asarray(res.assigned)
        kind = np.asarray(res.kind)
        placed = int((assigned >= 0).sum())
        # cluster is unsaturated (20k avg-2cpu tasks vs 64k cpu): all place
        assert placed == T_, placed
        # per-node capacity respected
        used = np.zeros((N_, R), np.float32)
        for i in np.nonzero((assigned >= 0) & (kind == 0))[0]:
            used[assigned[i]] += a["task_req"][i]
        assert (used <= a["node_idle"] + a["thresholds"][None, :]).all()
        assert np.asarray(res.job_ready).all()


class TestShardedHDRF:
    """The hdrf rescaling scenario on the mesh: the sharded solver's
    in-kernel hierarchical re-rank must reproduce the single-device
    split (sci takes half; eng's children split the rest along their
    dominant resources)."""

    def test_hdrf_rescaling_on_mesh(self, mesh):
        from types import SimpleNamespace

        from volcano_tpu.ops.hdrf import build_hdrf
        from volcano_tpu.api import Resource

        # the host test's single 10/10 node doesn't shard; spread an
        # equivalent-shape cluster over 8 equal nodes (16 cpu / 16G total,
        # so the strict hierarchical split would be 8/8/8)
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "2", "2G") for i in range(8)],
            [("pg1", 1, [("1", "1G")] * 10),
             ("pg21", 1, [("1", "0")] * 10),
             ("pg22", 1, [("0", "1G")] * 10)])
        for i, job in enumerate(jobs.values()):
            job.queue = ["q-sci", "q-dev", "q-prod"][i]
        queues = {
            "q-sci": SimpleNamespace(
                weight=1, capability=None, hierarchy="root/sci",
                weights="100/50"),
            "q-dev": SimpleNamespace(
                weight=1, capability=None, hierarchy="root/eng/dev",
                weights="100/50/50"),
            "q-prod": SimpleNamespace(
                weight=1, capability=None, hierarchy="root/eng/prod",
                weights="100/50/50"),
        }
        arr = flatten_snapshot(jobs, nodes, tasks, queues=queues)
        # drf inputs: zero allocated, cluster totals
        arr.drf_total = (arr.node_alloc
                         * arr.node_valid[:, None]).sum(axis=0).astype(
            np.float32)
        build_hdrf(arr, queues, {}, Resource())
        params = params_dict(arr, least_req_weight=1.0)
        assert arr.N % 8 == 0
        res = solve_allocate_sharded(
            arr.device_dict(), params, mesh, herd_mode="spread",
            score_families=("kube",), use_drf_order=True,
            use_hdrf_order=True)
        single = solve_allocate(
            arr.device_dict(), params, herd_mode="spread",
            score_families=("kube",), use_drf_order=True,
            use_hdrf_order=True)

        def tally(r):
            assigned = np.asarray(r.assigned)
            placed = {}
            for i, t in enumerate(arr.tasks_list):
                if assigned[i] >= 0:
                    placed[t.job] = placed.get(t.job, 0) + 1
            return placed

        mesh_p, single_p = tally(res), tally(single)
        # the mesh run must match the single-device kernel exactly
        assert mesh_p == single_p, (mesh_p, single_p)
        # fairness bounds (the kernel is work-conserving, so the strict
        # 8/8/8 analytic split may trade sci tasks for extra dev+prod
        # ones — an accepted greedy deviation): sci holds most of its
        # hierarchical half, the symmetric eng children stay equal, and
        # every dimension is fully used
        assert mesh_p["ns/pg1"] >= 6, mesh_p
        assert mesh_p["ns/pg21"] == mesh_p["ns/pg22"], mesh_p
        assert (mesh_p["ns/pg1"] + mesh_p["ns/pg21"]) == 16, mesh_p


class TestShardedFused:
    """The fused pallas choice kernel under shard_map (VERDICT r4 missing
    #2): each device runs the VMEM kernel on its [T, N/D] shard, and the
    sharded solve with fused="on" (interpret mode on this CPU mesh) must
    be observationally identical to the dense sharded path AND to the
    single-device solver."""

    def _problem(self):
        # shard-clean: 32 nodes -> 4 per device on the 8-device mesh
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", str(4 + i % 3), f"{8 + i % 5}Gi")
             for i in range(32)],
            [(f"j{k}", 3, [(str(1 + k % 2), f"{1 + k % 3}Gi")] * 3)
             for k in range(12)])
        return flatten_snapshot(jobs, nodes, tasks)

    @pytest.mark.parametrize("herd,families", [
        ("pack", ("binpack",)),
        ("spread", ("kube",)),
    ])
    def test_fused_matches_dense_on_mesh(self, mesh, herd, families):
        arr = self._problem()
        p = params_dict(arr,
                        binpack_weight=1.0 if "binpack" in families else 0.0,
                        least_req_weight=1.0 if "kube" in families else 0.0)
        d = arr.device_dict()
        r_off = solve_allocate_sharded(d, p, mesh, herd_mode=herd,
                                       score_families=families,
                                       fused="off")
        r_on = solve_allocate_sharded(d, p, mesh, herd_mode=herd,
                                      score_families=families,
                                      fused="on")
        assert (np.asarray(r_off.kind) == np.asarray(r_on.kind)).all()
        assert (np.asarray(r_off.job_ready)
                == np.asarray(r_on.job_ready)).all()
        a_off, a_on = np.asarray(r_off.assigned), np.asarray(r_on.assigned)
        assert ((a_off >= 0) == (a_on >= 0)).all()
        # same placement shape: identical per-node occupancy
        c_off = np.bincount(a_off[a_off >= 0], minlength=arr.N)
        c_on = np.bincount(a_on[a_on >= 0], minlength=arr.N)
        assert (c_off == c_on).all(), (c_off, c_on)

    def test_fused_hdrf_on_mesh(self, mesh):
        """fused="on" under shard_map with the hierarchical rank+cap (the
        fused placeability prefilter path) must match the dense sharded
        result."""
        from types import SimpleNamespace

        from volcano_tpu.api import Resource
        from volcano_tpu.ops.hdrf import build_hdrf

        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "2", "2G") for i in range(8)],
            [("pg1", 1, [("1", "1G")] * 10),
             ("pg21", 1, [("1", "0")] * 10),
             ("pg22", 1, [("0", "1G")] * 10)])
        for i, job in enumerate(jobs.values()):
            job.queue = ["q-sci", "q-dev", "q-prod"][i]
        queues = {
            "q-sci": SimpleNamespace(weight=1, capability=None,
                                     hierarchy="root/sci",
                                     weights="100/50"),
            "q-dev": SimpleNamespace(weight=1, capability=None,
                                     hierarchy="root/eng/dev",
                                     weights="100/50/50"),
            "q-prod": SimpleNamespace(weight=1, capability=None,
                                      hierarchy="root/eng/prod",
                                      weights="100/50/50"),
        }
        arr = flatten_snapshot(jobs, nodes, tasks, queues=queues)
        arr.drf_total = (arr.node_alloc
                         * arr.node_valid[:, None]).sum(axis=0).astype(
            np.float32)
        build_hdrf(arr, queues, {}, Resource())
        p = params_dict(arr, least_req_weight=1.0)
        d = arr.device_dict()
        kw = dict(herd_mode="spread", score_families=("kube",),
                  use_drf_order=True, use_hdrf_order=True)
        r_off = solve_allocate_sharded(d, p, mesh, fused="off", **kw)
        r_on = solve_allocate_sharded(d, p, mesh, fused="on", **kw)
        assert (np.asarray(r_off.kind) == np.asarray(r_on.kind)).all()
        a_off, a_on = np.asarray(r_off.assigned), np.asarray(r_on.assigned)
        assert ((a_off >= 0) == (a_on >= 0)).all()
        tj = np.asarray(arr.task_job)
        for j in range(3):
            assert ((a_off >= 0) & (tj == j)).sum() \
                == ((a_on >= 0) & (tj == j)).sum()


class TestShardedArenaEntry:
    """solve_allocate_sharded_arena over ShardedDeviceCache buffers: the
    D>1 steady-state entry must match the plain sharded solver (and the
    packed D=1 path) bit for bit, stay collective-free at D=1, and ship
    per-shard deltas only to the shard owning the dirty rows."""

    def _problem(self):
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(16)],
            [(f"j{k}", 4, [("1", "2Gi")] * 4) for k in range(8)])
        return flatten_snapshot(jobs, nodes, tasks)

    def test_matches_sharded_and_packed(self, mesh):
        from volcano_tpu.ops import PackedDeviceCache, ShardedDeviceCache
        from volcano_tpu.ops.solver import (
            decode_compact, solve_allocate_packed2d,
        )
        from volcano_tpu.parallel import solve_allocate_sharded_arena

        arr = self._problem()
        p = params_dict(arr, binpack_weight=1.0)
        kw = dict(herd_mode="pack", score_families=("binpack",))
        fbuf, ibuf, layout = arr.packed()
        sdc = ShardedDeviceCache(mesh)
        bufs = sdc.update(fbuf, ibuf, layout)
        r = solve_allocate_sharded_arena(*bufs, sdc.params_device(p),
                                         mesh, **kw)
        ref = solve_allocate_sharded(arr.device_dict(), p, mesh, **kw)
        np.testing.assert_array_equal(np.asarray(r.assigned),
                                      np.asarray(ref.assigned))
        np.testing.assert_array_equal(np.asarray(r.job_ready),
                                      np.asarray(ref.job_ready))
        dc = PackedDeviceCache()
        f2d, i2d = dc.update(fbuf, ibuf, layout)
        pk = solve_allocate_packed2d(f2d, i2d, layout, p, **kw)
        a_pk, k_pk = decode_compact(np.asarray(pk.compact))
        np.testing.assert_array_equal(np.asarray(r.assigned), a_pk)
        np.testing.assert_array_equal(np.asarray(r.kind), k_pk)

    def test_per_shard_delta_locality_and_zero_dirty(self, mesh):
        from volcano_tpu.ops import ShardedDeviceCache
        from volcano_tpu.parallel import solve_allocate_sharded_arena

        arr = self._problem()
        p = params_dict(arr, binpack_weight=1.0)
        kw = dict(herd_mode="pack", score_families=("binpack",))
        fbuf, ibuf, layout = arr.packed()
        sdc = ShardedDeviceCache(mesh)
        sdc.update(fbuf, ibuf, layout)
        assert sdc.last_full_ship and all(sdc.last_shard_bytes)

        # zero-dirty: the acceptance contract — an unchanged snapshot
        # ships 0 bytes to EVERY shard and solves off the resident arena
        bufs = sdc.update(fbuf, ibuf, layout)
        assert sdc.last_shipped_bytes == 0
        assert sdc.last_shard_bytes == [0] * sdc.D
        assert not sdc.last_full_ship
        r = solve_allocate_sharded_arena(*bufs, sdc.params_device(p),
                                         mesh, **kw)
        assert int((np.asarray(r.assigned) >= 0).sum()) > 0

        # dirty exactly one node row: only the owning shard receives bytes
        nl = arr.N // sdc.D
        victim_shard = 5
        arr.node_idle[victim_shard * nl, 0] -= 1.0
        fbuf2, ibuf2, _ = arr.packed()
        sdc.update(fbuf2, ibuf2, layout)
        got = [d for d, b in enumerate(sdc.last_shard_bytes) if b]
        assert got == [victim_shard], sdc.last_shard_bytes

    def test_full_ship_warms_every_delta_scatter(self, mesh, monkeypatch):
        """After a full ship, a delta of any dirty-chunk count compiles
        nothing on the session's thread, and the warm leaves the resident
        buffers as the host mirror has them."""
        from volcano_tpu.ops import ShardedDeviceCache, device_cache
        from volcano_tpu.ops.precompile import watcher

        monkeypatch.setattr(device_cache, "_APPLY_KEEP", None)  # cold jit
        watcher.install()
        arr = self._problem()
        fbuf, ibuf, layout = arr.packed()
        sdc = ShardedDeviceCache(mesh)
        sdc.update(fbuf, ibuf, layout)
        before = watcher.counts()[0]
        rng = np.random.default_rng(0)
        for m in (1, 5, fbuf.size):
            f2, i2 = fbuf.copy(), ibuf.copy()
            f2[rng.choice(fbuf.size, m)] += 1.0
            i2[rng.choice(ibuf.size, min(m, ibuf.size))] += 1
            sdc.update(f2, i2, layout)
            assert not sdc.last_full_ship and sdc.last_shipped_bytes
        assert watcher.counts()[0] == before
        np.testing.assert_array_equal(
            np.asarray(sdc._dev_rep_f).ravel(), sdc._host_rep_f)
        for d in range(sdc.D):
            np.testing.assert_array_equal(
                np.asarray(sdc._dev_node_i[d]).ravel(), sdc._host_node_i[d])

    def test_invalidate_keeps_params_then_full_reships(self, mesh):
        from volcano_tpu.ops import ShardedDeviceCache

        arr = self._problem()
        p = params_dict(arr, binpack_weight=1.0)
        fbuf, ibuf, layout = arr.packed()
        sdc = ShardedDeviceCache(mesh)
        sdc.update(fbuf, ibuf, layout)
        pinned = sdc.params_device(p)
        assert sdc.params_repins == 1
        sdc.invalidate()
        assert sdc._dev_rep_f is None and sdc._dev_node_f is None
        assert sdc._params_blob is not None
        sdc.update(fbuf, ibuf, layout)
        assert sdc.full_ships == 2 and sdc.last_full_ship
        # params re-validated in place, not re-uploaded
        assert sdc.params_device(p) is pinned
        assert sdc.params_repins == 1

    def test_split_layout_rejects_indivisible_node_axis(self):
        from volcano_tpu.ops import split_packed_layout

        layout = (("node_idle", "f", 0, 20, (10, 2)),)
        with pytest.raises(ValueError):
            split_packed_layout(layout, 8)

    def test_arena_entry_d1_no_collectives(self):
        """The D=1 arena program must stay collective-free (what the
        --solver-mode auto crossover costs on one chip: nothing)."""
        from volcano_tpu.ops import ShardedDeviceCache
        from volcano_tpu.parallel import (
            make_mesh, solve_allocate_sharded_arena,
        )

        arr = self._problem()
        p = params_dict(arr, binpack_weight=1.0)
        fbuf, ibuf, layout = arr.packed()
        mesh1 = make_mesh(jax.devices()[:1])
        sdc = ShardedDeviceCache(mesh1)
        bufs = sdc.update(fbuf, ibuf, layout)
        pd = sdc.params_device(p)
        txt = str(jax.make_jaxpr(
            lambda fr, ir, fn, im, pp: solve_allocate_sharded_arena(
                fr, ir, fn, im, bufs[4], bufs[5], pp, mesh1,
                herd_mode="pack", score_families=("binpack",))
        )(*bufs[:4], pd))
        for prim in TestShardedD1ZeroCost._COLLECTIVES:
            assert prim not in txt, f"D=1 arena jaxpr contains {prim}"


class TestRealMultiDeviceSubprocess:
    """The satellite contract: tier-1 exercises REAL multi-device
    shard_map collectives even when the outer environment pre-set
    XLA_FLAGS (the in-process conftest only appends the device-count
    flag when unset). The subprocess forces an 8-device host platform
    and proves (a) the D=8 program actually contains collectives and
    (b) its decisions equal the D=1 run's."""

    def test_d8_collectives_and_digest_in_forced_subprocess(
            self, eight_device_subprocess):
        code = """
import jax, numpy as np
assert len(jax.devices()) == 8, jax.devices()
from volcano_tpu.ops import flatten_snapshot
from volcano_tpu.parallel import make_mesh, solve_allocate_sharded
from test_solver import make_problem, params_dict

jobs, nodes, tasks = make_problem(
    [(f"n{i}", "8", "32Gi") for i in range(16)],
    [(f"j{k}", 4, [("1", "2Gi")] * 4) for k in range(8)])
arr = flatten_snapshot(jobs, nodes, tasks)
p = params_dict(arr, binpack_weight=1.0)
d = arr.device_dict()
mesh8 = make_mesh()
mesh1 = make_mesh(jax.devices()[:1])
kw = dict(herd_mode="pack", score_families=("binpack",))
txt = str(jax.make_jaxpr(
    lambda dd, pp: solve_allocate_sharded(dd, pp, mesh8, **kw))(d, p))
assert any(prim in txt for prim in ("all_gather", "psum", "pmax")), \\
    "D=8 jaxpr contains no collectives"
r8 = solve_allocate_sharded(d, p, mesh8, **kw)
r1 = solve_allocate_sharded(d, p, mesh1, **kw)
assert np.array_equal(np.asarray(r8.assigned), np.asarray(r1.assigned))
assert np.array_equal(np.asarray(r8.job_ready), np.asarray(r1.job_ready))
print("D8_COLLECTIVES_OK")
"""
        proc = eight_device_subprocess(code)
        assert "D8_COLLECTIVES_OK" in proc.stdout
