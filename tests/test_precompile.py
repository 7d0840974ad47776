"""Compile-and-dispatch pipeline tests (ops.precompile + the allocate
action's dispatch/collect split).

Covers the PR-2 contracts:
- predicted next-bucket packed layouts are byte-identical to a real
  flatten at those sizes (the prewarm compiles the EXACT variant the
  session will dispatch, or it's worthless);
- after a background pre-warm, a bucket-crossing session runs with ZERO
  solve compiles on the session thread;
- an async-collect failure (error surfacing at readback, after a donated
  dispatch) resets the device cache and completes the session through
  the host oracle;
- the pipelined (dispatch/collect overlapped) scheduler produces
  bind-for-bind identical decisions to the strictly serial loop across a
  multi-cycle churn script.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from volcano_tpu.ops import PackedDeviceCache, bucket, flatten_snapshot
from volcano_tpu.ops import precompile as pc


def _mini_problem(n_nodes, n_jobs, tasks_per_job, n_queues=1):
    from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION
    from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec

    nodes = {}
    for i in range(n_nodes):
        rl = {"cpu": "64", "memory": "256Gi", "pods": 110}
        nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                       capacity=dict(rl)))
    jobs, tasks = {}, []
    for k in range(n_jobs):
        pg = PodGroup(name=f"j{k}", namespace="t",
                      spec=PodGroupSpec(min_member=tasks_per_job,
                                        queue=f"q{k % n_queues}"))
        job = JobInfo(f"t/j{k}", pg)
        for i in range(tasks_per_job):
            pod = Pod(name=f"j{k}-{i}", namespace="t",
                      annotations={POD_GROUP_ANNOTATION: f"j{k}"},
                      containers=[{"requests": {"cpu": str(1 + k % 2),
                                                "memory": "1Gi"}}])
            t = TaskInfo(pod)
            job.add_task_info(t)
            tasks.append(t)
        jobs[job.uid] = job
    return jobs, nodes, tasks


def _score_params(arr):
    from volcano_tpu.ops import ScoreParams
    sp = ScoreParams(binpack_weight=1.0).resolved(arr.R, arr.N)
    return {
        "binpack_weight": np.float32(sp.binpack_weight),
        "binpack_res_weights": sp.binpack_res_weights,
        "least_req_weight": np.float32(sp.least_req_weight),
        "most_req_weight": np.float32(sp.most_req_weight),
        "balanced_weight": np.float32(sp.balanced_weight),
        "node_static": sp.node_static,
    }


FLAGS = dict(herd_mode="pack", score_families=("binpack", "kube"),
             use_queue_cap=False, use_drf_order=False,
             use_hdrf_order=False, work_conserving=True)


class TestLayoutPrediction:
    def test_predicted_layout_matches_real_flatten(self):
        jobs, nodes, tasks = _mini_problem(7, 6, 1)
        arr = flatten_snapshot(jobs, nodes, tasks)
        _, _, layout = arr.packed()
        dims = pc.layout_dims(layout)
        assert dims is not None and dims["T"] == arr.T \
            and dims["N"] == arr.N and dims["J"] == arr.J

        jobs2, nodes2, tasks2 = _mini_problem(7, 9, 1)
        arr2 = flatten_snapshot(jobs2, nodes2, tasks2)
        _, _, layout2 = arr2.packed()
        nxt = dict(dims)
        nxt["T"] = bucket(dims["T"] + 1)
        nxt["J"] = bucket(dims["J"] + 1)
        assert pc.predict_next_layout(layout, nxt) == layout2

    def test_unknown_keys_refuse_prediction(self):
        layout = (("task_init_req", "f", 0, 16, (8, 2)),
                  ("hdrf_parent", "i", 0, 4, (4,)))
        assert pc.layout_dims(layout) is None
        assert pc.predict_next_layout(layout, {"T": 8}) is None

    def test_dummy_buffers_cover_layout(self):
        jobs, nodes, tasks = _mini_problem(5, 4, 2)
        arr = flatten_snapshot(jobs, nodes, tasks)
        fbuf, ibuf, layout = arr.packed()
        f2d, i2d = pc.dummy_packed_buffers(layout, 512)
        assert f2d.size >= fbuf.size and i2d.size >= ibuf.size
        assert f2d.shape[1] == 512 and f2d.dtype == np.float32
        assert i2d.dtype == np.int32


class TestCompileWatcher:
    def test_background_threads_are_excluded_from_session_totals(self):
        w = pc.CompileWatcher()
        w._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
        done = threading.Event()

        def bg():
            w.register_background()
            w._on_duration("/jax/core/compile/backend_compile_duration", 2.0)
            done.set()

        t = threading.Thread(target=bg)
        t.start()
        t.join()
        assert done.is_set()
        c, s = w.session_totals()
        assert (c, s) == (1, 1.0)
        assert w.counts()[0] == 1

    def test_trace_lower_and_cache_load_by_phase(self):
        w = pc.CompileWatcher()
        for key, secs in (("/jax/core/compile/jaxpr_trace_duration", 0.5),
                          ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                           0.25),
                          ("/jax/compilation_cache/cache_retrieval_time_sec",
                           0.125),
                          ("/jax/core/compile/jaxpr_trace_duration", 0.5),
                          ("/jax/compilation_cache/compile_time_saved_sec",
                           9.0)):
            w._on_duration(key, secs, fun_name="f")

        def bg():
            w.register_background()
            w._on_duration("/jax/core/compile/jaxpr_trace_duration", 4.0)

        t = threading.Thread(target=bg)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert w.session_phase_totals() == {"trace": 1.0, "lower": 0.25,
                                            "cache_load": 0.125}
        assert w.session_totals() == (0, 0.0)

    def test_cache_hit_events_counted(self):
        w = pc.CompileWatcher()
        w._on_event("/jax/compilation_cache/cache_hits")
        w._on_event("/jax/compilation_cache/tasks_using_cache")
        assert w.cache_hits == 1


class TestBucketPrewarm:
    def test_crossing_runs_with_zero_session_thread_compiles(self):
        """The acceptance path: warm session at bucket B, occupancy trigger
        pre-warms B+1 off-thread, then a real crossing into B+1 dispatches
        with no compile on the calling (session) thread."""
        from volcano_tpu.ops.solver import solve_allocate_delta

        assert pc.watcher.install()

        def session(dc, tpj):
            # 4 jobs keeps T the only dim near its bucket edge (one warm
            # target => the test compiles 2 variants, not 14)
            jobs, nodes, tasks = _mini_problem(5, 4, tpj)
            arr = flatten_snapshot(jobs, nodes, tasks)
            fbuf, ibuf, layout = arr.packed()
            params = dc.params_device(_score_params(arr))
            payload = dc.plan_delta(fbuf, ibuf, layout)
            res, nf, ni = solve_allocate_delta(*payload, layout, params,
                                               **FLAGS)
            dc.commit(nf, ni)
            np.asarray(res.compact)
            dc.last_solve_flags = dict(layout=layout, **FLAGS)
            return arr

        dc = PackedDeviceCache()
        arr = session(dc, 12)              # 48 tasks: T = bucket(48) = 48
        assert arr.T == 48
        pw = pc.BucketPrewarmer()
        assert pw.observe(arr, dc)         # 48/48 >= 0.8 -> warm 56
        assert pw.wait(600)
        assert pw.completions >= 1 and pw.failures == 0
        # dedup: the same trigger doesn't re-warm
        assert not pw.observe(arr, dc)

        c0, _ = pc.watcher.counts()
        sz0 = pc.solver_cache_size()
        arr2 = session(dc, 13)             # 52 tasks: T = bucket(52) = 56
        assert arr2.T == bucket(49)
        c1, _ = pc.watcher.counts()
        assert c1 - c0 == 0, "solve compiled on the session thread"
        if sz0 >= 0:
            assert pc.solver_cache_size() == sz0

    def test_no_trigger_below_threshold(self):
        jobs, nodes, tasks = _mini_problem(5, 2, 2)  # 4 tasks in T=8
        arr = flatten_snapshot(jobs, nodes, tasks)
        fbuf, ibuf, layout = arr.packed()
        dc = PackedDeviceCache()
        dc.update(fbuf, ibuf, layout)
        dc.last_solve_flags = dict(layout=layout, **FLAGS)
        pw = pc.BucketPrewarmer()
        assert not pw.observe(arr, dc)


class TestSteadyChurn:
    """A steady session dispatches a finite set of variants: the flatten
    pads the task and job axes up to the arena's floor (the largest it has
    planned), and the packed arena dispatches one executable per layout,
    whatever the number of dirty chunks."""

    @staticmethod
    def _session(dc, jobs, nodes, tasks_by_job, k):
        keep = list(jobs)[:k]
        arr = flatten_snapshot({u: jobs[u] for u in keep}, nodes,
                               [t for u in keep for t in tasks_by_job[u]],
                               floor=dc.dims_floor)
        fbuf, ibuf, layout = arr.packed()
        res = dc.dispatch(dc.plan(fbuf, ibuf, layout, _score_params(arr),
                                  FLAGS))
        np.asarray(res.compact)
        return arr

    @staticmethod
    def _problem(n_jobs, tpj):
        jobs, nodes, tasks = _mini_problem(5, n_jobs, tpj)
        by_job = {}
        for t in tasks:
            by_job.setdefault(t.job, []).append(t)
        return jobs, nodes, by_job

    def test_warm_churn_compiles_nothing(self):
        assert pc.watcher.install()
        jobs, nodes, by_job = self._problem(12, 5)
        # 8-float chunks: a new job mix dirties more chunks than the
        # fused slots hold, a repeated one none
        dc = PackedDeviceCache(chunk=8)
        warm = self._session(dc, jobs, nodes, by_job, 12)   # T 60, J 13
        assert (warm.T, warm.J) == (bucket(60), bucket(13))
        self._session(dc, jobs, nodes, by_job, 7)
        self._session(dc, jobs, nodes, by_job, 7)
        c0, _ = pc.watcher.counts()
        sz0 = pc.solver_cache_size()
        full, fused = set(), set()
        # natural buckets T 16..64 and J 8..14, down and up across edges
        for k in (3, 12, 12, 2, 9, 9, 12, 4, 10, 1, 12):
            arr = self._session(dc, jobs, nodes, by_job, k)
            assert (arr.T, arr.J) == (warm.T, warm.J)
            assert bucket(5 * k) <= arr.T and bucket(k + 1) <= arr.J
            (full if dc.last_full_ship else fused).add(
                dc.last_shipped_chunks)
        assert full and fused and 0 in fused
        c1, _ = pc.watcher.counts()
        assert c1 == c0, "a warm churn session compiled"
        if sz0 >= 0:
            assert pc.solver_cache_size() == sz0

    def test_closed_waves_keep_their_variant(self):
        jobs, nodes, by_job = self._problem(6, 4)
        dc = PackedDeviceCache()
        for _ in range(3):
            arr = self._session(dc, jobs, nodes, by_job, 6)
            assert (arr.T, arr.J) == (bucket(24), bucket(7))
        assert dc.dims_floor == {"T": bucket(24), "J": bucket(7)}

    def test_prewarm_predicts_the_floors_growth(self, monkeypatch):
        jobs, nodes, by_job = self._problem(12, 4)
        dc = PackedDeviceCache()
        arr = self._session(dc, jobs, nodes, by_job, 10)   # T 40 of 40
        pw = pc.BucketPrewarmer()
        seen = []
        monkeypatch.setattr(pw, "_warm_many",
                            lambda work, chunk, flags: seen.extend(work))
        assert pw.observe(arr, dc)
        pw.wait(60)
        # past the floor the flatten pads to a power of two at least twice
        # the floor, not to the next bucket (T 48, J 14): that is the
        # variant worth warming
        assert {d["T"] for _k, _l, d in seen} == {arr.T, 128}
        assert {d["J"] for _k, _l, d in seen} == {arr.J, 32}

    def test_grow_ahead_compiles_the_growth_off_thread(self):
        assert pc.watcher.install()
        jobs, nodes, by_job = self._problem(12, 5)
        dc = PackedDeviceCache()
        first = self._session(dc, jobs, nodes, by_job, 6).T  # T 30 of 32
        assert dc.grow_ahead()
        dc.grow_thread.join(300)
        grown = dc.dims_floor["T"]
        assert first == 32 and grown == 64
        assert not dc.grow_ahead(), "grows ahead once per arena"
        c0, _ = pc.watcher.counts()
        for k in (6, 7, 3):                  # J stays at bucket(8)
            assert self._session(dc, jobs, nodes, by_job, k).T == grown
        assert pc.watcher.counts()[0] == c0, "the grown layout compiled " \
            "on the session thread"

    def test_outgrowing_the_floor_pads_to_a_power_of_two(self):
        jobs, nodes, by_job = self._problem(12, 5)
        dc = PackedDeviceCache()
        self._session(dc, jobs, nodes, by_job, 4)          # T 20
        arr = self._session(dc, jobs, nodes, by_job, 9)    # T 45
        assert arr.T == 64 and bucket(45) == 48
        assert self._session(dc, jobs, nodes, by_job, 12).T == 64
        assert self._session(dc, jobs, nodes, by_job, 2).T == 64


def _build_cluster(n_nodes=4, n_jobs=3, tpj=2, async_effectors=False):
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase

    store = ClusterStore()
    cache = SchedulerCache(store, async_effectors=async_effectors)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    store.apply("queues", build_queue("q0", weight=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(f"n{i}",
                                         {"cpu": "16", "memory": "64Gi"}))

    def wave(k):
        pg = build_pod_group(f"j{k}", "t", min_member=tpj, queue="q0")
        pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", pg)
        for i in range(tpj):
            store.create("pods", build_pod(
                "t", f"j{k}-{i}", "", "Pending",
                {"cpu": "1", "memory": "1Gi"}, f"j{k}"))

    for k in range(n_jobs):
        wave(k)
    return store, cache, wave


class TestCollectFailureFallback:
    def test_reset_and_host_oracle(self, monkeypatch):
        """An error surfacing at readback (async dispatch failure with
        donated buffers) must reset the device cache AND still schedule
        the session through the host loop."""
        from volcano_tpu.scheduler import Scheduler

        store, cache, wave = _build_cluster(n_jobs=3)
        sched = Scheduler(cache)
        import volcano_tpu.ops.solver as solver_mod

        real_decode = solver_mod.decode_compact
        calls = {"n": 0}

        def boom(compact):
            calls["n"] += 1
            raise RuntimeError("simulated device loss at readback")

        monkeypatch.setattr(solver_mod, "decode_compact", boom)
        sched.run_once()
        assert calls["n"] == 1
        # soft invalidation: the donated chunked buffers are dropped (the
        # failed dispatch consumed them), but the never-donated pinned
        # params and their content blob SURVIVE for re-validation — a
        # collect failure costs one full re-ship, not a cold arena
        dc = cache.device_cache
        assert dc._layout is None and dc._dev_f is None
        assert dc._params_blob is not None
        assert dc.invalidations == 1
        repins_after_fault = dc.params_repins
        # the session still placed every pod, via the host oracle
        assert len(cache.binder.binds) == 6
        assert sched.last_cycle_timing.get("host_fallback") == 1.0

        # next cycle recovers on the device path: full re-ship of the
        # chunked buffers, params re-validated in place (no re-upload)
        monkeypatch.setattr(solver_mod, "decode_compact", real_decode)
        wave(3)
        sched.run_once()
        assert len(cache.binder.binds) == 8
        assert dc._layout is not None
        assert dc.last_full_ship
        assert dc.params_repins == repins_after_fault
        assert "host_fallback" not in sched.last_cycle_timing

    def test_dispatch_failure_invalidates_arena(self, monkeypatch):
        """A fused dispatch that throws may already have consumed its
        donated buffers: the arena drops them, the session binds through
        the host oracle, and the next session re-ships in full."""
        from volcano_tpu.scheduler import Scheduler

        store, cache, wave = _build_cluster(n_jobs=3)
        sched = Scheduler(cache)
        import volcano_tpu.ops.solver as solver_mod

        real_delta = solver_mod.solve_allocate_delta

        def boom(*args, **kwargs):
            raise RuntimeError("simulated device loss at dispatch")

        monkeypatch.setattr(solver_mod, "solve_allocate_delta", boom)
        sched.run_once()
        dc = cache.device_cache
        assert dc._dev_f is None and dc._layout is None
        assert dc.invalidations >= 1
        assert len(cache.binder.binds) == 6
        assert sched.last_cycle_timing.get("host_fallback") == 1.0

        monkeypatch.setattr(solver_mod, "solve_allocate_delta", real_delta)
        wave(3)
        sched.run_once()
        assert len(cache.binder.binds) == 8
        assert dc.last_full_ship
        assert "host_fallback" not in sched.last_cycle_timing


class TestBacklogGrowth:
    def test_only_a_backlog_grows_the_arena_ahead(self):
        """The allocate action asks the packed arena to grow ahead when a
        solve leaves a task waiting, and not while every wave fits."""
        from volcano_tpu.scheduler import Scheduler

        store, cache, wave = _build_cluster(n_nodes=1, n_jobs=3)
        sched = Scheduler(cache)
        dc = cache.device_cache
        for k in (3, 4):
            sched.run_once()
            assert dc.grow_thread is None
            wave(k)
        sched.run_once()
        assert len(cache.binder.binds) == 10 and dc.grow_thread is None
        for k in range(5, 10):       # 20 pods of 1 cpu on 16 cpu
            wave(k)
        sched.run_once()
        assert len(cache.binder.binds) == 16
        assert dc.grow_thread is not None
        floor = dc.dims_floor["T"]
        dc.grow_thread.join(300)
        assert dc.dims_floor["T"] == 2 * floor
        wave(10)
        sched.run_once()
        assert dc._layout is not None
        assert dict((k, s) for k, _a, _o, _z, s in dc._layout)[
            "task_valid"][0] == dc.dims_floor["T"]


class TestPersistentCacheConfig:
    def test_configure_writes_executables(self, tmp_path, monkeypatch):
        import jax

        prev_dir = jax.config.jax_compilation_cache_dir
        prev_cfg = pc._configured_dir
        d = tmp_path / "xla-cache"
        try:
            got = pc.configure_compilation_cache(str(d))
            assert got == str(d)
            assert jax.config.jax_compilation_cache_dir == str(d)
            # idempotent
            assert pc.configure_compilation_cache(str(d)) == str(d)

            # a fresh jit signature must land an executable on disk
            f = jax.jit(lambda x: x * 3 + 1)
            np.asarray(f(np.arange(13, dtype=np.float32)))
            entries = list(d.iterdir())
            if not entries:  # backend without persistent-cache support
                pytest.skip("persistent cache unsupported on this backend")
            assert entries
        finally:
            pc._configured_dir = prev_cfg
            jax.config.update("jax_compilation_cache_dir", prev_dir)

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        """$JAX_COMPILATION_CACHE_DIR set: the directory in effect is
        the variable's, and no directory is set in code — not even the
        entry points' fixed default."""
        import jax

        prev_cfg = pc._configured_dir
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            pc._configured_dir = None
            monkeypatch.setenv(pc.CACHE_DIR_ENV, str(tmp_path / "envcache"))
            got = pc.configure_compilation_cache(
                default_dir=str(tmp_path / "fixed"))
            assert got == str(tmp_path / "envcache")
            assert jax.config.jax_compilation_cache_dir == prev_dir
            assert not (tmp_path / "fixed").exists()
        finally:
            pc._configured_dir = prev_cfg
            jax.config.update("jax_compilation_cache_dir", prev_dir)

    def test_explicit_dir_wins_over_env(self, monkeypatch, tmp_path):
        import jax

        prev_cfg = pc._configured_dir
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            pc._configured_dir = None
            monkeypatch.setenv(pc.CACHE_DIR_ENV, str(tmp_path / "envcache"))
            flag = str(tmp_path / "flag")
            assert pc.configure_compilation_cache(flag) == flag
            assert jax.config.jax_compilation_cache_dir == flag
        finally:
            pc._configured_dir = prev_cfg
            jax.config.update("jax_compilation_cache_dir", prev_dir)

    def test_disabled_without_dir(self, monkeypatch):
        """The library (no flag, no variable) keeps the cache off, so
        tests never write into the checkout."""
        prev_cfg = pc._configured_dir
        try:
            pc._configured_dir = None
            monkeypatch.delenv(pc.CACHE_DIR_ENV, raising=False)
            assert pc.configure_compilation_cache() is None
        finally:
            pc._configured_dir = prev_cfg

    def test_entry_point_default_is_fixed_in_checkout(self, monkeypatch,
                                                      tmp_path):
        """No flag, no variable: an entry point passes the fixed
        in-checkout path, which .gitignore lists."""
        import os

        import jax

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert pc.ENTRY_POINT_CACHE_DIR == os.path.join(root, ".jax_cache")
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        prev_cfg = pc._configured_dir
        prev_dir = jax.config.jax_compilation_cache_dir
        try:
            pc._configured_dir = None
            monkeypatch.delenv(pc.CACHE_DIR_ENV, raising=False)
            fixed = str(tmp_path / "fixed")
            assert pc.configure_compilation_cache(default_dir=fixed) \
                == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
        finally:
            pc._configured_dir = prev_cfg
            jax.config.update("jax_compilation_cache_dir", prev_dir)
