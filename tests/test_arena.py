"""Device-resident arena + session pipeline tests (PR 6 contracts).

- Byte-identity: the arena path (persistent device-resident chunked
  buffers, dirty-chunk deltas, pinned params) makes bind-for-bind
  identical decisions to the cold path (no arena, no flatten cache, full
  upload every cycle) across a 20-cycle churn script that includes a
  compile-bucket crossing AND a forced device-failure burst that trips
  the circuit breaker mid-run — with zero full-buffer uploads outside
  the cycles where a full ship is the contract (first session, layout
  changes, post-invalidate re-pin).
- Collect-failure re-pin: an async-collect failure soft-invalidates the
  arena — the donated chunked buffers are dropped, but the pinned params
  survive and are re-validated (not re-uploaded) on the next session.
- Phase-overlap smoke: 3 pipelined sessions on CPU exercising the
  three-phase machinery, asserting session N+1's upload dispatch lands
  before session N's collect completes.
- Bench fault isolation: bench.main always exits 0 with one parseable
  JSON line, converting crashes into error fields (BENCH_r05's rc=1
  regression).
"""

from __future__ import annotations

import json
import os
import sys
import threading

import numpy as np
import pytest

from volcano_tpu.ops import PackedDeviceCache, flatten_snapshot

from test_precompile import FLAGS, _mini_problem, _score_params


# ---------------------------------------------------------------------------
# scheduler-level churn harness
# ---------------------------------------------------------------------------

def _build_cluster(n_nodes=4):
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase

    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    store.apply("queues", build_queue("q0", weight=1))
    # sized so 20 cycles of bound-and-never-completing pods all fit:
    # a full cluster would leave later waves pending, growing T every
    # cycle and turning every session into a layout-change full ship
    for i in range(n_nodes):
        store.create("nodes", build_node(f"n{i}",
                                         {"cpu": "128", "memory": "512Gi"}))

    def wave(k, tpj=2):
        pg = build_pod_group(f"j{k}", "t", min_member=tpj, queue="q0")
        pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", pg)
        for i in range(tpj):
            store.create("pods", build_pod(
                "t", f"j{k}-{i}", "", "Pending",
                {"cpu": str(1 + (k + i) % 2), "memory": "1Gi"}, f"j{k}"))

    return store, cache, wave


CYCLES = 20
CROSSING_AT = 10       # 5-job wave: T crosses its compile bucket
TRIP_AT = (12, 13)     # decode failures: breaker counts 2 -> opens
BREAKER_COOLDOWN = 3   # in cycles (injectable clock)


class TestArenaByteIdentity:
    def _run(self, arena: bool, monkeypatch):
        """20-cycle churn script; returns (bind streams per cycle,
        full-ship cycles, device cache). Cycle CROSSING_AT submits a
        bigger wave (bucket crossing), cycles TRIP_AT fail at decode
        (collect failure -> breaker trip -> open -> half-open probe)."""
        import volcano_tpu.ops.solver as solver_mod
        from volcano_tpu.resilience import CircuitBreaker
        from volcano_tpu.scheduler import Scheduler

        store, cache, wave = _build_cluster()
        cycle_no = [0]
        cache.breaker = CircuitBreaker(
            "device-solver", failure_threshold=2,
            cooldown_s=BREAKER_COOLDOWN, clock=lambda: float(cycle_no[0]))
        if not arena:
            cache.device_cache = None
            cache.flatten_cache = None
        sched = Scheduler(cache)

        real_decode = solver_mod.decode_compact
        boom = [False]

        def maybe_boom(compact):
            if boom[0]:
                boom[0] = False
                raise RuntimeError("injected device loss at readback")
            return real_decode(compact)

        monkeypatch.setattr(solver_mod, "decode_compact", maybe_boom)

        streams, full_cycles, fallback_cycles = [], [], []
        k = 0
        dc = cache.device_cache
        for s in range(CYCLES):
            cycle_no[0] = s
            njobs = 5 if s == CROSSING_AT else 2
            for _ in range(njobs):
                wave(k)
                k += 1
            if s in TRIP_AT:
                boom[0] = True
            ships_before = dc.full_ships if dc is not None else 0
            sched.run_once()
            streams.append(sorted(cache.binder.binds.items()))
            if dc is not None and dc.full_ships > ships_before:
                full_cycles.append(s)
            if sched.last_cycle_timing.get("host_fallback"):
                fallback_cycles.append(s)
        monkeypatch.setattr(solver_mod, "decode_compact", real_decode)
        return streams, full_cycles, fallback_cycles, dc

    def test_arena_vs_cold_binds_identical_across_churn(self, monkeypatch):
        arena_streams, full_cycles, arena_fb, dc = \
            self._run(arena=True, monkeypatch=monkeypatch)
        cold_streams, _, cold_fb, _ = \
            self._run(arena=False, monkeypatch=monkeypatch)

        # the breaker script played out identically in both runs: the two
        # injected collect failures, then open-breaker host cycles until
        # the half-open probe
        assert arena_fb == cold_fb
        assert set(TRIP_AT) <= set(arena_fb)
        # bind-for-bind identity, cycle by cycle
        assert arena_streams == cold_streams

        # full-buffer uploads happened ONLY where the contract says:
        # first session, the bucket crossing (layout change, both ways),
        # and the re-pin sessions after the collect failures
        # (TRIP_AT[1] full-ships because TRIP_AT[0] invalidated; the
        # half-open probe cycle full-ships after TRIP_AT[1] invalidated)
        probe_cycle = TRIP_AT[1] + BREAKER_COOLDOWN
        allowed = {0, CROSSING_AT, CROSSING_AT + 1, TRIP_AT[1],
                   probe_cycle}
        assert set(full_cycles) <= allowed, full_cycles
        # steady tail: deltas only
        assert all(s < probe_cycle + 1 for s in full_cycles)

        # the arena stayed warm through the whole run: params were pinned
        # exactly once (re-validated, not re-uploaded, after the trips)
        assert dc.params_repins == 1
        assert dc.invalidations == 2
        # and most sessions were arena hits
        assert dc.delta_sessions >= CYCLES - len(allowed) - len(arena_fb)

    def test_breaker_recovered_to_closed(self, monkeypatch):
        _, _, fallback_cycles, dc = self._run(arena=True,
                                              monkeypatch=monkeypatch)
        # open-breaker cycles end at the half-open probe; the tail ran on
        # the device path again
        assert fallback_cycles
        assert max(fallback_cycles) < CYCLES - 1


# ---------------------------------------------------------------------------
# collect-failure re-pin (unit level)
# ---------------------------------------------------------------------------

class TestArenaInvalidate:
    def _session(self, dc, jobs, nodes, tasks):
        from volcano_tpu.ops.solver import solve_allocate_delta

        arr = flatten_snapshot(jobs, nodes, tasks)
        fbuf, ibuf, layout = arr.packed()
        params = dc.params_device(_score_params(arr))
        payload = dc.plan_delta(fbuf, ibuf, layout)
        res, nf, ni = solve_allocate_delta(*payload, layout, params,
                                           **FLAGS)
        dc.commit(nf, ni)
        return np.asarray(res.compact)

    def test_invalidate_keeps_params_and_reships_once(self):
        jobs, nodes, tasks = _mini_problem(4, 3, 2)
        dc = PackedDeviceCache()
        c1 = self._session(dc, jobs, nodes, tasks)
        assert dc.full_ships == 1 and dc.params_repins == 1
        pinned = dc._params_dev

        dc.invalidate()       # what a collect failure now does
        assert dc._dev_f is None and dc._layout is None
        assert dc._params_blob is not None  # pinned params survived

        c2 = self._session(dc, jobs, nodes, tasks)
        # one full re-ship, then back to steady
        assert dc.full_ships == 2 and dc.last_full_ship
        # params re-validated in place: same device dict, no re-upload
        assert dc.params_repins == 1
        assert dc._params_dev is pinned
        assert np.array_equal(c1, c2)

        c3 = self._session(dc, jobs, nodes, tasks)
        assert dc.full_ships == 2  # steady again: delta (zero-dirty) path
        assert np.array_equal(c1, c3)

    def test_invalidate_repins_params_when_device_copies_died(self):
        jobs, nodes, tasks = _mini_problem(4, 3, 2)
        dc = PackedDeviceCache()
        self._session(dc, jobs, nodes, tasks)
        assert dc.params_repins == 1
        for v in dc._params_dev.values():
            v.delete()        # an actual device restart deletes buffers
        dc.invalidate()
        self._session(dc, jobs, nodes, tasks)
        # re-validation found dead buffers -> params re-uploaded once
        assert dc.params_repins == 2

    def test_hard_reset_drops_params(self):
        jobs, nodes, tasks = _mini_problem(4, 3, 2)
        dc = PackedDeviceCache()
        self._session(dc, jobs, nodes, tasks)
        dc.reset()
        assert dc._params_blob is None and dc._params_dev is None

    def test_zero_dirty_session_ships_nothing(self):
        jobs, nodes, tasks = _mini_problem(4, 3, 2)
        dc = PackedDeviceCache()
        arr = flatten_snapshot(jobs, nodes, tasks)
        fbuf, ibuf, layout = arr.packed()
        dc.plan_delta(fbuf, ibuf, layout)
        f2d, i2d, fi, fv, ii, iv = dc.plan_delta(fbuf, ibuf, layout)
        # resident buffers, no upload: a no-op delta rewrites chunk 0
        # with its own bytes
        assert f2d is dc._dev_f and i2d is dc._dev_i
        assert not fi.any() and not ii.any()
        assert (fv == dc._host_f[:dc.chunk]).all()
        assert (iv == dc._host_i[:dc.chunk]).all()
        assert dc.last_shipped_chunks == 0
        assert not dc.last_full_ship
        assert dc.arena_hit_rate == 0.5


# ---------------------------------------------------------------------------
# three-phase pipeline smoke (fast, CPU)
# ---------------------------------------------------------------------------

class TestPipelineOverlapSmoke:
    def test_three_pipelined_sessions_overlap_phases(self):
        """3 pipelined sessions through the REAL arena dispatch path on
        CPU: flatten -> plan_delta -> fused solve dispatch -> collector
        readback, asserting the dispatch of session N+1's upload lands
        before session N's collect completes (the machinery the headline
        bench's steady-state measurement rides)."""
        from volcano_tpu.ops import SessionPipeline
        from volcano_tpu.ops.pipeline import start_readback
        from volcano_tpu.ops.solver import solve_allocate_delta

        dc = PackedDeviceCache()
        pipe = SessionPipeline(depth=2)
        gate = threading.Event()

        def make(sn, jobs, nodes, tasks):
            arr = flatten_snapshot(jobs, nodes, tasks)
            fbuf, ibuf, layout = arr.packed()
            params = dc.params_device(_score_params(arr))
            payload = dc.plan_delta(fbuf, ibuf, layout)

            def dispatch():
                r, nf, ni = solve_allocate_delta(*payload, layout, params,
                                                 **FLAGS)
                dc.commit(nf, ni)
                start_readback(r.compact)
                return r

            def collect(r):
                if sn == 0:
                    # hold session 0's collect until session 1 has
                    # dispatched: on CPU the solve completes instantly, so
                    # without the gate the interleaving is a coin flip and
                    # the overlap assertion would flake
                    gate.wait(10)
                return np.asarray(r.compact)

            return dispatch, collect

        results = []
        for sn in range(3):
            # churn: rotate the job mix so each session ships a real delta
            jobs, nodes, tasks = _mini_problem(4, 3, 2 + sn % 2)
            t = pipe.submit(sn, *make(sn, jobs, nodes, tasks))
            results.append(t)
            if sn == 1:
                gate.set()
        done = pipe.drain(timeout=60)
        pipe.close()
        assert len(done) == 3 and all(t.done() for t in done)
        # the phase-overlap evidence: session 1's upload dispatch landed
        # while session 0 was still uncollected
        assert pipe.overlap_pairs() >= 1, pipe.events
        # FIFO collect order
        assert [t.tag for t in done] == [0, 1, 2]
        # sessions produced real decisions
        for t in done:
            assert np.asarray(t.result()).size > 0

    def test_pipeline_backpressure_and_errors(self):
        from volcano_tpu.ops import SessionPipeline

        pipe = SessionPipeline(depth=1)
        with pytest.raises(ValueError):
            SessionPipeline(depth=0)

        t1 = pipe.submit(0, lambda: 1, lambda x: x + 1)
        assert t1.result(10) == 2

        def boom(_):
            raise RuntimeError("collect exploded")

        t2 = pipe.submit(1, lambda: 1, boom)
        with pytest.raises(RuntimeError, match="collect exploded"):
            t2.result(10)
        # the pipeline survives a failed collect
        t3 = pipe.submit(2, lambda: 2, lambda x: x * 2)
        assert t3.result(10) == 4
        pipe.close()


# ---------------------------------------------------------------------------
# bench fault isolation (BENCH_r05 rc=1 regression)
# ---------------------------------------------------------------------------

def _import_bench():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import bench
    return bench


class TestBenchFaultIsolation:
    def test_run_config_converts_crash_to_error_record(self):
        bench = _import_bench()

        def boom():
            raise ValueError("config exploded")

        rec = bench._run_config("x", boom)
        assert rec["error"].startswith("ValueError")
        assert rec["attempts"] == 1
        assert rec["traceback_tail"]

    def test_run_config_retries_transient_then_records(self):
        bench = _import_bench()
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise ConnectionError("store wire: connection reset by peer")

        rec = bench._run_config("x", flaky)
        assert calls["n"] == 2          # one transient retry
        assert rec["attempts"] == 2
        assert "connection reset" in rec["error"]

    def test_run_config_does_not_retry_device_runtime_error(self):
        """A runtime error of the local chip (OOM, Mosaic failure) is
        a real fault: recorded at once, never re-sent."""
        bench = _import_bench()
        calls = {"n": 0}

        JaxRuntimeError = type("JaxRuntimeError", (RuntimeError,), {})

        def oom():
            calls["n"] += 1
            raise JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

        rec = bench._run_config("x", oom)
        assert calls["n"] == 1
        assert rec["attempts"] == 1
        assert "RESOURCE_EXHAUSTED" in rec["error"]

    def test_run_config_recovers_on_transient_retry(self):
        bench = _import_bench()
        calls = {"n": 0}

        def flaky_once():
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("socket closed")
            return {"ok": True}

        assert bench._run_config("x", flaky_once) == {"ok": True}

    def test_main_always_exits_zero_with_json(self, monkeypatch, capsys):
        bench = _import_bench()

        def boom():
            raise RuntimeError("everything is on fire")

        monkeypatch.setattr(bench, "_main_inner", boom)
        rc = bench.main()
        out = capsys.readouterr().out.strip().splitlines()[-1]
        art = json.loads(out)
        assert rc == 0
        assert art["value"] is None
        assert "everything is on fire" in art["error"]

    def test_main_emits_json_when_artifact_not_serializable(
            self, monkeypatch, capsys):
        bench = _import_bench()
        monkeypatch.setattr(bench, "_main_inner",
                            lambda: {"value": object()})
        rc = bench.main()
        art = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0 and "not serializable" in art["error"]


class TestTransientRetry:
    def test_retries_transient_only(self):
        from volcano_tpu.resilience.transient import retry_transient

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("connection reset")
            return 7

        assert retry_transient(flaky, delay_s=0.0) == 7
        assert calls["n"] == 2

        def fatal():
            raise ValueError("wrong shape")

        with pytest.raises(ValueError):
            retry_transient(fatal, delay_s=0.0)

    def test_device_runtime_error_is_not_retried(self):
        from volcano_tpu.resilience.transient import (
            is_transient, retry_transient,
        )

        JaxRuntimeError = type("JaxRuntimeError", (RuntimeError,), {})
        calls = {"n": 0}

        def mosaic_failure():
            calls["n"] += 1
            raise JaxRuntimeError("INTERNAL: Mosaic failed to compile")

        assert not is_transient(JaxRuntimeError("INTERNAL: Mosaic"))
        with pytest.raises(JaxRuntimeError):
            retry_transient(mosaic_failure, delay_s=0.0)
        assert calls["n"] == 1

    def test_final_transient_failure_propagates(self):
        from volcano_tpu.resilience.transient import retry_transient

        def always():
            raise TimeoutError("deadline timed out")

        with pytest.raises(TimeoutError):
            retry_transient(always, retries=1, delay_s=0.0)
