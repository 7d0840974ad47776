"""Sharded device-resident arena: scheduler-level contracts (PR 7).

- Seeded fuzz parity: the sharded(D=8) scheduler makes bind-for-bind
  identical decisions to the packed(D=1) scheduler across churn that
  includes a compile-bucket crossing, a forced breaker trip mid-run
  (both runs degrade through the identical host-oracle fallback), and
  two quiet cycles; the host-oracle run completes the identical WORK
  (same pods bound every cycle — node choice may differ by the solver's
  documented waterfall-striping deviation).
- Zero-dirty steady state: a sharded session over an unchanged snapshot
  ships 0 bytes to every shard (the acceptance criterion), asserted at
  the scheduler level.
- Per-mode arena accounting: a sharded cycle's wire bytes land on the
  sharded arena's metrics series; the packed arena stays untouched.
- --solver-mode routing: packed/sharded/auto decision rule units.
"""

from __future__ import annotations

import numpy as np
import pytest

from helpers import build_node, build_pod, build_pod_group, build_queue


def _build_cluster(n_nodes=4):
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase

    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    store.apply("queues", build_queue("q0", weight=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(
            f"n{i}", {"cpu": "128", "memory": "512Gi"}))

    def wave(k, tpj=2, cpu=None):
        pg = build_pod_group(f"j{k}", "t", min_member=tpj, queue="q0")
        pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", pg)
        for i in range(tpj):
            store.create("pods", build_pod(
                "t", f"j{k}-{i}", "", "Pending",
                {"cpu": cpu or str(1 + (k + i) % 2), "memory": "1Gi"},
                f"j{k}"))

    return store, cache, wave


CYCLES = 12
CROSSING_AT = 5        # bigger wave: T crosses its compile bucket
TRIP_AT = (7, 8)       # output-check failures: breaker counts 2 -> opens
QUIET_AT = (10, 11)    # no submissions: cycle 11 must be zero-dirty
BREAKER_COOLDOWN = 2   # in cycles (injectable clock)


class _ChurnHarness:
    """One seeded churn script run under a given allocate mode."""

    def run(self, mode, seed, monkeypatch):
        import volcano_tpu.actions.allocate as alloc_mod
        from volcano_tpu.resilience import CircuitBreaker
        from volcano_tpu.scheduler import Scheduler
        from volcano_tpu.sim.virtualcluster import build_conf

        rng = np.random.default_rng(seed)
        store, cache, wave = _build_cluster()
        cycle_no = [0]
        cache.breaker = CircuitBreaker(
            "device-solver", failure_threshold=2,
            cooldown_s=BREAKER_COOLDOWN, clock=lambda: float(cycle_no[0]))
        sched = Scheduler(cache, scheduler_conf=build_conf(mode))

        real_check = alloc_mod.AllocateAction._check_solver_output
        boom = [False]

        def maybe_boom(assigned, kind, n_tasks, n_nodes):
            if boom[0]:
                boom[0] = False
                raise RuntimeError("injected device loss at readback")
            return real_check(assigned, kind, n_tasks, n_nodes)

        monkeypatch.setattr(alloc_mod.AllocateAction,
                            "_check_solver_output",
                            staticmethod(maybe_boom))

        streams, bound_sets, fallback_cycles = [], [], []
        zero_dirty_bytes = None
        zero_dirty_shards = None
        k = 0
        # one permanently-pending gang so the quiet cycles still flatten
        # a non-empty problem (otherwise the solver never dispatches and
        # "zero-dirty" would be vacuous)
        wave(10_000, tpj=1, cpu="100000")
        for s in range(CYCLES):
            cycle_no[0] = s
            if s not in QUIET_AT:
                njobs = 5 if s == CROSSING_AT else int(rng.integers(1, 3))
                for _ in range(njobs):
                    wave(k, tpj=int(rng.integers(1, 4)))
                    k += 1
            if s in TRIP_AT:
                boom[0] = True
            before = dict(cache.binder.binds)
            sched.run_once()
            binds = sorted(cache.binder.binds.items())
            streams.append(binds)
            bound_sets.append({p for p, _ in binds})
            if sched.last_cycle_timing.get("host_fallback"):
                fallback_cycles.append(s)
            if s == QUIET_AT[1]:
                sdc = cache.sharded_device_cache
                if sdc is not None:
                    zero_dirty_bytes = sdc.last_shipped_bytes
                    zero_dirty_shards = list(sdc.last_shard_bytes)
            del before
        monkeypatch.setattr(alloc_mod.AllocateAction,
                            "_check_solver_output",
                            staticmethod(real_check))
        return dict(streams=streams, bound=bound_sets,
                    fallback=fallback_cycles, cache=cache,
                    zero_dirty_bytes=zero_dirty_bytes,
                    zero_dirty_shards=zero_dirty_shards,
                    timing=sched.last_cycle_timing)


class TestShardedParityFuzz:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_sharded_equals_packed_and_host_work(self, seed, monkeypatch):
        h = _ChurnHarness()
        sharded = h.run("sharded", seed, monkeypatch)
        packed = h.run("solver", seed, monkeypatch)
        host = h.run("host", seed, monkeypatch)

        # the breaker script played out identically: two injected output
        # failures, then open-breaker host cycles until the probe
        assert sharded["fallback"] == packed["fallback"]
        assert set(TRIP_AT) <= set(sharded["fallback"])
        assert max(sharded["fallback"]) < CYCLES - 1  # recovered

        # bind-for-bind identity vs the packed(D=1) path, cycle by cycle,
        # through the crossing, the trip, and the zero-dirty tail
        assert sharded["streams"] == packed["streams"]

        # host-oracle work parity: the same pods are bound after every
        # cycle (placement node may legitimately differ — the solver's
        # waterfall herd choice vs the host loop's per-task re-score)
        assert sharded["bound"] == host["bound"]

        # zero-dirty steady state: the second quiet cycle shipped 0
        # bytes to every shard and solved off the resident arena
        assert sharded["zero_dirty_bytes"] == 0
        assert sharded["zero_dirty_shards"] is not None
        assert not any(sharded["zero_dirty_shards"])

        sdc = sharded["cache"].sharded_device_cache
        assert sdc is not None and sdc.D == 8
        # the trip invalidated the sharded arena (once per trip) and the
        # arena came back to delta-serving afterwards
        assert sdc.invalidations == len(TRIP_AT)
        assert sdc.delta_sessions > 0

    def test_sharded_full_ships_only_where_contracted(self, monkeypatch):
        """Full-buffer uploads only at: first session, the bucket
        crossing, and the re-ship after each breaker-trip invalidate —
        the steady tail serves deltas (arena engaged, not re-shipping)."""
        import volcano_tpu.actions.allocate as alloc_mod
        from volcano_tpu.resilience import CircuitBreaker
        from volcano_tpu.scheduler import Scheduler
        from volcano_tpu.sim.virtualcluster import build_conf

        store, cache, wave = _build_cluster()
        cycle_no = [0]
        cache.breaker = CircuitBreaker(
            "device-solver", failure_threshold=2,
            cooldown_s=BREAKER_COOLDOWN, clock=lambda: float(cycle_no[0]))
        sched = Scheduler(cache, scheduler_conf=build_conf("sharded"))
        real_check = alloc_mod.AllocateAction._check_solver_output
        boom = [False]

        def maybe_boom(assigned, kind, n_tasks, n_nodes):
            if boom[0]:
                boom[0] = False
                raise RuntimeError("injected")
            return real_check(assigned, kind, n_tasks, n_nodes)

        monkeypatch.setattr(alloc_mod.AllocateAction,
                            "_check_solver_output",
                            staticmethod(maybe_boom))
        full_cycles, k = [], 0
        for s in range(CYCLES):
            cycle_no[0] = s
            njobs = 5 if s == CROSSING_AT else 2
            for _ in range(njobs):
                wave(k)
                k += 1
            if s in TRIP_AT:
                boom[0] = True
            sdc = cache.sharded_device_cache
            ships_before = sdc.full_ships if sdc is not None else 0
            sched.run_once()
            sdc = cache.sharded_device_cache
            if sdc is not None and sdc.full_ships > ships_before:
                full_cycles.append(s)
        # TRIP_AT[0] fails at collect (already full/delta shipped), and
        # invalidates; the next DEVICE session full-ships. TRIP_AT[1]'s
        # session full-ships (post-invalidate) then fails again; the
        # half-open probe full-ships once more. Layout changes at the
        # crossing (and the cycle after, when the wave drains) re-ship.
        probe = TRIP_AT[1] + BREAKER_COOLDOWN
        allowed = {0, CROSSING_AT, CROSSING_AT + 1, TRIP_AT[1], probe}
        assert set(full_cycles) <= allowed, full_cycles
        assert max(full_cycles) <= probe
        sdc = cache.sharded_device_cache
        assert sdc.delta_sessions >= CYCLES - len(allowed) - len(TRIP_AT)


class TestPerModeArenaAccounting:
    def test_sharded_bytes_not_attributed_to_packed_arena(self):
        """The satellite fix: a sharded cycle's wire bytes must land on
        the sharded arena's volcano_arena_* series, and the packed arena
        must not account (or export) anything for it."""
        from volcano_tpu.metrics import metrics
        from volcano_tpu.scheduler import Scheduler
        from volcano_tpu.sim.virtualcluster import build_conf

        store, cache, wave = _build_cluster()
        sched = Scheduler(cache, scheduler_conf=build_conf("sharded"))
        for s in range(3):
            wave(s)
            sched.run_once()
        t = sched.last_cycle_timing
        assert t.get("arena_mode") == "sharded"
        assert "arena_bytes_shipped" in t
        assert t["mesh_devices"] == 8
        assert t["shard_bytes_max"] == max(
            cache.sharded_device_cache.last_shard_bytes)
        # packed arena untouched by sharded cycles
        assert cache.device_cache.sessions == 0
        sdc = cache.sharded_device_cache
        assert sdc.sessions == 3
        # per-mode gauges: sharded series live, per-shard gauge exported
        assert metrics.arena_bytes_shipped_total.get(
            {"mode": "sharded"}) == sdc.total_shipped_bytes
        assert metrics.arena_hit_rate.get(
            {"mode": "sharded"}) == pytest.approx(sdc.arena_hit_rate)
        shard0 = metrics.arena_shard_bytes_shipped.get({"shard": "0"})
        assert shard0 == sdc.last_shard_bytes[0]


class TestSolverModeRouting:
    def _ssn(self, **kw):
        from types import SimpleNamespace

        base = dict(configurations=[], solver_options={},
                    solver_mode=None, sharded_byte_budget=0,
                    device_cache=None, sharded_device_cache=None)
        base.update(kw)
        return SimpleNamespace(**base)

    def _resolve(self, ssn):
        from volcano_tpu.actions.allocate import AllocateAction

        return AllocateAction().resolve_mode(ssn)

    def test_defaults_and_explicit_modes(self):
        assert self._resolve(self._ssn()) == "solver"
        assert self._resolve(self._ssn(solver_mode="packed")) == "solver"
        assert self._resolve(self._ssn(solver_mode="sharded")) == "sharded"

    def test_conf_pin_wins_over_preference(self):
        from types import SimpleNamespace

        conf = SimpleNamespace(name="allocate",
                               arguments={"mode": "sequential"})
        ssn = self._ssn(configurations=[conf], solver_mode="sharded")
        assert self._resolve(ssn) == "sequential"
        # a conf block for allocate WITHOUT a mode leaves the
        # preference in charge
        conf2 = SimpleNamespace(name="allocate", arguments={})
        ssn2 = self._ssn(configurations=[conf2], solver_mode="sharded")
        assert self._resolve(ssn2) == "sharded"

    def test_auto_shards_on_byte_budget(self):
        class _DC:
            def __init__(self, n):
                self.n = n

            def full_upload_bytes(self):
                return self.n

        # no measurement yet -> packed; unset budget -> packed
        assert self._resolve(self._ssn(solver_mode="auto",
                                       sharded_byte_budget=100)) \
            == "solver"
        assert self._resolve(self._ssn(solver_mode="auto",
                                       device_cache=_DC(1000))) == "solver"
        # measured footprint over budget -> sharded (either arena's
        # measurement counts)
        assert self._resolve(self._ssn(
            solver_mode="auto", sharded_byte_budget=100,
            device_cache=_DC(1000))) == "sharded"
        assert self._resolve(self._ssn(
            solver_mode="auto", sharded_byte_budget=100,
            sharded_device_cache=_DC(101))) == "sharded"
        assert self._resolve(self._ssn(
            solver_mode="auto", sharded_byte_budget=2000,
            device_cache=_DC(1000))) == "solver"
        # force_host overrides everything
        ssn = self._ssn(solver_mode="sharded",
                        solver_options={"force_host_allocate": True})
        assert self._resolve(ssn) == "host"

    def test_standalone_and_vcctl_expose_the_flag(self):
        import inspect

        from volcano_tpu import standalone as sa_mod
        from volcano_tpu.cli import vcctl
        from volcano_tpu.sim.replay import run_sim
        from volcano_tpu.sim.virtualcluster import VirtualCluster

        for mod in (sa_mod, vcctl):
            assert "--solver-mode" in open(mod.__file__).read(), mod
        for fn in (run_sim, VirtualCluster.__init__):
            sig = inspect.signature(fn)
            assert "solver_mode" in sig.parameters, fn
            assert "sharded_byte_budget" in sig.parameters, fn
        assert "solver_mode" in inspect.signature(
            sa_mod.Standalone.__init__).parameters

    def test_scheduler_wires_solver_mode_to_cache(self):
        from volcano_tpu.scheduler import Scheduler

        store, cache, wave = _build_cluster()
        Scheduler(cache, solver_mode="auto",
                  sharded_byte_budget=12345)
        assert cache.solver_mode == "auto"
        assert cache.sharded_byte_budget == 12345


class TestShardedScaleBenchSmoke:
    def test_reduced_scale_completes_ok_on_cpu_mesh(self):
        """The sharded_100k_10k config at CPU-smoke scale: rc-0/ok-true
        shape, per-shard byte fields, zero-dirty contract, and the
        sub-scale digest cross-check vs the D=1 packed path."""
        import os
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        import bench

        out = bench.sharded_scale(
            n_tasks=1024, n_nodes=256, pipe_sessions=3,
            churn_tasks=32, churn_nodes=8, sub_tasks=512, sub_nodes=128)
        assert out["subscale_digest_identical"] is True
        assert out["mesh_devices"] == 8
        assert out["ok"] is True, out
        assert out["zero_dirty_ok"] is True
        assert not any(out["zero_dirty_shard_bytes"])
        assert len(out["bytes_per_shard_per_session"]) == 8
        assert out["bytes_shipped_per_session"] < out["full_upload_bytes"]
        assert out["placed"] > 0

    def test_degrades_to_partial_artifact_on_single_device(self):
        """Devices absent: error fields, never a crash — and the
        sub-scale cross-check still runs at D=1."""
        import os
        import sys
        from unittest import mock

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        import bench
        import volcano_tpu.parallel as par

        import jax

        mesh1 = par.make_mesh(jax.devices()[:1])
        # bench resolves arena_mesh from volcano_tpu.parallel at call
        # time (function-local from-import), so patching the package
        # attribute simulates a single-device host
        with mock.patch("volcano_tpu.parallel.arena_mesh",
                        return_value=mesh1):
            out = bench.sharded_scale(
                n_tasks=512, n_nodes=128, pipe_sessions=2,
                sub_tasks=256, sub_nodes=64)
        assert out["ok"] is False
        assert "error" in out and "multi-device" in out["error"]
        assert out["subscale_digest_identical"] is True


class TestShardedArenaPrewarm:
    def test_warm_compiles_the_exact_dispatch_variant(self):
        """dummy_sharded_buffers + the sharded-arena warm must land the
        SAME jit cache entry the real ShardedDeviceCache dispatch keys
        (aval + sharding): after the warm, a real dispatch at that
        layout adds no new compiled variant."""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from volcano_tpu.ops import ShardedDeviceCache, flatten_snapshot
        from volcano_tpu.ops.precompile import (
            dummy_score_params, dummy_sharded_buffers, layout_dims,
        )
        from volcano_tpu.parallel import (
            make_mesh, solve_allocate_sharded_arena,
        )
        from test_solver import make_problem, params_dict

        mesh = make_mesh()
        jobs, nodes, tasks = make_problem(
            [(f"n{i}", "8", "32Gi") for i in range(16)],
            [(f"j{k}", 3, [("1", "2Gi")] * 3) for k in range(6)])
        arr = flatten_snapshot(jobs, nodes, tasks)
        fbuf, ibuf, layout = arr.packed()
        kw = dict(herd_mode="spread", score_families=("kube",))
        bufs = dummy_sharded_buffers(layout, 512, mesh)
        ns_n = NamedSharding(mesh, P("n"))
        ns_rep = NamedSharding(mesh, P())
        sp = {k: jax.device_put(np.asarray(v),
                                ns_n if k == "node_static" else ns_rep)
              for k, v in dummy_score_params(layout_dims(layout)).items()}
        solve_allocate_sharded_arena(
            *bufs, sp, mesh, **kw).assigned.block_until_ready()
        n_warm = solve_allocate_sharded_arena._cache_size()

        sdc = ShardedDeviceCache(mesh)
        real = sdc.update(fbuf, ibuf, layout)
        p = params_dict(arr, least_req_weight=1.0)
        solve_allocate_sharded_arena(
            *real, sdc.params_device(p), mesh,
            **kw).assigned.block_until_ready()
        assert solve_allocate_sharded_arena._cache_size() == n_warm
