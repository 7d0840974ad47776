"""Resilience subsystem tests (volcano_tpu/resilience + the seams it
hardens): device-path circuit breaker open/half-open/close, host-oracle
fallback parity, per-action containment (throwing AND hung actions),
last-good conf retention, idempotent-op retry with backoff, watch-stream
resume across a StoreServer restart (in-process and cross-process), the
resync-safe cache handlers, and the deterministic fault injector driving
all of it. The chaos soak is marked slow; `bench.py`'s chaos_churn config
is the full 50-cycle acceptance run."""

import os
import subprocess
import sys
import threading
import time

import pytest

from helpers import build_node, build_pod, build_pod_group, build_queue
from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
from volcano_tpu.client import ClusterStore, RemoteClusterStore, StoreServer
from volcano_tpu.metrics import metrics
from volcano_tpu.models import PodGroupPhase
from volcano_tpu.resilience import (
    ActionTimeout, ActionWatchdog, CircuitBreaker, FaultError,
    FaultInjector, faults,
)
from volcano_tpu.scheduler import Scheduler


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _build_cluster(n_nodes=4, n_jobs=3, tpj=2):
    store = ClusterStore()
    cache = SchedulerCache(store)
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


def _arena_scheduler(cache, arena, **kw):
    """A Scheduler whose allocate solves on ``arena``: "packed" is the
    default conf's device-resident packed arena, "sharded" the conf's
    ``mode: sharded`` over a one-device mesh arena."""
    if arena == "packed":
        return Scheduler(cache, **kw)
    import jax

    from volcano_tpu.ops.device_cache import ShardedDeviceCache
    from volcano_tpu.parallel import make_mesh
    from volcano_tpu.sim.virtualcluster import build_conf

    cache.sharded_device_cache = ShardedDeviceCache(
        make_mesh(jax.devices()[:1]))
    return Scheduler(cache, scheduler_conf=build_conf("sharded"), **kw)


# ---------------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_open_after_threshold_then_half_open_then_close(self):
        clock = FakeClock()
        br = CircuitBreaker("t", failure_threshold=3, cooldown_s=10.0,
                            clock=clock)
        assert br.state == "closed" and br.allow()
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"  # below threshold
        br.record_failure()
        assert br.state == "open"
        assert not br.allow()  # cool-down running
        clock.t += 9.9
        assert not br.allow()
        clock.t += 0.2
        assert br.allow()  # the half-open probe
        assert br.state == "half_open"
        br.record_success()
        assert br.state == "closed"
        trace = [(frm, to) for _, frm, to in br.transitions]
        assert trace == [("closed", "open"), ("open", "half_open"),
                         ("half_open", "closed")]

    def test_failed_probe_reopens_for_a_fresh_cooldown(self):
        clock = FakeClock()
        br = CircuitBreaker("t", failure_threshold=1, cooldown_s=5.0,
                            clock=clock)
        br.record_failure()
        clock.t += 6
        assert br.allow() and br.state == "half_open"
        br.record_failure()
        assert br.state == "open"
        assert not br.allow()  # fresh cool-down, not the stale one
        clock.t += 6
        assert br.allow()

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker("t", failure_threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed"  # never 2 CONSECUTIVE failures


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------

class TestFaultInjector:
    def test_counter_schedules_are_deterministic(self):
        fi = FaultInjector()
        fi.arm("p", at=(2, 4))
        hits = []
        for i in range(5):
            try:
                fi.fire("p")
                hits.append(False)
            except FaultError:
                hits.append(True)
        assert hits == [False, True, False, True, False]
        assert fi.log == [("p", 2), ("p", 4)]

    def test_every_and_times_cap(self):
        fi = FaultInjector()
        fi.arm("p", every=2, times=2)
        fired = 0
        for _ in range(10):
            try:
                fi.fire("p")
            except FaultError:
                fired += 1
        assert fired == 2

    def test_arm_once_fires_on_next_call_only(self):
        fi = FaultInjector()
        fi.fire("p")  # disarmed: free
        fi.arm_once("p")
        with pytest.raises(FaultError):
            fi.fire("p")
        fi.fire("p")  # spent

    def test_seeded_probability_replays(self):
        def run():
            fi = FaultInjector(seed=7)
            fi.arm("p", p=0.5)
            out = []
            for _ in range(20):
                try:
                    fi.fire("p")
                    out.append(0)
                except FaultError:
                    out.append(1)
            return out
        assert run() == run()
        assert 1 in run()

    def test_env_spec_parses(self):
        fi = FaultInjector(env="a=at:1-2;b=every:3,times:1;c=delay:0.5,exc:none")
        with pytest.raises(FaultError):
            fi.fire("a")
        assert fi._points["b"].every == 3
        assert fi._points["c"].exc is None
        assert fi._points["c"].delay == 0.5

    def test_injected_faults_are_connection_errors(self):
        # the store/watch retry paths must treat simulated drops like
        # real ones
        assert issubclass(FaultError, ConnectionError)


# ---------------------------------------------------------------------------
# conf hot-reload: last-good retention
# ---------------------------------------------------------------------------

GOOD_CONF = ('actions: "enqueue, allocate"\n'
             'tiers:\n- plugins:\n  - name: gang\n')


class TestConfLastGood:
    def _touch(self, path, bump):
        os.utime(path, (time.time() + bump, time.time() + bump))

    def test_bad_reload_keeps_last_good_and_counts_once(self, tmp_path,
                                                        caplog):
        conf_file = tmp_path / "scheduler.yaml"
        conf_file.write_text(GOOD_CONF)
        store, cache, wave = _build_cluster()
        sched = Scheduler(cache, conf_path=str(conf_file))
        assert [a.name() for a in sched.actions] == ["enqueue", "allocate"]

        before = metrics.conf_load_errors.get()
        conf_file.write_text("actions: [\ntiers: broken")  # invalid YAML
        self._touch(conf_file, 2)
        with caplog.at_level("ERROR"):
            sched.load_conf()
            sched.load_conf()  # same bad text: no second log/count
        assert [a.name() for a in sched.actions] == ["enqueue", "allocate"]
        assert metrics.conf_load_errors.get() == before + 1
        assert sum("keeping the last good conf" in r.message
                   for r in caplog.records) == 1

        # the scheduler keeps SCHEDULING on the last good conf
        sched.run_once()
        assert len(cache.binder.binds) == 6

        # an unknown action is a reload error too, not a crash
        conf_file.write_text('actions: "nosuch"\n')
        self._touch(conf_file, 4)
        sched.load_conf()
        assert [a.name() for a in sched.actions] == ["enqueue", "allocate"]
        assert metrics.conf_load_errors.get() == before + 2

        # a fixed file is picked up again
        conf_file.write_text('actions: "allocate, backfill"\n'
                             'tiers:\n- plugins:\n  - name: gang\n')
        self._touch(conf_file, 6)
        sched.load_conf()
        assert [a.name() for a in sched.actions] == ["allocate", "backfill"]

    def test_first_load_still_raises(self):
        store, cache, _ = _build_cluster()
        with pytest.raises(Exception):
            Scheduler(cache, scheduler_conf='actions: "nosuch"\n')


# ---------------------------------------------------------------------------
# per-action containment (throwing + hung)
# ---------------------------------------------------------------------------

from volcano_tpu.framework import Action, register_action  # noqa: E402


class _ExplodingAction(Action):
    """Allocates one task through a statement, then blows up."""

    def name(self):
        return "test_explode"

    def execute(self, ssn):
        job = next(iter(ssn.jobs.values()))
        from volcano_tpu.api import TaskStatus
        task = next(iter(job.task_status_index[TaskStatus.PENDING].values()))
        stmt = ssn.statement()
        stmt.allocate(task, next(iter(ssn.nodes)))
        raise RuntimeError("boom mid-statement")


class _RecordingAction(Action):
    ran = []

    def name(self):
        return "test_record"

    def execute(self, ssn):
        self.ran.append(ssn.uid)


class _HangingAction(Action):
    def name(self):
        return "test_hang"

    def execute(self, ssn):
        faults.fire("slow_action")  # armed with delay => simulated hang


register_action(_ExplodingAction())
register_action(_RecordingAction())
register_action(_HangingAction())

CONTAIN_CONF = ('actions: "test_explode, enqueue, allocate, test_record"\n'
                'tiers:\n- plugins:\n  - name: gang\n'
                '  - name: predicates\n  - name: nodeorder\n')


class TestActionContainment:
    def test_throwing_action_is_contained_and_rolled_back(self):
        store, cache, wave = _build_cluster(n_jobs=2)
        sched = Scheduler(cache, scheduler_conf=CONTAIN_CONF)
        _RecordingAction.ran.clear()
        before = metrics.action_failures_total.get(
            labels={"action": "test_explode"})
        sched.run_once()  # must NOT raise
        # the exploding action's half-done statement was discarded...
        # (its ALLOCATED task went back to PENDING, so allocate placed it)
        assert len(cache.binder.binds) == 4
        # ...and the remaining actions of the cycle still ran
        assert len(_RecordingAction.ran) == 1
        assert sched.last_cycle_timing.get("test_explode_error") == 1.0
        assert metrics.action_failures_total.get(
            labels={"action": "test_explode"}) == before + 1

    def test_hung_action_times_out_statements_discard_cycle_continues(self):
        store, cache, wave = _build_cluster(n_jobs=2)
        conf = ('actions: "test_hang, enqueue, allocate, test_record"\n'
                'tiers:\n- plugins:\n  - name: gang\n'
                '  - name: predicates\n  - name: nodeorder\n')
        sched = Scheduler(cache, scheduler_conf=conf,
                          action_deadline_s=0.4)
        _RecordingAction.ran.clear()
        faults.arm("slow_action", at=(1,), delay=2.0, exc=None)
        before = metrics.action_timeouts_total.get(
            labels={"action": "test_hang"})
        t0 = time.perf_counter()
        sched.run_once()
        dt = time.perf_counter() - t0
        assert dt < 1.9, "the hung action blocked the whole cycle"
        assert sched.last_cycle_timing.get("test_hang_timeout") == 1.0
        assert metrics.action_timeouts_total.get(
            labels={"action": "test_hang"}) == before + 1
        # the cycle went on without the hung action
        assert len(cache.binder.binds) == 4
        assert len(_RecordingAction.ran) == 1

    def test_zombie_commit_after_containment_is_discarded(self):
        """A timed-out action's thread waking up later must not push its
        statement through commit (the epoch fence in Statement.commit)."""
        store, cache, wave = _build_cluster(n_jobs=1)
        sched = Scheduler(cache)
        from volcano_tpu.framework import open_session
        ssn = open_session(cache, sched.tiers, sched.configurations)
        ssn._action_epoch = 1
        stmt = ssn.statement()
        from volcano_tpu.api import TaskStatus
        job = next(iter(ssn.jobs.values()))
        task = next(iter(job.task_status_index[TaskStatus.PENDING].values()))
        stmt.allocate(task, "n0")
        # the scheduler contains epoch 1 (deadline breach)
        ssn._contained_epochs.add(1)
        ssn.discard_open_statements()
        stmt.allocate(task, "n0")  # zombie keeps going
        stmt.commit()              # ...and commits late
        assert cache.binder.binds == {}  # fence turned it into a discard
        assert task.status == TaskStatus.PENDING

    def test_watchdog_raises_action_timeout(self):
        wd = ActionWatchdog(0.1, dump=False)
        with pytest.raises(ActionTimeout):
            wd.run("sleepy", lambda: time.sleep(1.0))
        # and relays the action's own exception
        with pytest.raises(ValueError):
            wd.run("thrower", lambda: (_ for _ in ()).throw(ValueError("x")))


# ---------------------------------------------------------------------------
# device-path breaker through the allocate action
# ---------------------------------------------------------------------------

class TestBreakerFallback:
    def test_open_half_open_close_through_scheduler_cycles(self):
        store, cache, wave = _build_cluster(n_jobs=2)
        clock = FakeClock()
        cache.breaker = CircuitBreaker(
            "device-solver", failure_threshold=2, cooldown_s=10.0,
            clock=clock)
        sched = Scheduler(cache)
        faults.arm("solver_dispatch", at=(1, 2))

        sched.run_once()  # injected failure 1: host fallback, still closed
        assert sched.last_cycle_timing.get("host_fallback") == 1.0
        assert len(cache.binder.binds) == 4  # host oracle placed everything
        assert cache.breaker.state == "closed"

        wave(2)
        sched.run_once()  # injected failure 2: breaker opens
        assert cache.breaker.state == "open"
        assert len(cache.binder.binds) == 6

        wave(3)
        sched.run_once()  # open: no dispatch attempted, straight to host
        assert sched.last_cycle_timing.get("breaker_open") == 1.0
        assert sched.last_cycle_timing.get("breaker_state") == 2.0
        assert cache.breaker.fallback_cycles >= 1
        assert len(cache.binder.binds) == 8
        assert faults.fired("solver_dispatch") == 2  # nothing consumed

        clock.t += 11  # cool-down elapses
        wave(4)
        sched.run_once()  # half-open probe succeeds -> closed
        assert cache.breaker.state == "closed"
        assert "host_fallback" not in sched.last_cycle_timing
        assert len(cache.binder.binds) == 10
        trace = [(frm, to) for _, frm, to in cache.breaker.transitions]
        assert trace == [("closed", "open"), ("open", "half_open"),
                         ("half_open", "closed")]

    @pytest.mark.parametrize("arena", ["packed", "sharded"])
    def test_garbage_readback_counts_as_device_failure(self, arena,
                                                       monkeypatch):
        """Out-of-range solver output (a sick device returning nonsense
        without raising) routes through the same containment, on either
        arena."""
        store, cache, wave = _build_cluster(n_jobs=2)
        sched = _arena_scheduler(cache, arena)
        import numpy as np

        if arena == "packed":
            import volcano_tpu.ops.solver as solver_mod

            def garbage(compact):
                n = np.asarray(compact).shape[0]
                return (np.full(n, 10 ** 6, np.int32),
                        np.zeros(n, np.int32))

            monkeypatch.setattr(solver_mod, "decode_compact", garbage)
        else:
            # the sharded solve packs no compact readback: its collect
            # reads ``assigned`` itself, so the garbage goes there
            import volcano_tpu.parallel as par

            real_solve = par.solve_allocate_sharded_arena

            def garbage_solve(*args, **kwargs):
                res = real_solve(*args, **kwargs)
                return res._replace(assigned=res.assigned * 0 + 10 ** 6)

            monkeypatch.setattr(par, "solve_allocate_sharded_arena",
                                garbage_solve)
        sched.run_once()
        assert sched.last_cycle_timing.get("host_fallback") == 1.0
        assert sched.last_cycle_timing.get("arena_mode") == arena
        assert len(cache.binder.binds) == 4
        # one recorded failure on the breaker
        assert cache.breaker._consecutive_failures == 1
        # the arena that solved is the one the fault invalidated
        dc = cache.device_cache if arena == "packed" \
            else cache.sharded_device_cache
        assert dc.invalidations == 1


class TestDegradedParity:
    @pytest.mark.parametrize("arena", ["packed", "sharded"])
    def test_fallback_cycle_binds_match_pure_host_cycle(self, arena):
        """The degradation ladder's first rung must be semantics-free:
        a device-fault cycle that fell back to the host oracle produces
        bind-for-bind the decisions of a cycle configured host-only,
        whichever arena the device path solves on."""
        host_conf = (
            'actions: "enqueue, allocate, backfill"\n'
            'tiers:\n'
            '- plugins:\n  - name: priority\n  - name: gang\n'
            '- plugins:\n  - name: drf\n  - name: predicates\n'
            '  - name: proportion\n  - name: nodeorder\n'
            'configurations:\n'
            '- name: allocate\n  arguments: {mode: host}\n')

        def run(conf, inject):
            faults.reset()
            store, cache, wave = _build_cluster(n_jobs=4)
            sched = Scheduler(cache, scheduler_conf=conf) if conf \
                else _arena_scheduler(cache, arena)
            if inject:
                faults.arm_once("solver_dispatch")
            sched.run_once()
            if inject:
                assert sched.last_cycle_timing.get("host_fallback") == 1.0
            return sorted(cache.binder.binds.items())

        degraded = run(None, inject=True)
        pure_host = run(host_conf, inject=False)
        assert degraded == pure_host


# ---------------------------------------------------------------------------
# store client: idempotent retry with backoff
# ---------------------------------------------------------------------------

class TestRequestRetry:
    def test_read_rides_out_a_server_restart(self):
        store = ClusterStore()
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        server = StoreServer(store).start()
        port = server.port
        remote = RemoteClusterStore(
            f"127.0.0.1:{port}", connect_timeout=1.0,
            retry_attempts=40, retry_base_s=0.05, retry_cap_s=0.3)
        assert remote.ping()
        server.stop()
        box = []

        def restart():
            time.sleep(1.0)  # ~a systemd bounce
            box.append(StoreServer(store, port=port).start())

        t = threading.Thread(target=restart, daemon=True)
        before = metrics.store_request_retries_total.get()
        t.start()
        try:
            got = remote.get("nodes", "n1")  # retries through the gap
            assert got.name == "n1"
            assert metrics.store_request_retries_total.get() > before
        finally:
            t.join()
            remote.close()
            for s in box:
                s.stop()

    def test_injected_drop_is_retried(self, served):
        store, remote = served
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        faults.arm_once("store_request")
        assert remote.get("nodes", "n1").name == "n1"
        assert faults.fired("store_request") == 1

    @pytest.fixture()
    def served(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        remote = RemoteClusterStore(server.address, retry_base_s=0.01)
        try:
            yield store, remote
        finally:
            remote.close()
            server.stop()

    def _lose_ack_once(self, monkeypatch, for_ops):
        """Deliver the request frame, then break the connection on the
        RESPONSE read — the applied-but-unacked window (a failure inside
        the send itself is unambiguous and always retry-safe)."""
        import volcano_tpu.client.remote as remote_mod
        orig_send = remote_mod.send_frame
        orig_recv = remote_mod.recv_frame
        dropped = []
        state = {"armed": None}

        def send(sock, payload):
            orig_send(sock, payload)
            if payload.get("op") in for_ops and not dropped:
                state["armed"] = payload.get("op")

        def recv(sock):
            if state["armed"] is not None:
                dropped.append(state["armed"])
                state["armed"] = None
                raise ConnectionError("simulated ack loss")
            return orig_recv(sock)

        monkeypatch.setattr(remote_mod, "send_frame", send)
        monkeypatch.setattr(remote_mod, "recv_frame", recv)
        return dropped

    def test_unacked_update_retries_conditionally_surfaces_conflict(
            self, served, monkeypatch):
        """A bind-shaped update whose ack is lost after the server
        applied it must NOT double-apply on retry: the carried
        resource_version re-presents the precondition, so the replay
        surfaces ConflictError to the caller instead."""
        from volcano_tpu.client.store import ConflictError

        store, remote = served
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        node = remote.get("nodes", "n1")
        node.labels = {"zone": "a"}
        dropped = self._lose_ack_once(monkeypatch, ("update",))
        with pytest.raises(ConflictError):
            remote.update("nodes", node)
        assert dropped == ["update"]
        assert store.get("nodes", "n1").labels == {"zone": "a"}  # applied ONCE

    def test_unacked_create_retries_and_surfaces_conflict(
            self, served, monkeypatch):
        from volcano_tpu.client.store import ConflictError

        store, remote = served
        dropped = self._lose_ack_once(monkeypatch, ("create",))
        with pytest.raises(ConflictError):
            remote.create("nodes", build_node("n1", {"cpu": "1"}))
        assert dropped == ["create"]
        assert len(store.list("nodes")) == 1  # exactly one, not two

    def test_unacked_unconditional_update_still_raises_transport_error(
            self, served, monkeypatch):
        """No resource_version = no precondition: replaying would be a
        blind double-apply, so the transport error surfaces instead."""
        from volcano_tpu.models import Node

        store, remote = served
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        bare = Node(name="n1", allocatable={"cpu": "2"})  # rv 0
        self._lose_ack_once(monkeypatch, ("update",))
        with pytest.raises((ConnectionError, OSError)):
            remote.update("nodes", bare)


# ---------------------------------------------------------------------------
# watch-stream resume
# ---------------------------------------------------------------------------

def _wait(cond, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


class TestWatchResume:
    def test_injected_break_resumes_and_replays_missed_events(self):
        """Stream dies between two events (server stays up): the resume
        replays exactly the missed events from the journal, once."""
        store = ClusterStore()
        server = StoreServer(store).start()
        events = []
        remote = RemoteClusterStore(server.address,
                                    watch_backoff_cap_s=0.2)
        try:
            store.create("nodes", build_node("n1", {"cpu": "1"}))
            remote.watch("nodes", lambda ev, obj, old:
                         events.append((ev, obj.name)))
            assert events == [("add", "n1")]
            # the next received frame breaks the stream BEFORE delivery:
            # n2's event is lost from the wire, recovered via the journal
            faults.arm_once("watch_stream")
            store.create("nodes", build_node("n2", {"cpu": "1"}))
            assert _wait(lambda: ("add", "n2") in events)
            store.create("nodes", build_node("n3", {"cpu": "1"}))
            assert _wait(lambda: ("add", "n3") in events)
            assert events.count(("add", "n2")) == 1  # no duplicate
            assert remote.watch_resumes >= 1
            assert not remote.watch_failed
        finally:
            remote.close()
            server.stop()

    def test_resume_across_store_server_restart(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        port = server.port
        events, fired = [], []
        remote = RemoteClusterStore(
            f"127.0.0.1:{port}", connect_timeout=1.0,
            watch_backoff_cap_s=0.2,
            on_watch_failure=lambda: fired.append(1))
        server2 = None
        try:
            store.create("nodes", build_node("n1", {"cpu": "1"}))
            remote.watch("nodes", lambda ev, obj, old:
                         events.append((ev, obj.name)))
            server.stop()
            time.sleep(0.3)  # client is now in its backoff loop
            server2 = StoreServer(store, port=port).start()
            store.create("nodes", build_node("n2", {"cpu": "1"}))
            assert _wait(lambda: ("add", "n2") in events)
            assert events == [("add", "n1"), ("add", "n2")]
            assert not fired and not remote.watch_failed
            assert remote.watch_resumes >= 1
        finally:
            remote.close()
            for s in (server2,):
                if s is not None:
                    s.stop()

    def test_lost_resume_window_falls_back_crash_only(self):
        """Writes land while the server is down: the new server's journal
        cannot cover them, the resume refuses (ResumeGapError) and the
        crash-only contract fires exactly once."""
        store = ClusterStore()
        server = StoreServer(store).start()
        port = server.port
        fired = []
        remote = RemoteClusterStore(
            f"127.0.0.1:{port}", connect_timeout=1.0,
            watch_backoff_cap_s=0.2,
            on_watch_failure=lambda: fired.append(1))
        server2 = None
        try:
            store.create("nodes", build_node("n1", {"cpu": "1"}))
            remote.watch("nodes", lambda *a: None)
            server.stop()
            # missed while down — unreplayable by the restarted server
            store.create("nodes", build_node("n2", {"cpu": "1"}))
            server2 = StoreServer(store, port=port).start()
            assert _wait(lambda: fired == [1])
            assert remote.watch_failed
        finally:
            remote.close()
            for s in (server2,):
                if s is not None:
                    s.stop()

    def test_delete_events_survive_resume(self):
        """Deletes bump the store's rv and replay through the journal."""
        store = ClusterStore()
        server = StoreServer(store).start()
        events = []
        remote = RemoteClusterStore(server.address,
                                    watch_backoff_cap_s=0.2)
        try:
            store.create("nodes", build_node("n1", {"cpu": "1"}))
            store.create("nodes", build_node("n2", {"cpu": "1"}))
            remote.watch("nodes", lambda ev, obj, old:
                         events.append((ev, obj.name)))
            faults.arm_once("watch_stream")
            store.delete("nodes", "n2")
            assert _wait(lambda: ("delete", "n2") in events)
            assert events.count(("delete", "n2")) == 1
            assert not remote.watch_failed
        finally:
            remote.close()
            server.stop()


class TestResyncSafeHandlers:
    def test_replayed_add_of_known_pod_does_not_double_count(self):
        from volcano_tpu.client.codec import decode, encode

        store, cache, wave = _build_cluster(n_jobs=0)
        pod = build_pod("t", "p0", "n0", "Running",
                        {"cpu": "4", "memory": "4Gi"}, "pg0")
        store.create("podgroups", build_pod_group("pg0", "t", min_member=1))
        store.create("pods", pod)
        idle_after_add = cache.nodes["n0"].idle.clone()
        assert len(cache.nodes["n0"].tasks) == 1
        # a resume/re-list replays the add as a decoded copy: accounting
        # must stay single-counted, not raise, not double-subtract
        cache._on_pod("add", decode(encode(pod)), None)
        assert len(cache.nodes["n0"].tasks) == 1
        assert cache.nodes["n0"].idle == idle_after_add
        job = cache.jobs["t/pg0"]
        assert len(job.tasks) == 1


# ---------------------------------------------------------------------------
# cross-process: the HA scheduler proc survives a store-server restart
# ---------------------------------------------------------------------------

class TestCrossProcessWatchResume:
    def test_scheduler_proc_survives_server_restart(self):
        """Extends the ha_scheduler_proc flow: the round-5 outage class —
        a transient store-server drop — must now be a logged blip (watch
        resume + request retry), not an exit(3) crash-restart."""
        from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION

        store = ClusterStore()
        server = StoreServer(store).start()
        port = server.port
        store.create("nodes", Node(
            name="n1", allocatable={"cpu": "32", "memory": "64Gi"},
            capacity={"cpu": "32", "memory": "64Gi"}))

        def submit(idx):
            store.create("podgroups", PodGroup(
                name=f"pg{idx}", namespace="d",
                spec=PodGroupSpec(min_member=1)))
            store.create("pods", Pod(
                name=f"p{idx}", namespace="d",
                annotations={POD_GROUP_ANNOTATION: f"pg{idx}"},
                containers=[{"requests": {"cpu": "1", "memory": "1Gi"}}]))

        def bound(name):
            p = store.try_get("pods", name, "d")
            return p is not None and bool(p.node_name)

        submit(0)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.Popen(
            [sys.executable, os.path.join(here, "ha_scheduler_proc.py"),
             "--server", f"127.0.0.1:{port}", "--identity", "solo"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        server2 = None
        try:
            assert _wait(lambda: bound("p0"), timeout=120), \
                "scheduler never bound p0"
            server.stop()
            time.sleep(0.5)  # outage window: watch streams are broken
            server2 = StoreServer(store, port=port).start()
            submit(1)
            assert _wait(lambda: bound("p1"), timeout=60), \
                "scheduler did not recover after the server restart"
            # the proc rode the restart out in place — no crash-only exit
            assert proc.poll() is None
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
            for s in (server2,):
                if s is not None:
                    s.stop()


# ---------------------------------------------------------------------------
# chaos soak (slow; bench.py chaos_churn is the full acceptance run)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaosSoak:
    def test_fifteen_cycles_with_faults_zero_crashes(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        remote = RemoteClusterStore(server.address, connect_timeout=2.0,
                                    retry_base_s=0.05,
                                    watch_backoff_cap_s=0.3)
        cache = SchedulerCache(remote)
        cache.evictor = FakeEvictor()
        cache.run()
        clock = FakeClock()
        cache.breaker = CircuitBreaker("device-solver",
                                       failure_threshold=2,
                                       cooldown_s=3.0, clock=clock)
        sched = Scheduler(cache, period=0.05)
        store.apply("queues", build_queue("q0", weight=1))
        for i in range(4):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "16", "memory": "64Gi"}))

        def wave(k):
            pg = build_pod_group(f"j{k}", "t", min_member=2, queue="q0")
            pg.status.phase = PodGroupPhase.PENDING
            store.create("podgroups", pg)
            for i in range(2):
                store.create("pods", build_pod(
                    "t", f"j{k}-{i}", "", "Pending",
                    {"cpu": "1", "memory": "1Gi"}, f"j{k}"))

        crashes = 0
        try:
            for s in range(15):
                if s in (3, 9):
                    faults.arm_once("watch_stream")
                if s in (5, 11):
                    faults.arm_once("store_request")
                if s in (6, 7):
                    faults.arm_once("solver_dispatch")
                wave(s)
                assert _wait(lambda: f"t/j{s}" in cache.jobs
                             and len(cache.jobs[f"t/j{s}"].tasks) == 2), \
                    f"mirror froze before cycle {s}"
                clock.t += 1.0
                try:
                    sched.run_once()
                except Exception:
                    crashes += 1
            assert crashes == 0
            assert not remote.watch_failed
            assert cache.breaker.state == "closed"  # recovered
            # every gang of every cycle got placed despite the faults
            assert _wait(lambda: all(
                p.node_name for p in store.list("pods", namespace="t")))
        finally:
            remote.close()
            server.stop()
