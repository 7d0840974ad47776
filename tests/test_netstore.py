"""Networked ClusterStore: codec, server/client RPC, watch streams, and the
vcctl-over-TCP e2e against a separately-constructed standalone process
(reference: cmd/cli/vcctl.go:44-49 CRUDs against the API server;
pkg/scheduler/cache/cache.go:319-402 watches it)."""

import os
import subprocess
import sys
import threading
import time

import pytest

from volcano_tpu.client import (
    AdmissionError, ClusterStore, ConflictError, DurableClusterStore,
    NotFoundError, RemoteClusterStore, StoreServer,
)
from volcano_tpu.client.codec import decode, encode
from volcano_tpu.models import (
    Job, JobPhase, Node, Pod, PodGroup, PodGroupCondition, PodGroupPhase,
    PodGroupSpec, Queue, QueueSpec,
)

from helpers import build_node, build_pod, build_pod_group, build_queue


class TestCodec:
    def test_pod_roundtrip(self):
        pod = build_pod("ns1", "p0", "n3", "Running",
                        {"cpu": "2", "memory": "4Gi"}, "pg1")
        pod.volumes = [{"name": "v", "persistentVolumeClaim":
                        {"claimName": "c1"}}]
        out = decode(encode(pod))
        assert isinstance(out, Pod)
        assert out.name == "p0" and out.node_name == "n3"
        assert out.containers == pod.containers
        assert out.volumes == pod.volumes
        assert out.creation_timestamp == pod.creation_timestamp

    def test_podgroup_enum_and_conditions_roundtrip(self):
        pg = build_pod_group("pg1", "ns1", min_member=3)
        pg.status.phase = PodGroupPhase.INQUEUE
        pg.status.conditions.append(PodGroupCondition(
            type="Scheduled", status="True", transition_id="t1"))
        out = decode(encode(pg))
        assert isinstance(out, PodGroup)
        assert out.status.phase is PodGroupPhase.INQUEUE  # real enum member
        assert out.spec.min_member == 3
        assert out.status.conditions[0].type == "Scheduled"

    def test_job_spec_roundtrip(self):
        job = Job(name="j", namespace="d")
        job.status.state.phase = JobPhase.RUNNING
        out = decode(encode(job))
        assert isinstance(out, Job)
        assert out.status.state.phase is JobPhase.RUNNING

    def test_secret_bytes_roundtrip(self):
        from volcano_tpu.models import Secret
        sec = Secret(name="s1", namespace="d",
                     data={"id_rsa": b"\x00private\xff",
                           "config": b"StrictHostKeyChecking no\n"})
        out = decode(encode(sec))
        assert isinstance(out, Secret)
        assert out.data["id_rsa"] == b"\x00private\xff"
        assert out.data["config"] == b"StrictHostKeyChecking no\n"

    def test_decode_rejects_unknown_class(self):
        with pytest.raises(ValueError):
            decode({"__t": "os.system", "f": {}})


@pytest.fixture()
def served_store():
    store = ClusterStore()
    server = StoreServer(store).start()
    try:
        yield store, RemoteClusterStore(server.address)
    finally:
        server.stop()


class TestRemoteCrud:
    def test_create_get_list_delete(self, served_store):
        store, remote = served_store
        remote.create("nodes", build_node("n1", {"cpu": "4",
                                                 "memory": "8Gi"}))
        assert store.get("nodes", "n1").name == "n1"  # landed server-side
        got = remote.get("nodes", "n1")
        assert isinstance(got, Node) and got.allocatable["cpu"] == "4"
        remote.create("pods", build_pod("ns1", "p1", "", "Pending",
                                        {"cpu": "1"}, "pg"))
        assert [p.name for p in remote.list("pods", namespace="ns1")] \
            == ["p1"]
        assert remote.list("pods", namespace="other") == []
        remote.delete("pods", "p1", "ns1")
        with pytest.raises(NotFoundError):
            remote.get("pods", "p1", "ns1")

    def test_conflict_propagates(self, served_store):
        store, remote = served_store
        remote.create("queues", build_queue("q1", weight=1))
        q = remote.get("queues", "q1")
        q2 = remote.get("queues", "q1")
        q.weight = 5
        remote.update("queues", q)
        q2.weight = 7  # stale resource_version now
        with pytest.raises(ConflictError):
            remote.update("queues", q2)
        with pytest.raises(ConflictError):
            remote.create("queues", build_queue("q1"))

    def test_admission_error_propagates(self, served_store):
        store, remote = served_store

        def deny(verb, kind, obj):
            if kind == "pods" and verb == "create":
                raise AdmissionError("no pods today")
            return obj

        store.add_interceptor(deny)
        with pytest.raises(AdmissionError, match="no pods today"):
            remote.create("pods", build_pod("ns1", "p1", "", "Pending",
                                            {"cpu": "1"}, "pg"))

    def test_remote_interceptors_rejected(self, served_store):
        _, remote = served_store
        with pytest.raises(NotImplementedError):
            remote.add_interceptor(lambda v, k, o: o)


class TestRemoteWatch:
    def test_replay_then_live_events(self, served_store):
        store, remote = served_store
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        events = []
        done = threading.Event()

        def listener(event, obj, old):
            events.append((event, obj.name,
                           old.name if old is not None else None))
            if len(events) >= 3:
                done.set()

        remote.watch("nodes", listener)  # replay applied inline
        assert events == [("add", "n1", None)]
        n2 = store.create("nodes", build_node("n2", {"cpu": "1"}))
        n2.unschedulable = True
        store.update("nodes", n2)
        assert done.wait(5.0)
        assert events[1] == ("add", "n2", None)
        assert events[2] == ("update", "n2", "n2")  # old travels too

    def test_dead_watcher_unsubscribes(self, served_store):
        store, remote = served_store
        # the server's EventJournal holds one permanent listener per kind;
        # measure the WATCHER's listener against that baseline
        base = len(store._listeners["nodes"])
        remote.watch("nodes", lambda *a: None)
        deadline = time.time() + 5
        while len(store._listeners["nodes"]) <= base \
                and time.time() < deadline:
            time.sleep(0.01)
        assert len(store._listeners["nodes"]) == base + 1
        remote.close()
        # the reader thread's socket closing makes the server's next
        # heartbeat/send fail and unwatch; force an event to flush it
        for i in range(3, 40):
            store.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
            if len(store._listeners["nodes"]) <= base:
                break
            time.sleep(0.1)
        assert len(store._listeners["nodes"]) == base


class TestRemoteScheduling:
    def test_remote_cache_schedules(self, served_store):
        """A SchedulerCache attached over TCP sees the same cluster and
        binds pods through the wire."""
        from volcano_tpu.cache import FakeEvictor, SchedulerCache
        from volcano_tpu.scheduler import Scheduler

        store, remote = served_store
        store.create("nodes", build_node("n1", {"cpu": "8",
                                                "memory": "16Gi"}))
        pg = build_pod_group("pg1", "ns1", min_member=2)
        store.create("podgroups", pg)
        for i in range(2):
            store.create("pods", build_pod("ns1", f"p{i}", "", "Pending",
                                           {"cpu": "1", "memory": "1Gi"},
                                           "pg1"))
        cache = SchedulerCache(remote)
        cache.evictor = FakeEvictor()
        cache.run()
        cache.wait_for_cache_sync()
        sched = Scheduler(cache)
        sched.run_once()
        cache.wait_for_effects()
        deadline = time.time() + 5
        while time.time() < deadline:
            pods = store.list("pods", namespace="ns1")
            if pods and all(p.node_name == "n1" for p in pods):
                break
            time.sleep(0.05)
        assert all(p.node_name == "n1"
                   for p in store.list("pods", namespace="ns1"))

        # a SECOND wave after the first bind's informer echo: the echoed
        # update's stale `old` must not corrupt the mirror (the cache
        # deletes by its own stored task, not the event copy)
        pg2 = build_pod_group("pg2", "ns1", min_member=2)
        store.create("podgroups", pg2)
        for i in range(2):
            store.create("pods", build_pod("ns1", f"q{i}", "", "Pending",
                                           {"cpu": "1", "memory": "1Gi"},
                                           "pg2"))
        time.sleep(0.3)  # let the watch deliver the new wave
        sched.run_once()
        cache.wait_for_effects()
        deadline = time.time() + 5
        while time.time() < deadline:
            pods = [p for p in store.list("pods", namespace="ns1")
                    if p.name.startswith("q")]
            if len(pods) == 2 and all(p.node_name for p in pods):
                break
            time.sleep(0.05)
        assert all(p.node_name == "n1" for p in pods), [
            (p.name, p.node_name) for p in pods]
        assert not remote.watch_failed


class TestVcctlOverTcpE2E:
    def test_submit_via_tcp_to_separate_process(self, tmp_path):
        """The VERDICT r3 'done' bar: a job submitted with TCP vcctl to a
        separately-constructed standalone process gets scheduled there."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        port = _free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "volcano_tpu.standalone",
             "--serve-store", f"127.0.0.1:{port}",
             "--metrics-port", "0", "--period", "0.2"],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            remote = _connect_with_retry(f"127.0.0.1:{port}", proc)
            remote.create("nodes", Node(
                name="n1", allocatable={"cpu": "8", "memory": "16Gi"},
                capacity={"cpu": "8", "memory": "16Gi"}))

            yaml_path = tmp_path / "job.yaml"
            yaml_path.write_text("""
apiVersion: batch.volcano.sh/v1alpha1
kind: Job
metadata: {name: net-job, namespace: default}
spec:
  minAvailable: 2
  tasks:
    - replicas: 2
      name: worker
      template:
        spec:
          containers:
            - name: main
              image: busybox
              resources: {requests: {cpu: "1", memory: 1Gi}}
""")
            out = subprocess.run(
                [sys.executable, "-m", "volcano_tpu.cli",
                 "--server", f"127.0.0.1:{port}",
                 "job", "run", "-f", str(yaml_path)],
                env=env, capture_output=True, text=True, timeout=120,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            assert "successfully" in out.stdout, (out.stdout, out.stderr)

            deadline = time.time() + 90
            bound = []
            while time.time() < deadline:
                pods = remote.list("pods", namespace="default")
                bound = [p for p in pods if p.node_name]
                if len(bound) == 2:
                    break
                time.sleep(0.3)
            assert len(bound) == 2, [
                (p.name, p.node_name, p.phase)
                for p in remote.list("pods", namespace="default")]

            # and the CLI can read it back over the wire
            out = subprocess.run(
                [sys.executable, "-m", "volcano_tpu.cli",
                 "--server", f"127.0.0.1:{port}", "job", "list"],
                env=env, capture_output=True, text=True, timeout=60,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            assert "net-job" in out.stdout

            # multi-doc apply over the wire too
            q_yaml = tmp_path / "q.yaml"
            q_yaml.write_text(
                "kind: Queue\nmetadata: {name: wire-q}\n"
                "spec: {weight: 3}\n"
                "---\n"
                "kind: PodGroup\n"
                "metadata: {name: wire-pg, namespace: default}\n"
                "spec: {minMember: 2}\n")
            out = subprocess.run(
                [sys.executable, "-m", "volcano_tpu.cli",
                 "--server", f"127.0.0.1:{port}",
                 "apply", "-f", str(q_yaml)],
                env=env, capture_output=True, text=True, timeout=60,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
            assert "queue/wire-q" in out.stdout, (out.stdout, out.stderr)
            assert "podgroup/wire-pg" in out.stdout
            assert remote.get("queues", "wire-q").spec.weight == 3
            pg = remote.get("podgroups", "wire-pg", "default")
            assert pg.spec.min_member == 2 and pg.spec.queue == "default"
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _connect_with_retry(address: str, proc,
                        timeout: float = 120.0) -> RemoteClusterStore:
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                f"standalone exited rc={proc.returncode}:\n"
                f"{proc.stdout.read() if proc.stdout else ''}")
        try:
            remote = RemoteClusterStore(address, connect_timeout=2.0)
            remote.ping()
            return remote
        except OSError as e:
            last = e
            time.sleep(0.5)
    raise AssertionError(f"could not reach standalone store: {last}")


class TestStoreAuth:
    """Shared-token auth on the store server: wrong/missing token is
    refused before any op can touch the store; the right token works
    end to end (the manifest requires this for non-loopback binds)."""

    def test_token_required_and_accepted(self):
        store = ClusterStore()
        server = StoreServer(store, token="s3cret").start()
        try:
            good = RemoteClusterStore(server.address, token="s3cret")
            good.create("nodes", build_node("n1", {"cpu": "1"}))
            assert store.get("nodes", "n1").name == "n1"

            for bad_token in ("", "wrong"):
                bad = RemoteClusterStore(server.address, token=bad_token)
                with pytest.raises((RuntimeError, ConnectionError,
                                    OSError)):
                    bad.list("nodes")
            assert len(store.list("nodes")) == 1
        finally:
            server.stop()

    def test_tokenless_server_ignores_auth(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        try:
            remote = RemoteClusterStore(server.address, token="whatever")
            assert remote.ping()
        finally:
            server.stop()


class TestWatchFailureCallback:
    def test_server_death_triggers_callback_once(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        fired = []
        # short resume window: the server is gone for good, so the
        # crash-only fallback must fire once the reconnect attempts
        # exhaust (tests/test_resilience.py covers the resume side)
        remote = RemoteClusterStore(server.address, token="",
                                    watch_resume_window_s=1.0,
                                    on_watch_failure=lambda:
                                    fired.append(1))
        remote.watch("nodes", lambda *a: None)
        remote.watch("pods", lambda *a: None)
        server.stop()  # kills the streams
        deadline = time.time() + 10
        while not fired and time.time() < deadline:
            time.sleep(0.05)
        time.sleep(0.3)  # the second stream's failure must not re-fire
        assert fired == [1]
        assert remote.watch_failed

    def test_clean_close_does_not_fire(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        fired = []
        remote = RemoteClusterStore(server.address, token="",
                                    on_watch_failure=lambda:
                                    fired.append(1))
        remote.watch("nodes", lambda *a: None)
        remote.close()
        time.sleep(0.3)
        assert fired == [] and not remote.watch_failed
        server.stop()

    def test_unknown_watch_kind_refused_without_leak(self):
        store = ClusterStore()
        server = StoreServer(store).start()
        try:
            import socket as socket_mod
            from volcano_tpu.client.server import (
                MAGIC, recv_frame, send_frame,
            )
            sock = socket_mod.create_connection(
                (server.host, server.port), timeout=5)
            sock.sendall(MAGIC)
            send_frame(sock, {"op": "watch",
                              "kinds": ["pods", "bogus"]})
            resp = recv_frame(sock)
            assert resp["ok"] is False and "bogus" in resp["message"]
            sock.close()
            # nothing stayed subscribed beyond the journal's listener
            assert store._listeners["pods"] \
                == [dict(server.journal._listeners)["pods"]]
        finally:
            server.stop()


class TestStoreTLS:
    """TLS on the store protocol (the reference's equivalent seam — the
    k8s API server — is always TLS): a cert-verifying client round-trips
    CRUD and watch; a client pinning the wrong CA refuses the server; a
    plaintext client cannot talk to a TLS server."""

    @pytest.fixture()
    def certs(self, tmp_path):
        # cert generation needs pyca/cryptography, which the runtime
        # image may not carry — TLS coverage skips cleanly there
        pytest.importorskip("cryptography")
        from volcano_tpu.webhooks.server import generate_self_signed_cert
        cert, key = generate_self_signed_cert(str(tmp_path / "a"))
        cert2, key2 = generate_self_signed_cert(str(tmp_path / "b"))
        return cert, key, cert2

    def test_tls_crud_and_watch_roundtrip(self, certs):
        cert, key, _ = certs
        store = ClusterStore()
        server = StoreServer(store, token="t0k",
                             tls_cert=cert, tls_key=key).start()
        try:
            remote = RemoteClusterStore(server.address, token="t0k",
                                        tls_ca=cert)
            remote.create("nodes", build_node("n1", {"cpu": "1"}))
            assert store.get("nodes", "n1").name == "n1"
            seen = []
            remote.watch("nodes", lambda ev, obj, old:
                         seen.append((ev, obj.name)))
            assert seen == [("add", "n1")]  # replay over TLS
            store.create("nodes", build_node("n2", {"cpu": "1"}))
            deadline = time.time() + 5
            while len(seen) < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert ("add", "n2") in seen  # live event over TLS
        finally:
            server.stop()

    def test_wrong_ca_refused(self, certs):
        cert, key, other_cert = certs
        store = ClusterStore()
        server = StoreServer(store, tls_cert=cert, tls_key=key).start()
        try:
            bad = RemoteClusterStore(server.address, tls_ca=other_cert)
            with pytest.raises((ConnectionError, OSError)):
                bad.ping()
        finally:
            server.stop()

    def test_plaintext_client_rejected_by_tls_server(self, certs):
        cert, key, _ = certs
        store = ClusterStore()
        server = StoreServer(store, tls_cert=cert, tls_key=key).start()
        try:
            plain = RemoteClusterStore(server.address)
            with pytest.raises((RuntimeError, ConnectionError, OSError)):
                plain.ping()
            assert store.list("nodes") == []
        finally:
            server.stop()


class TestSlowWatcher:
    def test_overflowing_watcher_is_dropped_not_buffered(self, monkeypatch):
        """A watcher that never reads must be disconnected once its event
        queue overflows, instead of growing server memory without bound;
        the store itself keeps serving and other listeners are unaffected."""
        import socket as socket_mod

        from volcano_tpu.client import server as srv

        monkeypatch.setattr(srv, "WATCH_QUEUE_MAX", 8)
        # the writer only notices the stall when its blocked sendall hits
        # the send timeout; the production 30s exceeds this test's budget
        monkeypatch.setattr(srv, "WATCH_SEND_TIMEOUT_S", 1.0)
        from volcano_tpu.metrics import metrics

        dropped_before = metrics.store_watch_dropped_total.get()
        store = ClusterStore()
        server = StoreServer(store).start()
        try:
            sock = socket_mod.create_connection(
                (server.host, server.port), timeout=5)
            sock.sendall(srv.MAGIC)
            srv.send_frame(sock, {"op": "watch", "kinds": ["nodes"],
                                  "replay": False})
            # never read from sock; flood events until the bounded queue
            # condemns the watcher and its listener unsubscribes (the
            # journal's own per-kind listener stays, by design)
            base = 1  # the journal's listener
            # wait for the handler to actually subscribe first — flooding
            # before that point exits the loop vacuously (listeners never
            # exceeded base) and nothing was ever dropped
            deadline = time.time() + 10
            while len(store._listeners["nodes"]) <= base \
                    and time.time() < deadline:
                time.sleep(0.005)
            assert len(store._listeners["nodes"]) == base + 1
            deadline = time.time() + 10
            i = 0
            # 64 labels a node: each event frame is a few KB, so the
            # server's send buffer fills in hundreds of events
            labels = {f"pad-{k}": "x" * 63 for k in range(64)}
            while len(store._listeners["nodes"]) > base \
                    and time.time() < deadline:
                store.apply("nodes", build_node(f"n{i % 40}",
                                                {"cpu": "1"}, labels))
                i += 1
                time.sleep(0.001)
            assert len(store._listeners["nodes"]) == base, \
                "slow watcher was never dropped"
            # the drop is no longer log-only: it is exported
            deadline = time.time() + 5
            while metrics.store_watch_dropped_total.get() \
                    <= dropped_before and time.time() < deadline:
                time.sleep(0.02)
            assert metrics.store_watch_dropped_total.get() \
                > dropped_before
            sock.close()
        finally:
            server.stop()


class TestWAL:
    """WAL edge cases: torn-tail truncation, fsync policies, framing."""

    def _fill(self, d, n=5):
        store = DurableClusterStore(str(d))
        for i in range(n):
            store.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
        store.close()
        return store

    def test_torn_final_record_truncated(self, tmp_path):
        from volcano_tpu.client.durable import read_frames
        store = self._fill(tmp_path, n=5)
        seg = [p for p in os.listdir(tmp_path) if p.startswith("wal-")]
        assert len(seg) == 1
        path = str(tmp_path / seg[0])
        good_size = os.path.getsize(path)
        # a crash mid-append: half a record's worth of debris at the tail
        with open(path, "ab") as f:
            f.write(b"\xff\x00\x00\x00garbage-that-is-not-a-frame")
        records, valid, torn = read_frames(path)
        assert torn and len(records) == 5 and valid == good_size
        s2 = DurableClusterStore(str(tmp_path))
        assert sorted(n.name for n in s2.list("nodes")) \
            == [f"n{i}" for i in range(5)]
        assert s2._rv == store._rv  # rv counter restored exactly
        assert os.path.getsize(path) == good_size  # debris cut off
        # appends after recovery land on a clean frame boundary
        s2.create("nodes", build_node("post", {"cpu": "1"}))
        s2.close()
        s3 = DurableClusterStore(str(tmp_path))
        assert s3.try_get("nodes", "post") is not None

    def test_corrupt_crc_truncates_from_there(self, tmp_path):
        self._fill(tmp_path, n=4)
        seg = [p for p in os.listdir(tmp_path) if p.startswith("wal-")]
        path = str(tmp_path / seg[0])
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) - 3] ^= 0xFF  # flip a byte inside the LAST record
        open(path, "wb").write(raw)
        s2 = DurableClusterStore(str(tmp_path))
        # the first three records survive; the corrupted final one is gone
        assert sorted(n.name for n in s2.list("nodes")) \
            == ["n0", "n1", "n2"]

    def test_fsync_policies(self, tmp_path):
        s_every = DurableClusterStore(str(tmp_path / "every"),
                                      fsync="every")
        for i in range(4):
            s_every.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
        assert s_every.wal.fsyncs == 4  # one per commit

        s_int = DurableClusterStore(str(tmp_path / "interval"),
                                    fsync="interval",
                                    fsync_interval_s=3600.0)
        for i in range(4):
            s_int.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
        assert s_int.wal.fsyncs <= 1  # group commit: the window absorbs

        s_off = DurableClusterStore(str(tmp_path / "off"), fsync="off")
        for i in range(4):
            s_off.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
        assert s_off.wal.fsyncs == 0
        # flushed-but-not-fsynced records still survive a PROCESS death
        # (the bytes are in the OS): a fresh recovery sees them
        s2 = DurableClusterStore(str(tmp_path / "off"))
        assert len(s2.list("nodes")) == 4

    def test_wal_fsync_fault_point_fires(self, tmp_path):
        from volcano_tpu.resilience import faults
        faults.reset()
        try:
            faults.arm("wal_fsync", every=1, exc=None)
            store = DurableClusterStore(str(tmp_path), fsync="every")
            store.create("nodes", build_node("n0", {"cpu": "1"}))
            assert faults.fired("wal_fsync") >= 1
        finally:
            faults.reset()

    def test_store_crash_point_sits_between_append_and_announce(
            self, tmp_path):
        from volcano_tpu.resilience import faults
        faults.reset()
        try:
            seen = []
            store = DurableClusterStore(str(tmp_path))
            store.watch("nodes", lambda ev, obj, old:
                        seen.append(obj.name), replay=False)
            faults.arm_once("store_crash")
            with pytest.raises(ConnectionError):
                store.create("nodes", build_node("n0", {"cpu": "1"}))
            # the record IS durable (the crash seam is after the append)
            # but no listener ever heard the commit announced
            assert seen == []
            assert store.wal.appends == 1
        finally:
            faults.reset()


class TestDurableRecovery:
    def test_full_state_roundtrip_with_rv_counters(self, tmp_path):
        s1 = DurableClusterStore(str(tmp_path))
        s1.create("queues", build_queue("q1", weight=3))
        n = s1.create("nodes", build_node("n1", {"cpu": "4"}))
        n.unschedulable = True
        s1.update("nodes", n)
        s1.create("pods", build_pod("ns", "p1", "", "Pending",
                                    {"cpu": "1"}, "pg"))
        s1.delete("pods", "p1", "ns")
        s1.create("podgroups", build_pod_group("pg1", "ns", min_member=2))
        s2 = DurableClusterStore(str(tmp_path))
        assert s2._rv == s1._rv
        assert s2._kind_rv == s1._kind_rv
        assert s2.get("nodes", "n1").unschedulable is True
        assert s2.get("nodes", "n1").resource_version \
            == s1.get("nodes", "n1").resource_version
        assert s2.list("pods") == []  # the delete replayed too
        assert s2.get("podgroups", "pg1", "ns").spec.min_member == 2
        assert s2.recovered_records == 6

    def test_corrupt_snapshot_falls_back_to_previous_plus_wal(
            self, tmp_path):
        s1 = DurableClusterStore(str(tmp_path))
        for i in range(3):
            s1.create("nodes", build_node(f"a{i}", {"cpu": "1"}))
        s1.snapshot()
        for i in range(3):
            s1.create("nodes", build_node(f"b{i}", {"cpu": "1"}))
        s1.snapshot()
        s1.create("nodes", build_node("tail", {"cpu": "1"}))
        s1.close()
        snaps = sorted(p for p in os.listdir(tmp_path)
                       if p.startswith("snapshot-"))
        assert len(snaps) == 2
        newest = str(tmp_path / snaps[-1])
        raw = bytearray(open(newest, "rb").read())
        raw[20] ^= 0xFF
        open(newest, "wb").write(raw)
        s2 = DurableClusterStore(str(tmp_path))
        assert s2.snapshot_fallbacks == 1
        assert sorted(n.name for n in s2.list("nodes")) \
            == sorted(["a0", "a1", "a2", "b0", "b1", "b2", "tail"])
        assert s2._rv == s1._rv

    def test_snapshot_compaction_prunes_and_recovers(self, tmp_path):
        s1 = DurableClusterStore(str(tmp_path), snapshot_every=4)
        for i in range(11):  # crosses the threshold twice
            s1.create("nodes", build_node(f"n{i}", {"cpu": "1"}))
        s1.close()
        snaps = [p for p in os.listdir(tmp_path)
                 if p.startswith("snapshot-")]
        assert len(snaps) == 2  # keep_snapshots caps retention
        s2 = DurableClusterStore(str(tmp_path))
        assert len(s2.list("nodes")) == 11
        assert s2._rv == s1._rv

    def test_watch_resumes_across_store_restart(self, tmp_path):
        """The tentpole seam: a watcher mid-stream when the store dies
        resumes over the restart via ``since:`` — the events it missed
        (committed while it was disconnected) replay from the journal
        seeded out of the recovered WAL tail. No crash-only resync."""
        s1 = DurableClusterStore(str(tmp_path))
        server = StoreServer(s1)
        server.start()
        port = server.port
        fired = []
        remote = RemoteClusterStore(server.address,
                                    watch_backoff_cap_s=0.3,
                                    on_watch_failure=lambda:
                                    fired.append(1))
        seen = []
        remote.watch("nodes", lambda ev, obj, old:
                     seen.append((ev, obj.name)))
        s1.create("nodes", build_node("n1", {"cpu": "1"}))
        deadline = time.time() + 5
        while len(seen) < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert seen == [("add", "n1")]
        # the server dies; MORE writes commit before the crash finishes
        # taking the store down (the watcher never hears them live)
        server.stop()
        s1.create("nodes", build_node("n2", {"cpu": "1"}))
        n2 = s1.get("nodes", "n2")
        n2.unschedulable = True
        s1.update("nodes", n2)
        del s1  # crash: no clean close
        s2 = DurableClusterStore(str(tmp_path))
        server2 = StoreServer(s2, port=port).start()
        try:
            deadline = time.time() + 10
            while len(seen) < 3 and time.time() < deadline:
                time.sleep(0.02)
            assert seen == [("add", "n1"), ("add", "n2"),
                            ("update", "n2")]
            assert remote.watch_resumes == 1
            assert not remote.watch_failed and fired == []
            # and the stream is LIVE again after the replay
            s2.create("nodes", build_node("n3", {"cpu": "1"}))
            deadline = time.time() + 5
            while len(seen) < 4 and time.time() < deadline:
                time.sleep(0.02)
            assert seen[-1] == ("add", "n3")
        finally:
            remote.close()
            server2.stop()

    def test_in_memory_default_untouched(self, tmp_path):
        """No --store-data-dir => no WAL I/O: the plain store has no
        journaling seam engaged and writes nothing to disk."""
        store = ClusterStore()
        assert not hasattr(store, "_wal")
        before = set(os.listdir(tmp_path))
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        store.bulk_apply([("nodes", build_node("n2", {"cpu": "1"}))])
        assert set(os.listdir(tmp_path)) == before


class TestBulkApply:
    def test_in_memory_mixed_verbs_and_containment(self):
        store = ClusterStore()

        def deny(verb, kind, obj):
            if kind == "pods" and obj.name == "bad":
                raise AdmissionError("denied")
            return obj

        store.add_interceptor(deny)
        store.create("nodes", build_node("n1", {"cpu": "1"}))
        results = store.bulk_apply([
            ("pods", build_pod("ns", "p1", "", "Pending",
                               {"cpu": "1"}, "pg"), "create"),
            ("pods", build_pod("ns", "bad", "", "Pending",
                               {"cpu": "1"}, "pg"), "create"),
            ("pods", build_pod("ns", "p2", "", "Pending",
                               {"cpu": "1"}, "pg"), "create"),
            ("nodes", build_node("n1", {"cpu": "2"}), "apply"),
        ])
        assert [type(r).__name__ for r in results] \
            == ["Pod", "AdmissionError", "Pod", "Node"]
        # the denied pod cost that pod, not the wave
        assert sorted(p.name for p in store.list("pods")) == ["p1", "p2"]
        assert store.get("nodes", "n1").allocatable["cpu"] == "2"
        # duplicate create surfaces per-item too
        results = store.bulk_apply([
            ("pods", build_pod("ns", "p1", "", "Pending",
                               {"cpu": "1"}, "pg"), "create")])
        assert isinstance(results[0], ConflictError)

    def test_over_the_wire_one_frame(self, served_store):
        store, remote = served_store
        results = remote.bulk_apply(
            [("nodes", build_node(f"n{i}", {"cpu": "1"}))
             for i in range(10)]
            + [("pods", build_pod("ns", "p0", "", "Pending",
                                  {"cpu": "1"}, "pg"), "create")])
        assert all(not isinstance(r, Exception) for r in results)
        assert len(store.list("nodes")) == 10
        # per-item errors come back as rebuilt exception instances
        results = remote.bulk_apply(
            [("pods", build_pod("ns", "p0", "", "Pending",
                                {"cpu": "1"}, "pg"), "create"),
             ("nodes", build_node("n0", {"cpu": "4"}))])
        assert isinstance(results[0], ConflictError)
        assert results[1].allocatable["cpu"] == "4"

    def test_one_journal_batch_one_fsync(self, tmp_path):
        store = DurableClusterStore(str(tmp_path), fsync="every")
        base_syncs = store.wal.fsyncs
        store.bulk_apply([("nodes", build_node(f"n{i}", {"cpu": "1"}))
                          for i in range(16)])
        assert store.wal.appends == 16
        assert store.wal.fsyncs == base_syncs + 1  # ONE sync per batch
        # and everything in the batch is durable
        s2 = DurableClusterStore(str(tmp_path))
        assert len(s2.list("nodes")) == 16


class TestJobControllerBulkIngest:
    def test_wave_created_in_one_batch(self, monkeypatch):
        from volcano_tpu.controllers import ControllerManager
        from volcano_tpu.models import Job, JobSpec, PodGroupPhase, TaskSpec

        store = ClusterStore()
        calls = []
        orig = ClusterStore.bulk_apply

        def spy(self, items, fencing=None):
            items = list(items)
            calls.append(len(items))
            return orig(self, items, fencing=fencing)

        monkeypatch.setattr(ClusterStore, "bulk_apply", spy)
        cm = ControllerManager(store)
        cm.run()
        store.create("jobs", Job(
            name="bulkjob", namespace="default",
            spec=JobSpec(min_available=3, tasks=[TaskSpec(
                name="task", replicas=3, template={
                    "spec": {"containers": [{"name": "c", "requests":
                             {"cpu": "1", "memory": "1Gi"}}]}})])))
        cm.process_all()
        pg = store.get("podgroups", "bulkjob", "default")
        pg.status.phase = PodGroupPhase.INQUEUE
        store.update("podgroups", pg)
        cm.process_all()
        assert sorted(p.name for p in store.list("pods")) \
            == ["bulkjob-task-0", "bulkjob-task-1", "bulkjob-task-2"]
        assert 3 in calls  # the whole wave went through ONE batch


@pytest.mark.slow
class TestStoreCrashSoak:
    def test_kill9_recovery_trace_identical_to_golden(self, tmp_path):
        """The acceptance bar: SIGKILL the durable store process with a
        wave's pods committed but unbound, restart it on the same port +
        data dir, and the scheduler + controllers ride through — decision
        trace bind-for-bind identical to the uninterrupted golden run,
        zero lost/dup binds, every watcher resumed via ``since:`` (no
        crash-only resync)."""
        from durable_soak import run_store_crash_soak

        golden = run_store_crash_soak(str(tmp_path / "golden"), waves=6)
        crash = run_store_crash_soak(str(tmp_path / "crash"), waves=6,
                                     kill_at_wave=3)
        assert golden["crashes"] == 0 and golden["stalls"] == []
        assert crash["crashes"] == 0 and crash["stalls"] == []
        assert crash["restart_s"] is not None
        assert crash["binds_by_wave"] == golden["binds_by_wave"]
        assert crash["total_binds"] == 6 * 2 * 3
        assert crash["dup_binds"] == 0 and crash["lost_binds"] == 0
        assert crash["watch_resumes"] > 0
        assert not crash["watch_failed"]
        assert crash["crash_only_resyncs"] == 0


class TestVcctlTLSFlags:
    def test_vcctl_applies_over_tls_with_flags(self, tmp_path):
        """vcctl --server --token --tls-ca drives a TLS-served store
        (the deployed-control-plane path with encryption on)."""
        pytest.importorskip("cryptography")
        from volcano_tpu.cli.vcctl import main as vcctl
        from volcano_tpu.webhooks.server import generate_self_signed_cert

        cert, key = generate_self_signed_cert(str(tmp_path))
        store = ClusterStore()
        server = StoreServer(store, token="t0k",
                             tls_cert=cert, tls_key=key).start()
        try:
            qy = tmp_path / "q.yaml"
            qy.write_text(
                "apiVersion: scheduling.volcano.sh/v1beta1\n"
                "kind: Queue\n"
                "metadata: {name: tls-q}\n"
                "spec: {weight: 3}\n")
            out = vcctl(["--server", server.address, "--token", "t0k",
                         "--tls-ca", cert, "apply", "-f", str(qy)])
            assert "queue/tls-q" in out
            assert store.get("queues", "tls-q").spec.weight == 3
        finally:
            server.stop()
