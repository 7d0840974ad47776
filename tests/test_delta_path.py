"""Delta-native data path (columnar watch frames straight into the
packed arrays): negotiation, patch application, and the typed fallback
ladder. The heavyweight acceptance piece — the 40-cycle two-arm churn
matrix asserting delta and object arms byte-identical in mirror
content, packed arrays and scheduler decisions — lives in
``test_wire_delta.py`` (which shares this module's fixture/helpers)."""

import copy

import pytest

from volcano_tpu.client import ClusterStore, RemoteClusterStore, StoreServer
from volcano_tpu.resilience import faults

from helpers import build_pod


@pytest.fixture()
def served():
    store = ClusterStore()
    server = StoreServer(store).start()
    clients = []

    def client(**kw):
        c = RemoteClusterStore(server.address, **kw)
        clients.append(c)
        return c

    try:
        yield store, server, client
    finally:
        faults.reset()
        for c in clients:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        server.stop()


def pod_mirror(client, **kw):
    """A dict mirror of the pods stream: key -> pod, plus an event log of
    (event, phase) for exactly-once assertions."""
    m, log = {}, []

    def on_pod(event, obj, old):
        key = f"{obj.namespace}/{obj.name}"
        log.append((event, obj.phase))
        if event == "delete":
            m.pop(key, None)
        else:
            m[key] = obj
    client.watch("pods", on_pod)
    return m, log


def wait_applied(client, store, kind="pods", timeout=30.0):
    assert client.wait_stream_applied(kind, store._rv, timeout=timeout)


class TestNegotiation:
    def test_patch_flow_and_mirror_parity(self, served):
        store, server, client = served
        dc = client(delta_watch=True)
        oc = client()
        dm, _ = pod_mirror(dc)
        om, _ = pod_mirror(oc)
        for i in range(10):
            store.create("pods", build_pod(
                "d", f"p{i}", "", "Pending", {"cpu": "1"}, "g"))
        for f, phase in enumerate(("Running", "Succeeded")):
            for i in range(10):
                cur = copy.deepcopy(store.get("pods", f"p{i}",
                                              namespace="d"))
                cur.phase = phase
                cur.node_name = f"n{f}"
                store.update("pods", cur)
        wait_applied(dc, store)
        wait_applied(oc, store)
        assert set(dm) == set(om) and len(dm) == 10
        for k in om:
            assert dm[k].phase == om[k].phase == "Succeeded"
            assert dm[k].node_name == om[k].node_name == "n1"
            assert dm[k].resource_version == om[k].resource_version
        st = dc.delta_stats
        assert st["events"] == 20 and not st["fallbacks"]
        assert st["fields"] >= 40  # phase + node_name (+ rv) per update
        assert oc.delta_stats["events"] == 0

    def test_fail_safe_default_is_object_frames(self, served):
        store, server, client = served
        oc = client()  # no delta_watch: must never see delta machinery
        om, _ = pod_mirror(oc)
        store.create("pods", build_pod("d", "p0", "", "Pending",
                                       {"cpu": "1"}, "g"))
        cur = copy.deepcopy(store.get("pods", "p0", namespace="d"))
        cur.phase = "Running"
        store.update("pods", cur)
        wait_applied(oc, store)
        st = oc.delta_stats
        assert om["d/p0"].phase == "Running"
        assert st["frames"] == 0 and st["events"] == 0
        assert st["bytes_delta"] == 0 and st["bytes_object"] > 0

    def test_server_without_encoder_declines(self, served):
        store, server, client = served
        del server._server.delta_enc  # an old server: no delta support
        dc = client(delta_watch=True)
        dm, _ = pod_mirror(dc)
        store.create("pods", build_pod("d", "p0", "", "Pending",
                                       {"cpu": "1"}, "g"))
        cur = copy.deepcopy(store.get("pods", "p0", namespace="d"))
        cur.phase = "Running"
        store.update("pods", cur)
        wait_applied(dc, store)
        st = dc.delta_stats
        assert dm["d/p0"].phase == "Running"
        assert st["events"] == 0 and not st["fallbacks"]  # clean decline


def _flip_thrice(store):
    """Three single-field updates against pod d/p0 — the fault-ladder
    shape: each phase must reach a mirror exactly once."""
    for phase in ("Running", "Succeeded", "Failed"):
        cur = copy.deepcopy(store.get("pods", "p0", namespace="d"))
        cur.phase = phase
        store.update("pods", cur)


class TestFallbackLadder:
    def _run_ladder(self, served, point):
        store, server, client = served
        dc = client(delta_watch=True)
        oc = client()
        dm, dlog = pod_mirror(dc)
        om, olog = pod_mirror(oc)
        store.create("pods", build_pod("d", "p0", "", "Pending",
                                       {"cpu": "1"}, "g"))
        wait_applied(dc, store)
        faults.arm_once(point)
        _flip_thrice(store)
        wait_applied(dc, store)
        wait_applied(oc, store)
        # zero lost, zero duplicated: every phase exactly once, both arms
        updates = [p for e, p in dlog if e == "update"]
        assert updates == ["Running", "Succeeded", "Failed"]
        assert updates == [p for e, p in olog if e == "update"]
        assert dm["d/p0"].phase == om["d/p0"].phase == "Failed"
        return dc

    def test_dropped_frame_recovers_via_object_path(self, served):
        dc = self._run_ladder(served, "delta_frame")
        assert dc.delta_stats["fallbacks"] == {"delta_gap": 1}

    def test_duplicated_frame_recovers_via_object_path(self, served):
        dc = self._run_ladder(served, "delta_frame_dup")
        assert dc.delta_stats["fallbacks"] == {"delta_gap": 1}

    def test_vocab_overflow_falls_back_typed(self, served):
        store, server, client = served
        dc = client(delta_watch=True)
        dc.delta_vocab_max = 3  # tiny table: the first adds overflow it
        dm, _ = pod_mirror(dc)
        store.create("pods", build_pod("d", "p0", "", "Pending",
                                       {"cpu": "1"}, "g"))
        _flip_thrice(store)
        wait_applied(dc, store)
        assert dm["d/p0"].phase == "Failed"
        assert dc.delta_stats["fallbacks"].get("vocab_overflow", 0) >= 1

    def test_unknown_field_falls_back_typed(self, served, monkeypatch):
        from volcano_tpu.client import remote as remote_mod
        monkeypatch.setattr(remote_mod, "known_fields",
                            lambda cls: frozenset())
        store, server, client = served
        dc = client(delta_watch=True)
        dm, _ = pod_mirror(dc)
        store.create("pods", build_pod("d", "p0", "", "Pending",
                                       {"cpu": "1"}, "g"))
        _flip_thrice(store)
        wait_applied(dc, store)
        assert dm["d/p0"].phase == "Failed"
        assert dc.delta_stats["fallbacks"] == {"unknown_field": 1}
