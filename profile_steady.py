"""Profile the steady-state cycle (10k running pods, 100-pod waves).

Scratch tool for the round-4 host-path work; not part of the suite. It
runs on whatever platform JAX finds where it is started (the chip, or the
CPU with JAX_PLATFORMS=cpu).
Run: python profile_steady.py [--cprofile]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "tests"))

import numpy as np

from helpers import build_node, build_pod, build_pod_group, build_queue
from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
from volcano_tpu.client import ClusterStore
from volcano_tpu.models import PodGroupPhase
from volcano_tpu.scheduler import Scheduler

n_nodes, n_jobs, tpj = 2000, 1000, 10


def make_wave(store, k):
    pg = build_pod_group(f"j{k}", "bench", min_member=tpj, queue=f"q{k % 3}")
    pg.status.phase = PodGroupPhase.PENDING
    store.create("podgroups", pg)
    for i in range(tpj):
        store.create("pods", build_pod(
            "bench", f"j{k}-{i}", "", "Pending",
            {"cpu": str(1 + k % 3), "memory": f"{1 + k % 4}Gi"}, f"j{k}"))


def main():
    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder = FakeBinder()
    cache.evictor = FakeEvictor()
    cache.run()
    for i in range(3):
        store.apply("queues", build_queue(f"q{i}", weight=i + 1))
    for i in range(n_nodes):
        store.create("nodes", build_node(
            f"n{i}", {"cpu": "32", "memory": "128Gi"}))
    for k in range(n_jobs):
        make_wave(store, k)
    sched = Scheduler(cache)
    sched.run_once()  # the burst: now 10k running

    wave = n_jobs
    for w in range(20):
        make_wave(store, wave)
        wave += 1
        if w % 10 == 9:
            sched.run_once()

    if "--cprofile" in sys.argv:
        import cProfile
        import pstats
        pr = cProfile.Profile()
        for s in range(8):
            for w in range(10):
                make_wave(store, wave)
                wave += 1
            pr.enable()
            sched.run_once()
            pr.disable()
        st = pstats.Stats(pr)
        st.sort_stats("cumulative").print_stats(50)
        st.sort_stats("tottime").print_stats(30)
        print("timing", {k: (round(v, 2) if isinstance(v, float) else v)
                         for k, v in sched.last_cycle_timing.items()})
        return

    lats, host, flat_modes, order_modes = [], [], [], []
    patch_ms, full_ms = [], []
    order_ev_ms, order_full_ms = [], []
    for s in range(8):
        for w in range(10):
            make_wave(store, wave)
            wave += 1
        t0 = time.perf_counter()
        sched.run_once()
        lats.append((time.perf_counter() - t0) * 1e3)
        t = sched.last_cycle_timing
        host.append(t["total_ms"] - t.get("solve_ms", 0.0))
        # event-sourced flatten trace: which assembly mode each cycle
        # took and the patch-vs-full flatten latency split (BENCH_r0x
        # artifacts track these series)
        flat_modes.append((t.get("flatten_mode", "?"),
                           int(t.get("flatten_rows_patched", 0)),
                           int(t.get("flatten_events_applied", 0)),
                           t.get("flatten_fallback_reason", "")))
        if "flatten_patch_ms" in t:
            patch_ms.append(t["flatten_patch_ms"])
        if "flatten_full_ms" in t:
            full_ms.append(t["flatten_full_ms"])
        # event-sourced ordering trace: the ordering pass's mode, how
        # many job entries it patched, and its ms split next to the
        # flatten's (event path vs full-sort fallback)
        order_modes.append((t.get("order_mode", "?"),
                            int(t.get("order_entries_patched", 0)),
                            t.get("order_fallback_reason", "")))
        if t.get("order_mode") in ("reuse", "event"):
            order_ev_ms.append(t.get("order_ms", 0.0))
        elif "order_ms" in t:
            order_full_ms.append(t["order_ms"])
        sched._maybe_gc()
    print("steady p50", round(float(np.percentile(lats, 50)), 2),
          "host p50", round(float(np.percentile(host, 50)), 2))
    print("flatten modes (mode, rows, events, fallback):", flat_modes)
    print("flatten patch ms", [round(x, 2) for x in patch_ms],
          "p50", round(float(np.percentile(patch_ms, 50)), 2)
          if patch_ms else None)
    print("flatten full ms", [round(x, 2) for x in full_ms],
          "p50", round(float(np.percentile(full_ms, 50)), 2)
          if full_ms else None)
    print("order modes (mode, patched, fallback):", order_modes)
    print("order event ms", [round(x, 2) for x in order_ev_ms],
          "p50", round(float(np.percentile(order_ev_ms, 50)), 2)
          if order_ev_ms else None)
    print("order full ms", [round(x, 2) for x in order_full_ms],
          "p50", round(float(np.percentile(order_full_ms, 50)), 2)
          if order_full_ms else None)
    print("timing", {k: (round(v, 2) if isinstance(v, float) else v)
                     for k, v in sched.last_cycle_timing.items()})
    wire_delta_probe()


def wire_delta_probe(n_pods: int = 2000, flips: int = 4):
    """Wire-path companion to the in-process columns above: a live
    StoreServer, one delta-negotiated mirror and one object-path mirror,
    the same phase-flip churn through both — printed as the
    decode-vs-apply ms split (client/remote.py delta_stats) next to the
    flatten/ordering numbers."""
    import copy

    from volcano_tpu.client.remote import RemoteClusterStore
    from volcano_tpu.client.server import StoreServer

    store = ClusterStore()
    srv = StoreServer(store).start()
    arms = {}
    for name, delta in (("delta", True), ("object", False)):
        c = RemoteClusterStore(srv.address, delta_watch=delta)
        mirror = {}

        def on_pod(event, obj, old, _m=mirror):
            if event == "delete":
                _m.pop(f"{obj.namespace}/{obj.name}", None)
            else:
                _m[f"{obj.namespace}/{obj.name}"] = obj
        c.watch("pods", on_pod)
        arms[name] = (c, mirror)
    pods = [build_pod("bench", f"wp{i}", "", "Pending",
                      {"cpu": "1"}, f"wj{i % 50}") for i in range(n_pods)]
    for p in pods:
        store.create("pods", p)
    t0 = time.perf_counter()
    phases = ["Running", "Succeeded", "Pending", "Running"]
    for f in range(flips):
        for p in pods:
            cur = copy.deepcopy(
                store.get("pods", p.name, namespace="bench"))
            cur.phase = phases[f % len(phases)]
            cur.node_name = f"n{f}"
            store.update("pods", cur)
    applied = store._rv
    for c, _ in arms.values():
        c.wait_stream_applied("pods", applied, timeout=60.0)
    wall = (time.perf_counter() - t0) * 1e3
    dc, dm = arms["delta"]
    oc, om = arms["object"]
    n_ev = n_pods * flips
    assert all(dm[k].phase == om[k].phase and dm[k].node_name
               == om[k].node_name for k in om), "mirror divergence"
    st = dc.delta_stats
    print(f"wire delta: {st['events']}/{n_ev} events as patches, "
          f"decode {st['decode_ms']:.2f} ms vs apply "
          f"{st['apply_ms']:.2f} ms "
          f"({1e3 * (st['decode_ms'] + st['apply_ms']) / max(1, st['events']):.2f} us/event), "
          f"vocab {st['vocab']}, fallbacks {st['fallbacks']}")
    print(f"wire bytes: delta arm {st['bytes_delta']}, object arm "
          f"{oc.delta_stats['bytes_object']} "
          f"({oc.delta_stats['bytes_object'] / max(1, st['bytes_delta']):.1f}x), "
          f"churn wall {wall:.0f} ms for {n_ev} updates x 2 mirrors")
    for c, _ in arms.values():
        c.close()
    srv.stop()


if __name__ == "__main__":
    main()
