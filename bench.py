"""Benchmark: the BASELINE.json north star + configs #2/#4/#5.

Headline (config #3): 10k pods / 2k nodes / 3 weighted queues, solved per
session on one TPU chip with realistic churn between sessions (1% of jobs
rotate out of the pending set, ~1% of node rows change), measuring:
- steady-state wall p50 with the three-phase session pipeline engaged
  (ops.pipeline): session s+1's flatten + dirty-chunk upload dispatch
  overlap session s's in-flight solve while session s-1's readback blocks
  on the collector thread — the RTT floor amortizes across in-flight
  sessions, so wall/session converges to max(device, host flatten). This
  is the headline "value"; bind decisions are asserted byte-identical to
  the cold (full upload, no arena) path for every pipelined session.
- p50 synchronous session latency (sync_p50_ms, the BENCH_r01-r05
  series): flatten + delta upload (device-resident packed buffers, dirty
  chunks only) + solve + assignment readback;
- the device-bound solve rate (back-to-back solves on device-resident
  buffers): the throughput a locally attached chip sustains;
- the backend's no-op dispatch+readback floor; sync p50 - that floor is
  the implementation's share, and the pipeline is what reclaims the rest.
- arena wire accounting: bytes shipped per steady session (dirty chunks
  only) vs one full padded-buffer upload, and the arena hit rate.

Also measured, reported in extra.configs:
- #2  500 pods / 50 nodes: rounds-solver vs sequential-reference parity
      (identical job_ready sets + per-node capacity respect) + solve time.
- #4  2k running pods / 1k-pod high-priority gang: batched eviction solve
      (ops.solve_evict) end-to-end time.
- #5  5k pods / 1k nodes / 4 hierarchical-weight queues, cpu+mem+gpu
      multi-resource binpack with in-kernel queue caps.

Prints ONE JSON line.

Fault isolation contract: every config (headline included) runs inside
``_run_config`` — a transient transport drop (a store-wire socket, never a
device runtime error) retries once, and anything that still fails records
a per-config ``{"error": ...}`` field instead of discarding the numbers
already in hand. ``main`` always emits the JSON line and exits 0.

``chaos_churn`` extends that contract into the resilience acceptance
run: 50 full cycles over a networked store with deterministic faults
firing (watch breaks, store drops, a device-failure burst that opens the
circuit breaker), always emitting per-fault outcome fields
(fired/resumed/retried/host_fallback) plus the breaker's recovery trace
and a bind-for-bind comparison of the post-fault tail against the
no-fault run.

``failover`` is the crash-safe HA acceptance run: two scheduler
PROCESSES under leader election on a networked store, the leader
SIGKILLed mid-wave; records takeover latency (kill -> first standby
bind, and lease-expiry -> first bind) and the first-post-takeover
cycle's solve time + session-thread compile count, WARM standby
(shadow cycles) vs COLD as an A/B.

``store_durability`` closes the crash ladder at the store itself: WAL
churn overhead per fsync policy (single-op vs bulk batches), recovery
time vs journal length, and the kill-9 store soak — the durable store
PROCESS SIGKILLed with a wave committed but unbound, restarted on the
same port + data dir, decision trace asserted bind-for-bind identical
to an uninterrupted golden run with every watcher resuming via
``since:``.

``store_shard_scale`` is the sharded-front-door acceptance run (ROADMAP
item 3): at shards in {1, 4, 8} a ShardRouter serves the partitioned
store on one endpoint while 4 writer clients push chunked bulk pod
waves, a mirror counts every event off one batched bulk_watch stream,
and a live Scheduler's cycle p50 is measured idle vs under full churn;
plus the BENCH_r03 burst_decomp ingest shape (serial per-op baseline vs
the chunked-bulk sharded path).

``read_replica_fanout`` is the read-tier acceptance run (ISSUE 12): a
durable primary in its own process with a live paced Scheduler, and a
200-watcher + list-storm read load (separate processes) attached either
to the primary directly or to 1-2 WAL-shipped replica processes;
reports scheduler cycle stretch per arm, read-tier events/sec, and
replica apply lag (records, p50/p99) — ``ok`` enforces stretch <= 1.05x
idle with the storm on one replica.

``overload_shed`` is the admission-layer acceptance run (ISSUE 15): the
read_replica_fanout storm rig (200 watchers + list storm) aimed AT the
primary, against an ungated front door (the PR-12 collapse, writers
~20x down) and a gated one (read lane bounded at 8:64:16 — the storm
sheds TYPED at the gate while bulk-lane writers and control-lane
scheduler traffic pass); ``ok`` enforces gated writers >= 10x the
ungated floor and >= 300 events/sec, zero system-lane sheds, every
storm refusal a typed OverloadedError with a retry-after hint, and
binds identical to an unloaded golden.

``cycle_start_scale`` is the event-sourced ordering acceptance run
(ISSUE 14): two identical live-Scheduler rigs over a 10k-pending-task /
1k-job backlog run the same seeded churn script, one with the
OrderCache and one on the legacy full-sort collection; ``ok`` enforces
bind-for-bind identical decisions, steady-churn ordering >= 3x faster
than the full sort, and quiet cycles' ordering pass < 1 ms with zero
entries patched and zero re-sorts.

Core-bound floors: multi-process configs (``store_shard_scale``,
``read_replica_fanout``) split their absolute throughput/stretch floors
into a ``core_bound`` field when ``cpu_count`` is too small to prove
them — a 1-core rig records the values honestly without failing ``ok``
for a rig limitation; capable rigs still gate on the absolute floors.
"""

from __future__ import annotations

import json
import sys
import time
from types import SimpleNamespace

import numpy as np

TARGET_MS = 50.0
SESSIONS = 8
STEADY_CYCLES = 16    # steady-state cycles (variance wants > SESSIONS)
CHURN_JOBS = 10       # jobs rotated out of the pending set per session
CHURN_NODES = 20      # node rows dirtied per session

_NOOP = None


def rtt_probe(n: int = 3) -> float:
    """Median no-op dispatch+readback time (the backend's per-call
    floor). Cheap enough to interleave with timed sections so RTT drift
    during a run is visible instead of silently skewing derived metrics."""
    global _NOOP
    import jax

    if _NOOP is None:
        _NOOP = jax.jit(lambda x: x + 1)
        np.asarray(_NOOP(np.zeros(8, np.float32)))  # compile
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(_NOOP(np.zeros(8, np.float32)))
        samples.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(samples))


def spread_fields(prefix: str, samples) -> dict:
    """p10/p90/std for a sample set — the artifact's only p90 source (the
    explicit *_p90_ms fields were dropped so one statistic can't ship
    under two names)."""
    a = np.asarray(samples, np.float64)
    return {
        f"{prefix}_p10_ms": round(float(np.percentile(a, 10)), 2),
        f"{prefix}_p90_ms": round(float(np.percentile(a, 90)), 2),
        f"{prefix}_std_ms": round(float(a.std()), 2),
    }


def make_problem(n_nodes, n_jobs, tasks_per_job, cpu="32", mem="128Gi",
                 n_queues=1, queue_weights=None, gpu_every=0):
    from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION
    from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec

    nodes = {}
    for i in range(n_nodes):
        rl = {"cpu": cpu, "memory": mem, "pods": 110}
        if gpu_every:
            rl["nvidia.com/gpu"] = 8
        nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                       capacity=dict(rl)))
    jobs, tasks = {}, []
    for k in range(n_jobs):
        queue = f"q{k % n_queues}"
        pg = PodGroup(name=f"j{k}", namespace="bench",
                      spec=PodGroupSpec(min_member=tasks_per_job,
                                        queue=queue))
        job = JobInfo(f"bench/j{k}", pg)
        for i in range(tasks_per_job):
            # sizes vary by job so churn dirties real content (uniform
            # sizes make rotated jobs' rows byte-identical)
            req = {"cpu": str(1 + k % 3), "memory": f"{1 + k % 4}Gi"}
            if gpu_every and k % gpu_every == 0:
                req["nvidia.com/gpu"] = 1
            pod = Pod(name=f"j{k}-{i}", namespace="bench",
                      annotations={POD_GROUP_ANNOTATION: f"j{k}"},
                      containers=[{"requests": req}])
            t = TaskInfo(pod)
            job.add_task_info(t)
            tasks.append(t)
        jobs[job.uid] = job
    weights = queue_weights or [1] * n_queues
    queues = {f"q{i}": SimpleNamespace(weight=weights[i], capability=None)
              for i in range(n_queues)}
    return jobs, nodes, tasks, queues


def fill_queue_demand(arr, jobs, demand_cache):
    """Bench stand-in for the proportion plugin's session-open attrs:
    request = total demand per queue, allocated = 0. Per-job demand vectors
    cache on (uid, flat_version) like the flatten's blocks; the cache dict
    is per-config (configs reuse job uids, so sharing one would alias
    different problems' vectors).

    The per-queue totals are maintained incrementally (float64, deltas for
    departed/arrived/changed members only) so a 1%-churn session costs
    O(churn) numpy ops, not one vector add per job; a periodic full
    recompute bounds float drift far below float32 resolution."""
    qidx = {q: i for i, q in enumerate(arr.queues_list)}
    arr.queue_allocated[:] = 0.0
    st = demand_cache.get("__totals__")
    key = (tuple(arr.queues_list), arr.R)
    Q = arr.queue_request.shape[0]
    if st is None or st["key"] != key or st["tick"] >= 64:
        st = {"key": key, "members": {}, "tick": 0,
              "totals": np.zeros((Q, len(arr.vocab)), np.float64)}
        demand_cache["__totals__"] = st
    st["tick"] += 1
    totals = st["totals"]
    members = st["members"]
    seen = {}
    for uid, job in jobs.items():
        v = job.flat_version
        prev = members.get(uid)
        qi = qidx.get(job.queue)
        if prev is not None and prev[0] == v and prev[1] == qi:
            seen[uid] = prev
            continue
        ent = demand_cache.get(uid)
        if ent is None or ent[0] != v or ent[1].shape[0] != arr.R:
            ent = (v, job.total_request.to_vector(arr.vocab))
            demand_cache[uid] = ent
        if prev is not None and prev[1] is not None:
            totals[prev[1]] -= prev[2]
        if qi is not None:
            totals[qi] += ent[1]
        seen[uid] = (v, qi, ent[1])
    for uid, prev in members.items():
        if uid not in seen and prev[1] is not None:
            totals[prev[1]] -= prev[2]
    st["members"] = seen
    arr.queue_request[:] = totals.astype(np.float32)


def headline(n_nodes=2000, n_jobs=1000, tpj=10):
    import jax
    from __graft_entry__ import _params
    from volcano_tpu.api import TaskStatus
    from volcano_tpu.ops import FlattenCache, PackedDeviceCache, \
        flatten_snapshot
    from volcano_tpu.ops.solver import solve_allocate_delta
    jobs, nodes, tasks, queues = make_problem(
        n_nodes, n_jobs, tpj, n_queues=3, queue_weights=[1, 2, 3])
    node_list = list(nodes.values())
    fcache, dcache = FlattenCache(), PackedDeviceCache()
    demand_cache = {}
    tasks_by_job = {}
    for t in tasks:
        tasks_by_job.setdefault(t.job, []).append(t)

    held = {}

    def churn(s):
        """Rotate CHURN_JOBS jobs out of the pending set and dirty
        CHURN_NODES node rows through the accounting API."""
        from volcano_tpu.api import TaskInfo
        from volcano_tpu.api.types import POD_GROUP_ANNOTATION
        from volcano_tpu.models import Pod

        lo = (s * CHURN_JOBS) % n_jobs
        excl = {f"bench/j{(lo + d) % n_jobs}" for d in range(CHURN_JOBS)}
        jobs_s = {u: j for u, j in jobs.items() if u not in excl}
        grouped_s = [(j, tasks_by_job[u]) for u, j in jobs_s.items()]
        tasks_s = [t for _, ts in grouped_s for t in ts]
        for d in range(CHURN_NODES):
            ni = node_list[(s * CHURN_NODES + d) % n_nodes]
            t = held.pop(ni.name, None)
            if t is not None:
                ni.remove_task(t)
            else:
                pod = Pod(name=f"churn-{ni.name}", namespace="bench",
                          node_name=ni.name, phase="Running",
                          annotations={POD_GROUP_ANNOTATION: "j0"},
                          containers=[{"requests": {"cpu": "1",
                                                    "memory": "1Gi"}}])
                t = TaskInfo(pod)
                t.status = TaskStatus.RUNNING
                ni.add_task(t)
                held[ni.name] = t
        return jobs_s, tasks_s, grouped_s

    def one_session(jobs_s, tasks_s, grouped_s=None, drf=False):
        # fused dispatch: scatter+solve in ONE device call, then one
        # compact readback — 2 round-trips total per session (deltas over
        # FUSED_SLOTS chunks fall back to scatter + non-fused solve)
        from volcano_tpu.ops.solver import solve_allocate_packed2d
        arr = flatten_snapshot(jobs_s, nodes, tasks_s, cache=fcache,
                               queues=queues, grouped=grouped_s)
        fill_queue_demand(arr, jobs_s, demand_cache)
        fbuf, ibuf, layout = arr.packed()
        params = _params(arr)
        kind, payload = dcache.plan_delta(fbuf, ibuf, layout)
        if kind == "updated":
            f2d, i2d = payload
            return solve_allocate_packed2d(f2d, i2d, layout, params,
                                           use_queue_cap=True,
                                           use_drf_order=drf)
        f2d, i2d, fi, fv, ii, iv = payload
        res, nf, ni = solve_allocate_delta(
            f2d, i2d, fi, fv, ii, iv, layout, params,
            use_queue_cap=True, use_drf_order=drf)
        dcache.commit(nf, ni)
        return res

    # warmup / compile, on the same churn pattern the timed sessions use so
    # the delta-scatter kernels for steady-state chunk-count buckets are
    # already compiled (a fresh bucket recompiles ~1s)
    for s in range(4):
        res = one_session(*churn(s))
    res.assigned.block_until_ready()

    # synchronous sessions (the honest per-cycle latency), with an RTT
    # probe interleaved after every session so wire drift is measured at
    # the same moments the sessions ran, not once at the end
    lat, flat_ms, chunks, rtts, placed = [], [], [], [], 0
    for s in range(4, 4 + SESSIONS):
        jobs_s, tasks_s, grouped_s = churn(s)
        t0 = time.perf_counter()
        res = one_session(jobs_s, tasks_s, grouped_s)
        assigned = np.asarray(res.compact)
        lat.append((time.perf_counter() - t0) * 1e3)
        chunks.append(dcache.last_shipped_chunks)
        rtts.append(rtt_probe(1))
        placed = int((assigned[:len(tasks_s)] >= 0).sum())
    # flatten-only share (warm, with churn): 5 reps so the artifact
    # carries the spread, not a single draw
    fl_reps = []
    for rep in range(5):
        jobs_s, tasks_s, grouped_s = churn(4 + SESSIONS + rep)
        t0 = time.perf_counter()
        arr = flatten_snapshot(jobs_s, nodes, tasks_s, cache=fcache,
                               queues=queues, grouped=grouped_s)
        fill_queue_demand(arr, jobs_s, demand_cache)
        arr.packed()
        fl_reps.append((time.perf_counter() - t0) * 1e3)
    flatten_ms = float(np.median(fl_reps))

    # device-bound solve rate: back-to-back solves on device-resident
    # buffers — the throughput the chip sustains
    # (solve_allocate_packed2d: no donation, so one buffer set serves all)
    from volcano_tpu.ops.solver import solve_allocate_packed2d
    jobs_s, tasks_s, grouped_s = churn(6 + 3 * SESSIONS)
    r = one_session(jobs_s, tasks_s, grouped_s)
    r.compact.block_until_ready()
    arr = flatten_snapshot(jobs_s, nodes, tasks_s, cache=fcache,
                           queues=queues, grouped=grouped_s)
    fill_queue_demand(arr, jobs_s, demand_cache)
    fbuf, ibuf, layout = arr.packed()
    f2d, i2d = dcache.update(fbuf, ibuf, layout)
    params = _params(arr)
    # warm the non-donating solves (the timed loops must not compile)
    solve_allocate_packed2d(f2d, i2d, layout, params,
                            use_queue_cap=True).compact.block_until_ready()
    arr.drf_total = (arr.node_alloc
                     * arr.node_valid[:, None]).sum(axis=0).astype(
        np.float32)
    fbuf_d, ibuf_d, layout_d = arr.packed()
    dcache2 = type(dcache)()
    f2d_d, i2d_d = dcache2.update(fbuf_d, ibuf_d, layout_d)
    rd = solve_allocate_packed2d(f2d_d, i2d_d, layout_d, params,
                                 use_queue_cap=True, use_drf_order=True)
    rd.compact.block_until_ready()  # compile
    drf_placed = int((np.asarray(rd.assigned)[:len(tasks_s)] >= 0).sum())

    def batch(bufs, lay, drf):
        """SESSIONS back-to-back solves, blocking on the last: device work
        is serial in dispatch order, so one amortized round trip times the
        whole batch."""
        t0 = time.perf_counter()
        futs = [solve_allocate_packed2d(bufs[0], bufs[1], lay, params,
                                        use_queue_cap=True,
                                        use_drf_order=drf)
                for _ in range(SESSIONS)]
        futs[-1].compact.block_until_ready()
        return (time.perf_counter() - t0) / SESSIONS * 1e3

    # device-bound solve rate, A/B-interleaved with the drf variant and
    # repeated so the artifact carries spread, not a single draw (this
    # rig's chip tenancy swings device timings 20-30% between runs)
    dev_reps, drf_reps = [], []
    for _ in range(3):
        dev_reps.append(batch((f2d, i2d), layout, False))
        drf_reps.append(batch((f2d_d, i2d_d), layout_d, True))
        rtts.append(rtt_probe(1))
    device_ms = float(np.median(dev_reps))
    drf_device_ms = float(np.median(drf_reps))
    device_pods_per_sec = int(len(tasks_s) / (device_ms / 1e3))

    # ------------------------------------------------------------------
    # pipelined steady state: the three-phase overlap (ops.pipeline).
    # Session s+1's flatten + delta upload dispatch on the main thread
    # while session s solves on device and session s-1's readback blocks
    # on the collector thread — the RTT floor amortizes across in-flight
    # sessions and wall/session converges to max(device, host flatten).
    # Byte-identity vs the cold path (fresh full-buffer upload, no arena)
    # is asserted for every pipelined session after the timed run.
    # ------------------------------------------------------------------
    from volcano_tpu.ops.pipeline import SessionPipeline, start_readback

    pipe_sessions = 2 * SESSIONS
    s0 = 8 + 4 * SESSIONS
    # warm the device-params solve variants (delta + packed2d with PINNED
    # params): the sync sessions above used host-side params, and a first
    # pipelined dispatch must not compile
    params_dev = dcache.params_device(params)
    c = dcache.chunk
    cfw = dcache._host_f.size // c
    zero16 = np.zeros(dcache.FUSED_SLOTS, np.int32)
    fvw = dcache._host_f.reshape(cfw, c)[zero16]
    ivw = dcache._host_i.reshape(-1, c)[zero16]
    res_w, nfw, niw = solve_allocate_delta(
        dcache._dev_f, dcache._dev_i, zero16, fvw, zero16, ivw,
        dcache._layout, params_dev, use_queue_cap=True)
    dcache.commit(nfw, niw)
    res_w.compact.block_until_ready()
    solve_allocate_packed2d(dcache._dev_f, dcache._dev_i, dcache._layout,
                            params_dev,
                            use_queue_cap=True).compact.block_until_ready()

    pipe = SessionPipeline(depth=2)
    refs = []           # (fbuf, ibuf, layout, n_tasks) for the cold replay
    pbytes, pchunks = [], []
    ship0 = dcache.total_shipped_bytes
    sess0 = dcache.sessions
    hit0 = dcache.delta_sessions

    def make_session(kind, payload, layout, params_dev):
        def dispatch():
            if kind == "updated":
                f2d, i2d = payload
                r = solve_allocate_packed2d(
                    f2d, i2d, layout, params_dev, use_queue_cap=True)
            else:
                f2d, i2d, fi, fv, ii, iv = payload
                r, nf, ni = solve_allocate_delta(
                    f2d, i2d, fi, fv, ii, iv, layout, params_dev,
                    use_queue_cap=True)
                dcache.commit(nf, ni)
            start_readback(r.compact)
            return r

        def collect(r):
            return np.asarray(r.compact)

        return dispatch, collect

    t_pipe0 = time.perf_counter()
    for i in range(pipe_sessions):
        jobs_s, tasks_s, grouped_s = churn(s0 + i)
        arr = flatten_snapshot(jobs_s, nodes, tasks_s, cache=fcache,
                               queues=queues, grouped=grouped_s)
        fill_queue_demand(arr, jobs_s, demand_cache)
        fbuf, ibuf, layout = arr.packed()
        refs.append((fbuf.copy(), ibuf.copy(), layout, len(tasks_s)))
        kind, payload = dcache.plan_delta(fbuf, ibuf, layout)
        pbytes.append(dcache.last_shipped_bytes)
        pchunks.append(dcache.last_shipped_chunks)
        params_dev = dcache.params_device(params)
        pipe.submit(i, *make_session(kind, payload, layout, params_dev))
    tickets = pipe.drain(timeout=600)
    pipe_wall_ms = (time.perf_counter() - t_pipe0) * 1e3
    overlap_pairs = pipe.overlap_pairs()
    pipe.close()
    # per-session steady wall: deltas between consecutive collect
    # completions once the pipe is full (first `depth` sessions fill it)
    cts = [t.t_collected for t in tickets]
    gaps = (np.diff(cts)[2:] * 1e3) if len(cts) > 3 else \
        np.asarray([pipe_wall_ms / max(pipe_sessions, 1)])
    pipe_p50 = float(np.percentile(gaps, 50))

    # byte-identity: replay every pipelined session through the cold path
    # (fresh full-buffer device_put, host params, no arena) and compare
    # decoded assignments bit-for-bit
    from volcano_tpu.ops.solver import decode_compact
    identical = True
    for t, (fb, ib, lay, ntasks) in zip(tickets, refs):
        a_pipe, k_pipe = decode_compact(t.result())
        cfr = -(-max(fb.size, 1) // c)
        cir = -(-max(ib.size, 1) // c)
        hf = np.zeros(cfr * c, np.float32)
        hf[:fb.size] = fb
        hi = np.zeros(cir * c, np.int32)
        hi[:ib.size] = ib
        rr = solve_allocate_packed2d(
            jax.device_put(hf.reshape(cfr, c)),
            jax.device_put(hi.reshape(cir, c)), lay, params,
            use_queue_cap=True)
        a_cold, k_cold = decode_compact(np.asarray(rr.compact))
        if not (np.array_equal(a_pipe[:ntasks], a_cold[:ntasks])
                and np.array_equal(k_pipe[:ntasks], k_cold[:ntasks])):
            identical = False
    full_bytes = dcache.full_upload_bytes()
    bytes_per_session = float(np.mean(pbytes)) if pbytes else 0.0
    arena_sessions = dcache.sessions - sess0
    arena_hits = dcache.delta_sessions - hit0

    rtt = float(np.median(rtts))
    rtt_drift = float(max(rtts) / max(min(rtts), 1e-9))
    p50 = float(np.percentile(lat, 50))
    steady_wall_p50 = pipe_p50
    return {
        # steady-state wall p50 with the three-phase pipeline engaged —
        # the headline "value" (a steady production cycle's honest wall
        # cost); the synchronous per-session latency stays as sync_p50_ms
        # for continuity with BENCH_r01-r05
        "steady_wall_p50_ms": round(steady_wall_p50, 2),
        **spread_fields("steady_wall", gaps),
        "steady_wall_over_device": round(
            steady_wall_p50 / max(device_ms, 1e-9), 3),
        "pipeline_depth": 2,
        "pipeline_sessions": pipe_sessions,
        "pipeline_wall_ms_total": round(pipe_wall_ms, 2),
        "pipeline_overlap_pairs": overlap_pairs,
        "pipelined_identical_to_cold": bool(identical),
        # arena wire accounting over the pipelined steady run
        "bytes_shipped_per_session": int(bytes_per_session),
        "full_upload_bytes": int(full_bytes),
        "bytes_shipped_pct_of_full": round(
            100.0 * bytes_per_session / max(full_bytes, 1), 2),
        "dirty_chunks_mean": round(float(np.mean(pchunks)), 1)
        if pchunks else 0.0,
        "arena_hit_rate": round(
            arena_hits / max(arena_sessions, 1), 3),
        "sync_p50_ms": round(p50, 2),
        **spread_fields("lat", lat),
        "rtt_floor_ms": round(rtt, 2),
        "rtt_p10_ms": round(float(np.percentile(rtts, 10)), 2),
        "rtt_p90_ms": round(float(np.percentile(rtts, 90)), 2),
        # >2x drift between interleaved probes means wire-derived fields
        # (p50_minus_rtt) are untrustworthy for this run
        "rtt_drift_ratio": round(rtt_drift, 2),
        "rtt_unstable": bool(rtt_drift > 2.0),
        "p50_minus_rtt_ms": round(max(p50 - rtt, 0.0), 2),
        "pods_per_sec": int(placed / (p50 / 1e3)),
        "device_ms_per_session": round(device_ms, 2),
        "device_ms_reps": [round(x, 2) for x in dev_reps],
        "device_pods_per_sec": device_pods_per_sec,
        "drf_device_ms_per_session": round(drf_device_ms, 2),
        "drf_device_ms_reps": [round(x, 2) for x in drf_reps],
        "drf_placed": drf_placed,
        # host flatten + device solve per session
        "p50_local_estimate_ms": round(flatten_ms + device_ms, 2),
        "flatten_ms": round(flatten_ms, 2),
        "flatten_ms_reps": [round(x, 2) for x in fl_reps],
        "shipped_chunks_mean": round(float(np.mean(chunks)), 1),
        "placed": placed,
        "sessions": SESSIONS,
    }


def full_cycle():
    """The FULL runOnce at the headline scale — snapshot clone + plugin
    session-opens + enqueue/allocate/backfill + Statement replay + job
    updater close — i.e. what the reference's e2e scheduling-latency
    histogram wraps (pkg/scheduler/metrics/metrics.go:41-70). Two regimes:

    - burst: a fresh 10k-pod wave scheduled in ONE cycle on an idle 2k-node
      cluster (the all-cold worst case: every flatten block recomputes,
      ~10k Statement ops replay, 1k podgroup statuses update);
    - steady: the production regime — the same cluster with 10k RUNNING
      pods, a 100-pod wave arriving per cycle (1% churn). Reported p50
      with open/solve/replay/close decomposition.
    """
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase
    from volcano_tpu.scheduler import Scheduler

    n_nodes, n_jobs, tpj = 2000, 1000, 10

    def build_cluster(shared_dcache=None):
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
        if shared_dcache is not None:
            cache.device_cache = shared_dcache
        return store, cache

    def make_wave(store, k):
        pg = build_pod_group(f"j{k}", "bench", min_member=tpj,
                             queue=f"q{k % 3}")
        pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", pg)
        for i in range(tpj):
            store.create("pods", build_pod(
                "bench", f"j{k}-{i}", "", "Pending",
                {"cpu": str(1 + k % 3), "memory": f"{1 + k % 4}Gi"},
                f"j{k}"))

    # warm-up burst: compiles every jit variant this scenario hits
    store, cache = build_cluster()
    sched = Scheduler(cache)
    sched.run_once()

    # measured burst on a fresh identical cluster (device cache shared so
    # the packed layout and jit executables are warm, as a long-running
    # scheduler's would be; flatten blocks are cold — new jobs ARE new)
    store, cache = build_cluster(shared_dcache=cache.device_cache)
    sched = Scheduler(cache)
    t0 = time.perf_counter()
    sched.run_once()
    burst_ms = (time.perf_counter() - t0) * 1e3
    burst_bound = len(cache.binder.binds)
    burst_timing = dict_timing(sched)

    # steady state: 100 new pods/cycle on the now-10k-running cluster.
    # Two warm cycles first: the steady wave's flatten buckets (T~128 vs
    # the burst's 10k) compile their own solve variant. An RTT probe runs
    # after EVERY timed cycle so the wire's drift is sampled at the same
    # moments the cycles ran.
    lat, host_ms, solve_ms, placed, rtts = [], [], [], [], []
    wave = n_jobs
    for w in range(20):
        make_wave(store, wave)
        wave += 1
        if w % 10 == 9:
            sched.run_once()
    for s in range(STEADY_CYCLES):
        for w in range(10):
            make_wave(store, wave)
            wave += 1
        before = len(cache.binder.binds)
        t0 = time.perf_counter()
        sched.run_once()
        lat.append((time.perf_counter() - t0) * 1e3)
        t = sched.last_cycle_timing
        # host share = everything but the solve dispatch+readback
        host_ms.append(t["total_ms"] - t.get("solve_ms", 0.0))
        solve_ms.append(t.get("solve_ms", 0.0))
        placed.append(len(cache.binder.binds) - before)
        rtts.append(rtt_probe(1))
        sched._maybe_gc()  # the run() loop's between-cycles housekeeping
    steady_timing = dict_timing(sched)

    # device-bound steady solve: re-dispatch the exact solve variant the
    # steady cycles ran (same committed buffers, same flags) back-to-back,
    # blocking once — the steady-shape analog of the headline's
    # device_ms_per_session, and the honest "local chip" solve cost
    from volcano_tpu.ops.solver import solve_allocate_packed2d
    dc = cache.device_cache
    fl = dict(dc.last_solve_flags)
    lay = fl.pop("layout")
    sd_params = dc.last_params
    f2d, i2d = dc._dev_f, dc._dev_i
    solve_allocate_packed2d(
        f2d, i2d, lay, sd_params, **fl).compact.block_until_ready()
    # 3 reps (median + recorded spread): whether a device-time drift is
    # rig noise or a regression must be readable from one artifact
    sd_reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        futs = [solve_allocate_packed2d(f2d, i2d, lay, sd_params, **fl)
                for _ in range(SESSIONS)]
        futs[-1].compact.block_until_ready()
        sd_reps.append((time.perf_counter() - t0) / SESSIONS * 1e3)
    steady_device_ms = float(np.median(sd_reps))

    p50 = float(np.percentile(lat, 50))
    host_p50 = float(np.percentile(host_ms, 50))
    solve_p50 = float(np.percentile(solve_ms, 50))
    # two local-chip estimates that must agree: (a) measured host share +
    # measured device-bound solve; (b) per-cycle host + solve with that
    # cycle's own RTT probe subtracted
    local_sub = [h + max(s - r, 0.0)
                 for h, s, r in zip(host_ms, solve_ms, rtts)]
    rtt_drift = float(max(rtts) / max(min(rtts), 1e-9))
    return {
        "burst_ms": round(burst_ms, 2),
        "burst_bound": burst_bound,
        "burst_decomp": burst_timing,
        "steady_p50_ms": round(p50, 2),
        **spread_fields("steady", lat),
        "steady_host_p50_ms": round(host_p50, 2),
        **spread_fields("steady_host", host_ms),
        "steady_solve_p50_ms": round(solve_p50, 2),
        "steady_device_ms": round(steady_device_ms, 2),
        "steady_device_ms_reps": [round(x, 2) for x in sd_reps],
        "steady_rtt_p50_ms": round(float(np.median(rtts)), 2),
        "steady_rtt_drift_ratio": round(rtt_drift, 2),
        "steady_rtt_unstable": bool(rtt_drift > 2.0),
        # (a): the primary local estimate — measured host + device-bound
        # steady solve, no wire in either term
        "steady_local_p50_ms": round(host_p50 + steady_device_ms, 2),
        # (b): the RTT-subtraction cross-check (per-cycle probes)
        "steady_local_rttsub_p50_ms": round(
            float(np.percentile(local_sub, 50)), 2),
        "steady_placed_per_cycle": int(np.median(placed)),
        "steady_decomp": steady_timing,
        "cycles": STEADY_CYCLES,
    }


def dict_timing(sched):
    t = getattr(sched, "last_cycle_timing", None)
    # timing carries non-numeric diagnostics too (arena_mode str) —
    # round only the scalars
    return {k: (round(v, 2) if isinstance(v, (int, float)) else v)
            for k, v in (t or {}).items()}


def sharded_path_compare(single_device_ms):
    """Single-device vs shard_map solver on the SAME problem and chip
    (VERDICT r4 missing #2's measurement): a 1-device mesh on the real
    TPU runs the sharded code path — per-shard fused pallas kernel,
    collectives now SKIPPED AT TRACE TIME at D=1 (the compiled program is
    collective-free, tests/test_parallel.py::TestShardedD1ZeroCost) — so
    its device-bound rate is directly comparable to the single-device
    solver's. Both sides dispatch the same device-resident packed-buffer
    form (solve_allocate_*_packed2d), so the measured ratio is pure
    shard_map wrapper cost, not a transfer asymmetry. Multi-chip behavior
    itself is proven on the virtual mesh (tests/test_parallel) and by the
    driver's dryrun; this records what the sharded path costs on silicon.

    Fault containment (BENCH_r05's rc=1 regression): every sharded
    dispatch gets the shared transient-transport retry, and a dispatch
    that still fails returns a PARTIAL artifact — error fields plus
    whatever reps were already measured — instead of escaping to main.
    The _run_config wrapper remains the outer line of defense."""
    import jax
    from __graft_entry__ import _params
    from volcano_tpu.ops import PackedDeviceCache, flatten_snapshot
    from volcano_tpu.ops.pallas_kernels import (
        fused_choice_auto, use_interpret,
    )
    from volcano_tpu.parallel import (
        make_mesh, solve_allocate_sharded_packed2d,
    )
    from volcano_tpu.resilience.transient import retry_transient

    jobs, nodes, tasks, queues = make_problem(
        2000, 1000, 10, n_queues=3, queue_weights=[1, 2, 3])
    arr = flatten_snapshot(jobs, nodes, tasks, queues=queues)
    fill_queue_demand(arr, jobs, {})
    fbuf, ibuf, layout = arr.packed()
    f2d, i2d = PackedDeviceCache().update(fbuf, ibuf, layout)
    params = {k: jax.device_put(np.asarray(v))
              for k, v in _params(arr).items()}
    mesh = make_mesh(jax.devices()[:1])
    out = {
        "single_device_ms": round(single_device_ms, 2),
        "fused_on_shard": bool(not use_interpret()
                               and fused_choice_auto(arr.T, arr.N)),
        "devices": 1,
    }
    reps = []
    try:
        def compile_probe():
            r = solve_allocate_sharded_packed2d(
                f2d, i2d, layout, params, mesh, use_queue_cap=True)
            r.assigned.block_until_ready()
            return r

        res = retry_transient(compile_probe, what="sharded compile")
        for _ in range(3):  # median of 3 like the single-device measure
            def rep():
                t0 = time.perf_counter()
                futs = [solve_allocate_sharded_packed2d(
                            f2d, i2d, layout, params, mesh,
                            use_queue_cap=True)
                        for _ in range(SESSIONS)]
                futs[-1].assigned.block_until_ready()
                return (time.perf_counter() - t0) / SESSIONS * 1e3

            reps.append(retry_transient(rep, what="sharded solve rep"))
    except Exception as e:  # noqa: BLE001 — partial artifact, never abort
        out["error"] = f"{type(e).__name__}: {e}".strip()[:500]
        out["sharded_device_ms_reps"] = [round(x, 2) for x in reps]
        return out
    sharded_ms = float(np.median(reps))
    placed = int((np.asarray(res.assigned)[:len(tasks)] >= 0).sum())
    ratio = (sharded_ms / single_device_ms
             if single_device_ms and single_device_ms > 0 else None)
    out.update({
        "sharded_device_ms": round(sharded_ms, 2),
        "sharded_device_ms_reps": [round(x, 2) for x in reps],
        "sharded_over_single": round(ratio, 3) if ratio else None,
        "placed": placed,
    })
    return out


def _synth_snapshot(n_tasks: int, n_nodes: int, n_queues: int = 3,
                    tasks_per_job: int = 97, seed: int = 7):
    """A SnapshotArrays built directly from numpy (no 100k python pod
    objects): the beyond-one-chip bench exercises the arena + sharded
    solve data path, whose inputs are exactly these padded arrays. Sized
    unsaturated so every gang places in one fixpoint iteration and the
    measured time is the steady solve, not a pathological revert storm."""
    from volcano_tpu.api.resource import ResourceVocab
    from volcano_tpu.ops import SnapshotArrays

    rng = np.random.default_rng(seed)
    T, N = n_tasks, n_nodes
    R = 2
    J = max(T // tasks_per_job + (1 if T % tasks_per_job else 0), 1)
    arr = SnapshotArrays(vocab=ResourceVocab())
    arr.task_init_req = np.zeros((T, R), np.float32)
    arr.task_job = np.zeros(T, np.int32)
    arr.task_rank = np.arange(T, dtype=np.int32)
    arr.task_sig = np.zeros(T, np.int32)
    arr.task_counts_ready = np.ones(T, bool)
    arr.task_valid = np.ones(T, bool)
    job_min = np.zeros(J, np.int32)
    for j in range(J):
        lo, hi = j * tasks_per_job, min((j + 1) * tasks_per_job, T)
        req = (float(rng.integers(1, 4)) * 1000.0,
               float(rng.integers(1, 5)) * (1 << 30))
        arr.task_init_req[lo:hi] = req
        arr.task_job[lo:hi] = j
        job_min[j] = hi - lo
    arr.task_req = arr.task_init_req.copy()
    arr.job_min = job_min
    arr.job_ready_base = np.zeros(J, np.int32)
    arr.job_queue = (np.arange(J) % n_queues).astype(np.int32)
    arr.job_valid = np.ones(J, bool)
    arr.job_drf_allocated = np.zeros((J, R), np.float32)
    arr.drf_total = np.zeros(R, np.float32)
    arr.job_drf_prerank = np.zeros(J, np.int32)
    idle = np.zeros((N, R), np.float32)
    # capacity ~3x demand: binpack concentrates, nothing reverts
    per_node_cpu = max(3.0 * np.sum(arr.task_init_req[:, 0]) / N, 8000.0)
    idle[:, 0] = np.float32(per_node_cpu)
    idle[:, 1] = np.float32(256.0 * (1 << 30))
    arr.node_idle = idle
    arr.node_extra_future = np.zeros((N, R), np.float32)
    arr.node_used = np.zeros((N, R), np.float32)
    arr.node_alloc = idle.copy()
    arr.node_npods = np.zeros(N, np.int32)
    arr.node_max_pods = np.full(N, 1 << 20, np.int32)
    arr.node_valid = np.ones(N, bool)
    arr.sig_masks = np.ones((1, N), bool)
    qw = np.arange(1, n_queues + 1, dtype=np.float32)
    arr.queue_weight = qw
    arr.queue_capability = np.full((n_queues, R), np.inf, np.float32)
    arr.queue_allocated = np.zeros((n_queues, R), np.float32)
    qreq = np.zeros((n_queues, R), np.float64)
    for j in range(J):
        lo, hi = j * tasks_per_job, min((j + 1) * tasks_per_job, T)
        qreq[arr.job_queue[j]] += arr.task_init_req[lo:hi].sum(axis=0)
    arr.queue_request = qreq.astype(np.float32)
    arr.thresholds = np.array([10.0, 1.0], np.float32)
    arr.scalar_dim_mask = np.zeros(R, bool)
    return arr


def _decision_digest(*arrays) -> str:
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def sharded_scale(n_tasks: int = 100_000, n_nodes: int = 10_000,
                  pipe_sessions: int = 8, churn_tasks: int = 256,
                  churn_nodes: int = 64, sub_tasks: int = 2_048,
                  sub_nodes: int = 1_024):
    """The beyond-one-chip headline (``sharded_100k_10k``): 100k tasks x
    10k nodes solved with the node axis sharded over the device mesh —
    padded buffers that deliberately exceed one chip's working set — via
    the SHARDED device-resident arena (ops.device_cache.
    ShardedDeviceCache) and the three-phase session pipeline. Reports:

    - pipelined steady-state wall p50 across churned sessions (session
      s+1's delta ships while session s solves on the mesh);
    - wire bytes shipped PER SHARD per steady session + arena hit rate,
      and a zero-dirty session asserted to ship 0 bytes to every shard;
    - a sub-scale digest cross-check: the same problem solved by the
      sharded arena on the full mesh and by the D=1 packed path must be
      decision-identical bit for bit (the host-oracle leg of the
      cross-check runs in ``sim_quality``, whose host/device/sharded
      arms share one seeded workload).

    Degradation contract: on a single-device host the full-scale run is
    not attempted (one chip cannot hold it — that is the point); the
    artifact carries the sub-scale cross-check plus an ``error`` field
    and ``ok=false``, never a crash (BENCH_r05's regression shape).
    """
    import jax

    from volcano_tpu.ops.device_cache import (
        PackedDeviceCache, ShardedDeviceCache,
    )
    from volcano_tpu.ops.pipeline import SessionPipeline, start_readback
    from volcano_tpu.ops.solver import decode_compact, \
        solve_allocate_packed2d
    from volcano_tpu.parallel import arena_mesh, solve_allocate_sharded_arena
    from volcano_tpu.resilience.transient import retry_transient

    mesh = arena_mesh()
    D = int(mesh.devices.size)
    out = {
        "tasks": n_tasks, "nodes": n_nodes,
        "devices": len(jax.devices()), "mesh_devices": D,
        "ok": False,
    }
    kw = dict(herd_mode="pack", score_families=("binpack",),
              use_queue_cap=True)

    def _scale_params(a):
        return {
            "binpack_weight": np.float32(1.0),
            "binpack_res_weights": np.ones(a.R, np.float32),
            "least_req_weight": np.float32(0.0),
            "most_req_weight": np.float32(0.0),
            "balanced_weight": np.float32(0.0),
            "node_static": np.zeros(a.N, np.float32),
        }

    # ---- sub-scale digest cross-check (runs at any device count) ----
    sub = _synth_snapshot(sub_tasks, sub_nodes)
    fbuf, ibuf, layout = sub.packed()
    params = _scale_params(sub)
    sdc_sub = ShardedDeviceCache(mesh)
    bufs = sdc_sub.update(fbuf, ibuf, layout)
    r_sh = retry_transient(
        lambda: solve_allocate_sharded_arena(
            *bufs, sdc_sub.params_device(params), mesh, **kw),
        what="sub-scale sharded dispatch")
    dc = PackedDeviceCache()
    f2d, i2d = dc.update(fbuf, ibuf, layout)
    r_pk = solve_allocate_packed2d(f2d, i2d, layout, params, **kw)
    a_pk, k_pk = decode_compact(np.asarray(r_pk.compact))
    d_sh = _decision_digest(np.asarray(r_sh.assigned)[:sub_tasks],
                            np.asarray(r_sh.kind)[:sub_tasks])
    d_pk = _decision_digest(a_pk[:sub_tasks], k_pk[:sub_tasks])
    out["subscale_tasks"] = sub_tasks
    out["subscale_digest_sharded"] = d_sh
    out["subscale_digest_packed_d1"] = d_pk
    out["subscale_digest_identical"] = bool(d_sh == d_pk)

    if D < 2:
        out["error"] = (
            f"sharded_100k_10k needs a multi-device mesh (have {D} "
            "device(s)): the full-scale problem does not fit one chip's "
            "padded buffers by design; sub-scale cross-check recorded")
        return out

    # ---- full-scale pipelined steady state over the sharded arena ----
    arr = _synth_snapshot(n_tasks, n_nodes)
    params = _scale_params(arr)
    sdc = ShardedDeviceCache(mesh)

    def churn(s):
        """Dirty one contiguous task band (a job wave re-sizing: the
        replicated delta) and one contiguous node band (idle drift on a
        rack: the per-shard delta) — the headline's ~1% churn shape,
        contiguous like real job blocks so the dirty set stays a few
        chunks, not a chunk-per-row smear."""
        lo = (s * churn_tasks) % max(n_tasks - churn_tasks, 1)
        ti = np.arange(lo, lo + churn_tasks)
        arr.task_init_req[ti, 0] = np.float32((1.0 + (s % 3)) * 1000.0)
        arr.task_req[ti] = arr.task_init_req[ti]
        nlo = (s * churn_nodes) % max(n_nodes - churn_nodes, 1)
        ni = np.arange(nlo, nlo + churn_nodes)
        arr.node_idle[ni, 0] = arr.node_alloc[ni, 0] - np.float32(
            1000.0 * (1 + s % 4))

    def session(tag, pipe):
        fb, ib, lay = arr.packed()
        bufs = sdc.update(fb, ib, lay)
        pd = sdc.params_device(params)
        sbytes = (list(sdc.last_shard_bytes),
                  int(sdc.last_shipped_bytes))

        def dispatch():
            r = retry_transient(
                lambda: solve_allocate_sharded_arena(
                    *bufs, pd, mesh, **kw),
                what="sharded scale dispatch")
            start_readback(r.assigned, r.kind)
            return r

        def collect(r):
            return np.asarray(r.assigned), np.asarray(r.kind)

        return pipe.submit(tag, dispatch, collect), sbytes

    try:
        # warm (compile) + settle
        pipe = SessionPipeline(depth=2)
        t_warm = time.perf_counter()
        t0, _ = session(-1, pipe)
        a0, _k0 = t0.result(1800)
        out["warm_s"] = round(time.perf_counter() - t_warm, 1)
        out["placed_warm"] = int((a0[:n_tasks] >= 0).sum())

        # zero-dirty session: unchanged snapshot -> 0 bytes to every shard
        tz, (zbytes, _zwire) = session(-2, pipe)
        tz.result(600)
        out["zero_dirty_shard_bytes"] = [int(b) for b in zbytes]
        out["zero_dirty_ok"] = not any(zbytes)

        shard_bytes, wire_bytes = [], []
        tickets = []
        t_pipe0 = time.perf_counter()
        for s in range(pipe_sessions):
            churn(s)
            t, (sb, wb) = session(s, pipe)
            tickets.append(t)
            shard_bytes.append(sb)
            wire_bytes.append(wb)
        pipe.drain(timeout=1800)
        wall_ms = (time.perf_counter() - t_pipe0) * 1e3
        out["pipeline_overlap_pairs"] = pipe.overlap_pairs()
        pipe.close()
        cts = [t.t_collected for t in tickets]
        gaps = (np.diff(cts)[1:] * 1e3) if len(cts) > 2 else \
            np.asarray([wall_ms / max(pipe_sessions, 1)])
        a_last, _ = tickets[-1].result()
        placed = int((a_last[:n_tasks] >= 0).sum())
        per_shard = np.asarray(shard_bytes, np.float64)   # [S, D]
        full = sdc.full_upload_bytes()
        wire_mean = float(np.mean(wire_bytes))
        out.update({
            "steady_wall_p50_ms": round(float(np.percentile(gaps, 50)), 2),
            **spread_fields("steady_wall", gaps),
            "pipeline_sessions": pipe_sessions,
            "pipeline_wall_ms_total": round(wall_ms, 2),
            # per-shard view: what each device received (its node chunks
            # + its copy of the replicated task/job delta)
            "bytes_per_shard_per_session":
                [int(x) for x in per_shard.mean(axis=0)],
            # host-wire view: the arena accounting (replicated delta
            # counted once — the runtime fans it out)
            "bytes_shipped_per_session": int(wire_mean),
            "bytes_shipped_pct_of_full": round(
                100.0 * wire_mean / max(full, 1), 2),
            "full_upload_bytes": int(full),
            "arena_hit_rate": round(sdc.arena_hit_rate, 3),
            "placed": placed,
        })
        out["ok"] = bool(
            out["subscale_digest_identical"] and out["zero_dirty_ok"]
            and placed > 0 and sdc.arena_hit_rate > 0.5)
    except Exception as e:  # noqa: BLE001 — partial artifact, never abort
        out["error"] = f"{type(e).__name__}: {e}".strip()[:500]
    return out


def config2_parity():
    """500 pods / 50 nodes: rounds solver vs sequential reference greedy."""
    from __graft_entry__ import _params
    from volcano_tpu.ops import flatten_snapshot
    from volcano_tpu.ops.solver import solve_allocate, \
        solve_allocate_sequential

    import jax

    jobs, nodes, tasks, _ = make_problem(50, 100, 5, cpu="16", mem="64Gi")
    arr = flatten_snapshot(jobs, nodes, tasks)
    params = _params(arr)
    d = {k: jax.device_put(v) for k, v in arr.device_dict().items()}
    r1 = solve_allocate(d, params)
    r2 = solve_allocate_sequential(d, params)
    ready1 = np.asarray(r1.job_ready)
    ready2 = np.asarray(r2.job_ready)
    t0 = time.perf_counter()
    np.asarray(solve_allocate(d, params).compact)
    solve_ms = (time.perf_counter() - t0) * 1e3
    # capacity respect for the rounds solver
    a = np.asarray(r1.assigned)
    k = np.asarray(r1.kind)
    used = np.zeros_like(arr.node_idle)
    for i in np.nonzero((a >= 0) & (k == 0))[0]:
        used[a[i]] += arr.task_req[i]
    cap_ok = bool((used <= arr.node_idle + 1e-3).all())
    # characterize the divergence (VERDICT r2 weak #3): which jobs the two
    # solvers disagree on, and whether the swaps trade like for like
    counts = np.bincount(np.asarray(arr.task_job),
                         weights=np.asarray(arr.task_valid))
    swap_sizes = {
        "rounds_only": [int(counts[j])
                        for j in np.nonzero(ready1 & ~ready2)[0]],
        "sequential_only": [int(counts[j])
                            for j in np.nonzero(ready2 & ~ready1)[0]],
    }
    # strict-parity mode (VERDICT r4 weak #4): per_node_cap=2 re-scores
    # nodes after every 2 admissions (the fidelity knob), which converges
    # the rounds solver to the sequential reference's exact job_ready set
    # on this config — the rounds-vs-sequential divergence is a
    # user-selectable speed/fidelity trade, not an implicit one
    r_strict = solve_allocate(d, params, per_node_cap=2, max_rounds=256)
    ready_s = np.asarray(r_strict.job_ready)  # also compiles
    t0 = time.perf_counter()
    np.asarray(solve_allocate(d, params, per_node_cap=2,
                              max_rounds=256).compact)
    strict_ms = (time.perf_counter() - t0) * 1e3
    strict = {
        "mode": "per_node_cap=2,max_rounds=256",
        "job_ready_agreement": round(float((ready_s == ready2).mean()), 4),
        "jobs_ready": int(ready_s.sum()),
        "placed": int((np.asarray(r_strict.assigned) >= 0).sum()),
        "solve_ms": round(strict_ms, 2),
    }

    starvation = _config2_starvation()
    return {
        "tasks": len(tasks), "nodes": 50,
        "strict_parity": strict,
        # under contention the rounds solver and the sequential reference
        # can satisfy different (equally valid) job subsets; report both
        # the overlap and the work each completes, plus the job sizes on
        # each side of the swap (like-for-like swaps = greedy-order
        # deviation, not lost work)
        "job_ready_agreement": round(
            float((ready1 == ready2).mean()), 4),
        "divergent_job_sizes": swap_sizes,
        "jobs_ready_rounds": int(ready1.sum()),
        "jobs_ready_sequential": int(ready2.sum()),
        "placed_rounds": int((a >= 0).sum()),
        "placed_sequential": int((np.asarray(r2.assigned) >= 0).sum()),
        "capacity_respected": cap_ok,
        "solve_ms": round(solve_ms, 2),
        **starvation,
    }


def _config2_starvation():
    """Multi-cycle churn on the contended config-2 shape: completed gangs
    vacate each cycle, the rest re-contend. A job on the losing side of a
    like-for-like swap must not lose repeatedly (VERDICT r3 weak #3):
    starvation_free = every job completed within the ideal cycle count
    (ceil(jobs / first-cycle throughput)) + 1 slack cycle, with per-cycle
    completions never below the sequential oracle's."""
    import math

    from __graft_entry__ import _params
    from volcano_tpu.ops import flatten_snapshot
    from volcano_tpu.ops.solver import solve_allocate, \
        solve_allocate_sequential

    all_jobs, nodes, _, _ = make_problem(50, 100, 5, cpu="16", mem="64Gi")
    order = list(all_jobs)
    pending = set(order)
    waits = {}
    cycle = 0
    first_done = 0
    oracle_ok = True
    while pending and cycle < 12:
        live = [u for u in order if u in pending]
        jobs = {u: all_jobs[u] for u in live}
        tasks = [t for j in jobs.values() for t in j.tasks.values()]
        arr = flatten_snapshot(jobs, nodes, tasks)
        params = _params(arr)
        d = arr.device_dict()
        ready = np.asarray(solve_allocate(d, params).job_ready)
        ready_seq = np.asarray(
            solve_allocate_sequential(d, params).job_ready)
        done = int(ready[:len(jobs)].sum())
        if done < int(ready_seq[:len(jobs)].sum()):
            oracle_ok = False
        if done == 0:
            break  # live-lock; reported via starved count
        if cycle == 0:
            first_done = done
        for idx, u in enumerate(live):
            if ready[idx]:
                waits[u] = cycle
                pending.discard(u)
        cycle += 1
    ideal = math.ceil(len(order) / max(first_done, 1))
    max_wait = max(waits.values()) if waits else -1
    return {
        "churn_cycles_to_drain": cycle,
        "max_wait_cycles": max_wait,
        "ideal_cycles": ideal,
        "starved_jobs": len(pending),
        "per_cycle_ge_sequential": oracle_ok,
        "starvation_free": (not pending and oracle_ok
                            and max_wait <= ideal),
    }


def config4_preempt():
    """2k running pods; a 1k-task high-priority gang triggers the batched
    eviction solve (ops.solve_evict)."""
    from __graft_entry__ import _params
    from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo, TaskStatus
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION
    from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
    from volcano_tpu.ops import flatten_snapshot
    from volcano_tpu.ops.evict import solve_evict_uniform

    n_nodes, n_running, n_claim = 200, 2000, 1000
    nodes = {}
    for i in range(n_nodes):
        rl = {"cpu": "16", "memory": "64Gi", "pods": 110}
        nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                       capacity=dict(rl)))
    low_pg = PodGroup(name="low", namespace="bench",
                      spec=PodGroupSpec(min_member=1))
    low = JobInfo("bench/low", low_pg)
    victims = []
    for i in range(n_running):
        pod = Pod(name=f"low-{i}", namespace="bench",
                  node_name=f"n{i % n_nodes}", phase="Running",
                  annotations={POD_GROUP_ANNOTATION: "low"},
                  containers=[{"requests": {"cpu": "1", "memory": "2Gi"}}])
        t = TaskInfo(pod)
        t.status = TaskStatus.RUNNING
        low.add_task_info(t)
        nodes[f"n{i % n_nodes}"].add_task(t)
        victims.append(t)
    hi_pg = PodGroup(name="hi", namespace="bench",
                     spec=PodGroupSpec(min_member=n_claim))
    hi = JobInfo("bench/hi", hi_pg)
    claimers = []
    for i in range(n_claim):
        pod = Pod(name=f"hi-{i}", namespace="bench",
                  annotations={POD_GROUP_ANNOTATION: "hi"},
                  containers=[{"requests": {"cpu": "2", "memory": "4Gi"}}])
        t = TaskInfo(pod)
        hi.add_task_info(t)
        claimers.append(t)

    arr = flatten_snapshot({hi.uid: hi}, nodes, claimers)
    params = _params(arr)
    # the uniform gang fast path (solve_evict_uniform): one step per job
    from volcano_tpu.ops.evict import pack_victim_arrays
    varrays = pack_victim_arrays(arr, victims, n_claim)

    import jax

    d = {k: jax.device_put(v) for k, v in arr.device_dict().items()}
    v = {k: jax.device_put(np.asarray(val)) for k, val in varrays.items()}
    from volcano_tpu.ops.evict import decode_evict_compact

    res = solve_evict_uniform(d, v, params)  # compile
    res.compact.block_until_ready()
    t0 = time.perf_counter()
    res = solve_evict_uniform(d, v, params)
    assigned, evicted = decode_evict_compact(
        res.compact, d["task_init_req"].shape[0])
    dt = (time.perf_counter() - t0) * 1e3
    return {
        "running": n_running, "claimers": n_claim, "nodes": n_nodes,
        "solve_ms": round(dt, 2),
        "placed": int((assigned[:n_claim] >= 0).sum()),
        "evictions": int((evicted >= 0).sum()),
    }


def config5_hierarchical():
    """5k pods / 1k nodes / 4 weighted queues, cpu+mem+gpu binpack with
    in-kernel queue caps."""
    from __graft_entry__ import _params
    from volcano_tpu.ops import FlattenCache, PackedDeviceCache, \
        flatten_snapshot
    from volcano_tpu.ops.solver import solve_allocate_packed2d

    jobs, nodes, tasks, queues = make_problem(
        1000, 500, 10, cpu="16", mem="64Gi",
        n_queues=4, queue_weights=[1, 2, 3, 4], gpu_every=5)
    fcache, dcache = FlattenCache(), PackedDeviceCache()
    demand_cache = {}
    arr = flatten_snapshot(jobs, nodes, tasks, cache=fcache, queues=queues)
    fill_queue_demand(arr, jobs, demand_cache)
    fbuf, ibuf, layout = arr.packed()
    f2d, i2d = dcache.update(fbuf, ibuf, layout)
    params = _params(arr)
    res = solve_allocate_packed2d(f2d, i2d, layout, params,
                                  use_queue_cap=True)
    res.assigned.block_until_ready()
    t0 = time.perf_counter()
    arr = flatten_snapshot(jobs, nodes, tasks, cache=fcache, queues=queues)
    fill_queue_demand(arr, jobs, demand_cache)
    fbuf, ibuf, layout = arr.packed()
    f2d, i2d = dcache.update(fbuf, ibuf, layout)
    res = solve_allocate_packed2d(f2d, i2d, layout, params,
                                  use_queue_cap=True)
    assigned = np.asarray(res.assigned)
    dt = (time.perf_counter() - t0) * 1e3
    return {
        "tasks": len(tasks), "nodes": 1000, "queues": 4,
        "session_ms": round(dt, 2),
        "placed": int((assigned[:len(tasks)] >= 0).sum()),
    }


def flatten_event_path(n_nodes=2000, n_jobs=1000, tpj=10,
                       big_shape=True):
    """Event-sourced flatten acceptance (ISSUE 11): flatten_ms vs churn
    rate at the 10k x 2k headline shape, comparing the LEDGER-FED cache
    (watch deltas patch the persistent padded buffers, flatten = validate
    epoch + patch dirty rows) against the plain incremental cache (full
    per-cycle re-diff) over IDENTICAL mutation scripts, with packed-buffer
    byte-identity asserted every cycle. Both caches get fresh per-cycle
    task lists, exactly as the allocate action hands them over.

    Churn levels per cycle: quiet (0 deltas), steady (~1% node rows + a
    few podgroup tweaks), heavy (5% node rows + 2% jobs). Acceptance:
    steady-churn event flatten >= 3x faster than incremental, quiet-cycle
    event flatten ~0 ms with ZERO rows patched and the assembly object
    reused. A second leg runs the sharded_100k_10k shape (100k tasks x
    10k nodes) where the O(cluster) scans the event path deletes are
    ~10x the 10k cost."""
    from volcano_tpu.api import TaskInfo, TaskStatus
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION
    from volcano_tpu.models import Pod
    from volcano_tpu.ops import FlattenCache, flatten_snapshot

    def build(nn, nj, tp):
        jobs, nodes, tasks, queues = make_problem(
            nn, nj, tp, n_queues=3, queue_weights=[1, 2, 3])
        tasks_by_job = {}
        for t in tasks:
            tasks_by_job.setdefault(t.job, []).append(t)
        return jobs, nodes, tasks_by_job, queues

    def run_shape(nn, nj, tp, cycles):
        jobs, nodes, tasks_by_job, queues = build(nn, nj, tp)
        node_list = list(nodes.values())
        uids = list(jobs)
        fc_ev = FlattenCache()
        fc_ev.enable_events()
        fc_inc = FlattenCache()
        held = {}

        def mutate(s, node_churn, job_churn):
            """One cycle's mirror deltas, fed to the event ledger exactly
            as the SchedulerCache hooks would."""
            for d in range(node_churn):
                ni = node_list[(s * node_churn + d) % nn]
                t = held.pop(ni.name, None)
                if t is not None:
                    ni.remove_task(t)
                    fc_ev.feed_event("pod", "delete", job=t.job,
                                     node=ni.name)
                else:
                    pod = Pod(name=f"churn-{ni.name}", namespace="bench",
                              node_name=ni.name, phase="Running",
                              annotations={POD_GROUP_ANNOTATION: "j0"},
                              containers=[{"requests": {
                                  "cpu": "1", "memory": "1Gi"}}])
                    t = TaskInfo(pod)
                    t.status = TaskStatus.RUNNING
                    ni.add_task(t)
                    held[ni.name] = t
                    fc_ev.feed_event("pod", "add", job=t.job,
                                     node=ni.name)
            for d in range(job_churn):
                uid = uids[(s * job_churn + d) % nj]
                job = jobs[uid]
                pg = job.pod_group
                pg.spec.min_member = 1 + (s + d) % tp
                job.set_pod_group(pg)
                fc_ev.feed_event("podgroup", "update", job=uid)

        def one_cycle(fc):
            # fresh per-cycle list objects, like the allocate action's
            # _pending_tasks rebuild — the incremental path pays its
            # per-job uid verification, the event path skips it
            grouped = [(j, list(tasks_by_job[u]))
                       for u, j in jobs.items()]
            tasks = [t for _, ts in grouped for t in ts]
            t0 = time.perf_counter()
            arr = flatten_snapshot(jobs, nodes, tasks, cache=fc,
                                   queues=queues, grouped=grouped)
            return (time.perf_counter() - t0) * 1e3, arr

        # warm both caches (cold assembly + one settle cycle)
        for _ in range(2):
            one_cycle(fc_ev)
            one_cycle(fc_inc)

        def run_level(name, node_churn, job_churn, n_cycles):
            ev_ms, inc_ms, rows, modes = [], [], [], {}
            identical = True
            arr_prev = fc_ev._evn["arr"] if fc_ev._evn else None
            reused = True
            for s in range(n_cycles):
                mutate(s, node_churn, job_churn)
                e_ms, arr_e = one_cycle(fc_ev)
                i_ms, arr_i = one_cycle(fc_inc)
                ev_ms.append(e_ms)
                inc_ms.append(i_ms)
                rows.append(fc_ev.last_rows_patched)
                m = fc_ev.last_flatten_mode
                modes[m] = modes.get(m, 0) + 1
                ef, ei, el = arr_e.packed()
                cf, ci, cl = arr_i.packed()
                if not (el == cl and ef.tobytes() == cf.tobytes()
                        and ei.tobytes() == ci.tobytes()):
                    identical = False
                if arr_e is not arr_prev:
                    reused = False
                arr_prev = arr_e
            ev_p50 = float(np.percentile(ev_ms, 50))
            inc_p50 = float(np.percentile(inc_ms, 50))
            return {
                "event_flatten_p50_ms": round(ev_p50, 3),
                "incremental_flatten_p50_ms": round(inc_p50, 3),
                "speedup": round(inc_p50 / max(ev_p50, 1e-6), 2),
                "rows_patched_mean": round(float(np.mean(rows)), 1),
                "modes": modes,
                "identical": identical,
                "assembly_reused": reused,
            }

        steady_nodes = max(nn // 100, 1)
        steady_jobs = max(nj // 250, 1)
        return {
            "tasks": nj * tp, "nodes": nn,
            "quiet": run_level("quiet", 0, 0, max(cycles // 2, 4)),
            "steady": run_level("steady", steady_nodes, steady_jobs,
                                cycles),
            "heavy": run_level("heavy", max(nn // 20, 2),
                               max(nj // 50, 1), max(cycles // 2, 4)),
        }

    shape_10k = run_shape(n_nodes, n_jobs, tpj, cycles=20)
    out = {"shape_10k_2k": shape_10k}
    if big_shape:
        try:
            out["shape_100k_10k"] = run_shape(10_000, 10_000, 10,
                                              cycles=6)
        except Exception as e:  # noqa: BLE001 — partial artifact
            out["shape_100k_10k"] = {"error": f"{type(e).__name__}: "
                                              f"{e}"[:300]}
    q = shape_10k["quiet"]
    s = shape_10k["steady"]
    out["ok"] = bool(
        s["identical"] and q["identical"]
        and s["speedup"] >= 3.0
        and q["rows_patched_mean"] == 0.0
        and q["assembly_reused"]
        and q["event_flatten_p50_ms"] < 1.0)
    out["quiet_flatten_ms"] = q["event_flatten_p50_ms"]
    out["steady_speedup"] = s["speedup"]
    return out


def cycle_start_scale(n_nodes=2000, n_jobs=1000, tpj=10,
                      steady_cycles=12, quiet_cycles=6):
    """Event-sourced ordering acceptance (ISSUE 14): the whole cycle
    start O(changes), not O(pending). Two IDENTICAL rigs — a live
    Scheduler over a stable 10k-pending-task / 1k-job backlog on 2k
    nodes — run the same seeded churn script (podgroup min_member flips,
    priority-class flips, one schedulable mini-wave per cycle), one with
    the OrderCache enabled and one forced onto the legacy full
    sort-every-cycle collection. Reports the ordering pass p50 per churn
    level and arm; ``ok`` enforces (a) bind-for-bind identical decisions
    across the whole run, (b) steady-churn ordering >= 3x faster than
    the full sort, (c) quiet cycles' ordering pass < 1 ms with ZERO
    entries patched and ZERO re-sorts (walk-object reuse)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase, PriorityClass
    from volcano_tpu.scheduler import Scheduler

    def rig(use_order_cache):
        store = ClusterStore()
        cache = SchedulerCache(store)
        cache.binder = FakeBinder()
        cache.evictor = FakeEvictor()
        if not use_order_cache:
            cache.order_cache = None
        cache.run()
        for i in range(3):
            store.apply("queues", build_queue(f"q{i}", weight=i + 1))
        store.create("priorityclasses", PriorityClass("cyc-high", 1000))
        for i in range(n_nodes):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "8", "memory": "64Gi"}))
        # stable unschedulable backlog: per-pod cpu exceeds any node, so
        # the pending problem stays at n_jobs x tpj every cycle with no
        # store churn of its own (the PR-11 condition-write dedup keeps
        # re-reports out of the store)
        for k in range(n_jobs):
            pg = build_pod_group(f"j{k}", "bench", min_member=tpj,
                                 queue=f"q{k % 3}")
            pg.status.phase = PodGroupPhase.PENDING
            store.create("podgroups", pg)
            for i in range(tpj):
                store.create("pods", build_pod(
                    "bench", f"j{k}-{i}", "", "Pending",
                    {"cpu": "20", "memory": "1Gi"}, f"j{k}"))
        return store, cache, Scheduler(cache)

    def churn(store, s):
        """One steady cycle's deltas: ~1% min_member flips + 2 priority
        flips on the backlog, plus a small schedulable wave that BINDS —
        the decisions the identity gate compares."""
        for d in range(max(n_jobs // 100, 1)):
            k = (s * 7 + d * 13) % n_jobs
            pg = store.get("podgroups", f"j{k}", "bench")
            pg.spec.min_member = 1 + (s + d) % tpj
            store.apply("podgroups", pg)
        for d in range(2):
            k = (s * 11 + d * 17) % n_jobs
            pg = store.get("podgroups", f"j{k}", "bench")
            pg.spec.priority_class_name = \
                "" if pg.spec.priority_class_name else "cyc-high"
            store.apply("podgroups", pg)
        pg = build_pod_group(f"w{s}", "bench", min_member=2,
                             queue=f"q{s % 3}")
        pg.status.phase = PodGroupPhase.PENDING
        store.create("podgroups", pg)
        for i in range(2):
            store.create("pods", build_pod(
                "bench", f"w{s}-{i}", "", "Pending",
                {"cpu": "1", "memory": "1Gi"}, f"w{s}"))

    def run_arm(use_order_cache):
        store, cache, sched = rig(use_order_cache)
        sched.run_once()  # cold burst
        sched.run_once()  # settle the first cycle's status writes
        oc = cache.order_cache
        steady_ms, modes = [], {}
        patched = []
        for s in range(steady_cycles):
            churn(store, s)
            sched.run_once()
            t = sched.last_cycle_timing
            steady_ms.append(t.get("order_ms", 0.0))
            modes[t.get("order_mode", "legacy")] = \
                modes.get(t.get("order_mode", "legacy"), 0) + 1
            patched.append(t.get("order_entries_patched", 0.0))
            sched._maybe_gc()
        sched.run_once()  # settle the last wave's writes
        sched.run_once()
        quiet_ms, quiet_modes = [], {}
        quiet_patched = 0.0
        sorts_before = oc.sorts_performed if oc is not None else 0
        for _ in range(quiet_cycles):
            sched.run_once()
            t = sched.last_cycle_timing
            quiet_ms.append(t.get("order_ms", 0.0))
            quiet_modes[t.get("order_mode", "legacy")] = \
                quiet_modes.get(t.get("order_mode", "legacy"), 0) + 1
            quiet_patched += t.get("order_entries_patched", 0.0)
        quiet_sorts = (oc.sorts_performed - sorts_before) \
            if oc is not None else -1
        return {
            "steady_order_p50_ms": round(
                float(np.percentile(steady_ms, 50)), 3),
            "quiet_order_p50_ms": round(
                float(np.percentile(quiet_ms, 50)), 3),
            "steady_modes": modes,
            "quiet_modes": quiet_modes,
            "steady_entries_patched_mean": round(
                float(np.mean(patched)), 1),
            "quiet_entries_patched": quiet_patched,
            "quiet_sorts": quiet_sorts,
            "binds": list(cache.binder.channel),
        }

    cached = run_arm(True)
    legacy = run_arm(False)
    binds_identical = cached["binds"] == legacy["binds"]
    n_binds = len(cached["binds"])
    del cached["binds"], legacy["binds"]
    speedup = round(legacy["steady_order_p50_ms"]
                    / max(cached["steady_order_p50_ms"], 1e-6), 2)
    out = {
        "tasks": n_jobs * tpj, "nodes": n_nodes,
        "event_sourced": cached, "full_sort": legacy,
        "steady_order_speedup": speedup,
        "quiet_order_p50_ms": cached["quiet_order_p50_ms"],
        "binds_identical": binds_identical,
        "binds_compared": n_binds,
        "ok": bool(
            binds_identical and n_binds > 0
            and speedup >= 3.0
            and cached["quiet_order_p50_ms"] < 1.0
            and cached["quiet_entries_patched"] == 0.0
            and cached["quiet_sorts"] == 0
            and set(cached["quiet_modes"]) == {"reuse"}),
    }
    return out


def steady_churn():
    """Sustained-churn throughput (the PR-2 acceptance config): M
    back-to-back full scheduling cycles on a running cluster with ~1%
    churn per cycle PLUS one forced compile-bucket crossing mid-run,
    executed twice over the identical churn script. Reports pods/sec,
    p50/p99 session ms and the solve-compile count observed on the
    session thread after warmup (must be 0: the crossing swaps to the
    pre-warmed variant).

    The steady wave is 6 jobs x 5 pods (pending T flattens to bucket 32);
    the crossing wave is 8 jobs x 5 pods (T -> bucket 40, J -> bucket
    10), both of which the BucketPrewarmer compiles in the background
    from the steady cycles' occupancy trigger. The bench waits (untimed,
    reported) for the prewarm before injecting the crossing wave — the
    lead time a production cluster gets from the 80% trigger."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.models import PodGroupPhase
    from volcano_tpu.ops.precompile import watcher
    from volcano_tpu.scheduler import Scheduler

    n_nodes, base_jobs, tpj = 400, 300, 5
    cycles, crossing_at = 20, 12

    def run(shared_dcache=None):
        store = ClusterStore()
        cache = SchedulerCache(store)
        cache.binder = FakeBinder()
        cache.evictor = FakeEvictor()
        cache.run()
        store.apply("queues", build_queue("q0", weight=1))
        for i in range(n_nodes):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "32", "memory": "128Gi"}))
        if shared_dcache is not None:
            cache.device_cache = shared_dcache
        wave_no = [0]

        def wave(jobs_n):
            for _ in range(jobs_n):
                k = wave_no[0]
                wave_no[0] += 1
                pg = build_pod_group(f"j{k}", "bench", min_member=tpj,
                                     queue="q0")
                pg.status.phase = PodGroupPhase.PENDING
                store.create("podgroups", pg)
                for i in range(tpj):
                    store.create("pods", build_pod(
                        "bench", f"j{k}-{i}", "", "Pending",
                        {"cpu": str(1 + k % 3), "memory": f"{1 + k % 4}Gi"},
                        f"j{k}"))

        sched = Scheduler(cache, prewarm=True)
        # warmup: the base burst (its own bucket) + two steady waves so
        # every steady-shape jit variant is compiled before timing starts
        wave(base_jobs)
        sched.run_once()
        for _ in range(2):
            wave(6)
            sched.run_once()
            sched._maybe_gc()

        lat, compiles, prewarm_wait = [], 0, 0.0
        crossing_ms = None
        cycle_bytes, full_ships = [], 0
        placed0 = len(cache.binder.binds)
        for s in range(cycles):
            if s == crossing_at:
                t0 = time.perf_counter()
                cache.prewarmer.wait(600)  # untimed lead the 80% trigger buys
                prewarm_wait = time.perf_counter() - t0
                wave(8)                    # forced bucket crossing
            else:
                wave(6)
            t0 = time.perf_counter()
            sched.run_once()
            dt = (time.perf_counter() - t0) * 1e3
            lat.append(dt)
            if s == crossing_at:
                crossing_ms = dt
            compiles += int(sched.last_cycle_timing.get(
                "session_compiles", 0))
            t = sched.last_cycle_timing
            if "arena_bytes_shipped" in t:
                cycle_bytes.append(t["arena_bytes_shipped"])
                full_ships += int(t.get("arena_full_ship", 0))
            sched._maybe_gc()
        placed = len(cache.binder.binds) - placed0
        dc = cache.device_cache
        return {
            "pods_per_sec": int(placed / max(sum(lat) / 1e3, 1e-9)),
            "p50_ms": round(float(np.percentile(lat, 50)), 2),
            "p99_ms": round(float(np.percentile(lat, 99)), 2),
            "session_compiles_after_warmup": compiles,
            "crossing_session_ms": round(crossing_ms, 2),
            "prewarm_wait_s": round(prewarm_wait, 2),
            "prewarm_completions": cache.prewarmer.completions,
            "prewarm_failures": cache.prewarmer.failures,
            # arena wire accounting: steady cycles must ship dirty chunks,
            # not padded buffers (full ships = layout changes, i.e. the
            # forced bucket crossing + the first session of the run)
            "bytes_shipped_per_session": int(np.mean(cycle_bytes))
            if cycle_bytes else 0,
            "full_ships": full_ships,
            "arena_hit_rate": round(dc.arena_hit_rate, 3)
            if dc is not None else None,
            "placed": placed,
        }, cache.device_cache

    watcher.install()
    # two reps, the better one reported: the first pays every compile
    # (solver variants + the background warms)
    first, dcache = run()
    second, _ = run(shared_dcache=dcache)
    compiles = (first["session_compiles_after_warmup"]
                + second["session_compiles_after_warmup"])
    return {
        "cycles": cycles,
        "churn_pods_per_cycle": 30,
        "crossing_wave_pods": 40,
        "steady": max(first, second, key=lambda r: r["pods_per_sec"]),
        "pods_per_sec_reps": [first["pods_per_sec"],
                              second["pods_per_sec"]],
        # the acceptance criterion: crossing included, nothing compiled
        # on the session thread once warm
        "zero_session_compiles": compiles == 0,
    }


def chaos_churn():
    """The resilience acceptance run (PR-3): 50 full scheduling cycles on
    a REMOTE-store control plane (StoreServer + RemoteClusterStore-backed
    cache, binds over the wire) with deterministic faults firing through
    cycle 34 — one watch-stream break and one store connection drop per 5
    cycles, plus a 3-cycle device-solve failure burst that opens the
    circuit breaker — executed twice over the identical wave script, with
    and without the faults. Each cycle fully turns over its wave (the
    previous cycle's pods are deleted before the next wave submits), so
    fault-free cycles are state-independent and the post-fault tail is
    comparable bind-for-bind.

    Reports: zero-crash/zero-frozen-mirror booleans, the breaker's
    open -> half-open -> close trace, per-fault outcome fields, p50 with
    faults firing vs the no-fault p50, and whether the post-fault cycles'
    scheduling decisions are byte-identical to the no-fault run."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.cache import FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore, RemoteClusterStore, \
        StoreServer
    from volcano_tpu.models import PodGroupPhase
    from volcano_tpu.resilience import CircuitBreaker, faults
    from volcano_tpu.scheduler import Scheduler

    cycles, fault_until = 50, 35
    n_nodes, jobs_per_wave, tpj = 8, 4, 3
    schedule = []  # (cycle, point)
    for w in range(5, fault_until, 5):
        schedule.append((w, "watch_stream"))
        schedule.append((w + 2, "store_request"))
    for w in (10, 11, 12):
        schedule.append((w, "solver_dispatch"))

    def wait_for(cond, timeout=15.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if cond():
                return True
            time.sleep(0.01)
        return cond()

    def run(inject):
        faults.reset()
        store = ClusterStore()
        server = StoreServer(store).start()
        binds_log = []

        def audit(verb, kind, obj):
            if kind == "pods" and verb == "update" and obj.node_name:
                binds_log.append((f"{obj.namespace}/{obj.name}",
                                  obj.node_name))
            return obj

        store.add_interceptor(audit)
        remote = RemoteClusterStore(server.address, connect_timeout=2.0,
                                    retry_base_s=0.05, retry_cap_s=0.4,
                                    watch_backoff_cap_s=0.3)
        cache = SchedulerCache(remote)
        cache.evictor = FakeEvictor()
        cache.run()
        # cycle-counter breaker clock: cool-down in CYCLES, deterministic
        # regardless of wall-clock jitter (burst 10-12 opens it at 12,
        # the half-open probe lands at 16)
        cycle_no = [0]
        cache.breaker = CircuitBreaker(
            "device-solver", failure_threshold=3, cooldown_s=4,
            clock=lambda: float(cycle_no[0]))
        sched = Scheduler(cache, action_deadline_s=60.0)
        store.apply("queues", build_queue("q0", weight=1))
        for i in range(n_nodes):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "32", "memory": "128Gi"}))

        def submit_wave(s):
            for j in range(jobs_per_wave):
                name = f"w{s}-j{j}"
                pg = build_pod_group(name, "bench", min_member=tpj,
                                     queue="q0")
                pg.status.phase = PodGroupPhase.PENDING
                store.create("podgroups", pg)
                for i in range(tpj):
                    store.create("pods", build_pod(
                        "bench", f"{name}-{i}", "", "Pending",
                        {"cpu": str(1 + j % 3), "memory": "1Gi"}, name))

        def retire_wave(s):
            for j in range(jobs_per_wave):
                name = f"w{s}-j{j}"
                for i in range(tpj):
                    store.delete("pods", f"{name}-{i}", "bench")
                store.delete("podgroups", name, "bench")

        def mirror_synced(s):
            # this wave fully arrived (podgroup object included — a job
            # whose podgroup event is still in flight on a resuming
            # stream has no scheduling spec and would be skipped) AND the
            # previous wave fully left
            for j in range(jobs_per_wave):
                job = cache.jobs.get(f"bench/w{s}-j{j}")
                if job is None or job.pod_group is None \
                        or len(job.tasks) != tpj:
                    return False
            return not any(u.startswith(f"bench/w{s - 1}-")
                           for u in cache.jobs)

        lat, crashes, mirror_stalls = [], 0, 0
        binds_by_cycle = []
        fault_events = []
        fallback_cycles = set()
        try:
            for s in range(cycles):
                cycle_no[0] = s
                if s > 0:
                    retire_wave(s - 1)
                if inject:
                    for (w, point) in schedule:
                        if w == s:
                            faults.arm_once(point)
                            fault_events.append(
                                {"cycle": s, "point": point,
                                 "_log_mark": len(faults.log)})
                submit_wave(s)
                if not wait_for(lambda: mirror_synced(s)):
                    mirror_stalls += 1
                mark = len(binds_log)
                t0 = time.perf_counter()
                try:
                    cache.process_resync_tasks()
                    sched.run_once()
                except Exception:
                    crashes += 1
                lat.append((time.perf_counter() - t0) * 1e3)
                if sched.last_cycle_timing.get("host_fallback"):
                    fallback_cycles.add(s)
                binds_by_cycle.append(sorted(binds_log[mark:]))
                for ev in fault_events:
                    if ev["cycle"] == s:
                        ev["fired"] = any(
                            p == ev["point"]
                            for p, _ in faults.log[ev["_log_mark"]:])
            placed = sum(len(b) for b in binds_by_cycle)
            for ev in fault_events:
                ev.pop("_log_mark", None)
                name = ev["point"]
                if name == "watch_stream":
                    ev["outcome"] = ("resumed" if not remote.watch_failed
                                     else "crash_only")
                elif name == "store_request":
                    ev["outcome"] = ("retried" if crashes == 0
                                     else "cycle_error")
                else:
                    ev["outcome"] = ("host_fallback"
                                     if ev["cycle"] in fallback_cycles
                                     else ("breaker_open_skip"
                                           if not ev["fired"]
                                           else "unknown"))
            trace = [f"{frm}->{to}"
                     for _, frm, to in cache.breaker.transitions]
            return {
                "lat": lat, "crashes": crashes,
                "mirror_stalls": mirror_stalls,
                "watch_failed": remote.watch_failed,
                "watch_resumes": remote.watch_resumes,
                "binds_by_cycle": binds_by_cycle,
                "placed": placed,
                "fallback_cycles": sorted(fallback_cycles),
                "breaker_trace": trace,
                "faults": fault_events,
            }
        finally:
            faults.reset()
            remote.close()
            server.stop()

    chaos = run(inject=True)
    clean = run(inject=False)
    tail = slice(fault_until, cycles)
    post_identical = chaos["binds_by_cycle"][tail] \
        == clean["binds_by_cycle"][tail]
    chaos_p50 = float(np.percentile(chaos["lat"], 50))
    clean_p50 = float(np.percentile(clean["lat"], 50))
    trace = chaos["breaker_trace"]
    return {
        "cycles": cycles,
        "faults_injected": len(chaos["faults"]),
        "faults": chaos["faults"],
        "crashes": chaos["crashes"],
        "mirror_stalls": chaos["mirror_stalls"],
        "mirror_frozen": bool(chaos["watch_failed"]
                              or chaos["mirror_stalls"]),
        "watch_resumes": chaos["watch_resumes"],
        "breaker_trace": trace,
        "breaker_recovered": ("closed->open" in trace
                              and trace[-1].endswith("->closed")),
        "fallback_cycles": chaos["fallback_cycles"],
        "placed": chaos["placed"],
        "placed_no_fault": clean["placed"],
        "p50_ms": round(chaos_p50, 2),
        "p99_ms": round(float(np.percentile(chaos["lat"], 99)), 2),
        "p50_no_fault_ms": round(clean_p50, 2),
        "p50_ratio": round(chaos_p50 / max(clean_p50, 1e-9), 3),
        "post_fault_binds_identical": bool(post_identical),
        # the acceptance line: no crash, no frozen mirror, breaker went
        # open and came back, and the post-fault tail is byte-identical
        "ok": bool(chaos["crashes"] == 0
                   and not chaos["watch_failed"]
                   and chaos["mirror_stalls"] == 0
                   and post_identical
                   and "closed->open" in trace
                   and trace and trace[-1].endswith("->closed")),
    }


def failover():
    """Kill-the-leader takeover latency + warm-vs-cold standby A/B (see
    module docstring). Two ha_scheduler_proc processes contend on a
    1-second lease over a StoreServer; the driver submits fixed gang
    waves, SIGKILLs the leader while a wave is in flight, and reads the
    survivor's pinned first-leader-cycle report (compiles/solve/total)
    plus the bind timestamps from a store interceptor."""
    import os
    import subprocess
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.client import ClusterStore, StoreServer
    from volcano_tpu.client.store import NotFoundError
    from volcano_tpu.models import PodGroupPhase

    LEASE = 1.0
    WARMUP_WAVES, JOBS, TPJ, NODES = 6, 3, 2, 6

    def run(warm: bool):
        store = ClusterStore()
        binds = []  # (t, pod, node) on unbound -> bound transitions

        def audit(verb, kind, obj):
            if kind == "pods" and verb == "update" and obj.node_name:
                prev = store.try_get("pods", obj.name, obj.namespace)
                if prev is None or prev is obj or not prev.node_name:
                    binds.append((time.time(), obj.name, obj.node_name))
            return obj

        store.add_interceptor(audit)
        server = StoreServer(store).start()
        store.apply("queues", build_queue("q0", weight=1))
        for i in range(NODES):
            store.create("nodes", build_node(
                f"n{i}", {"cpu": "16", "memory": "64Gi"}))

        here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tests")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = {}
        for ident in ("ha-a", "ha-b"):
            cmd = [sys.executable,
                   os.path.join(here, "ha_scheduler_proc.py"),
                   "--server", server.address, "--identity", ident,
                   "--period", "0.2", "--lease", str(LEASE),
                   "--renew", "0.75", "--retry", "0.25", "--report"]
            if not warm:
                cmd.append("--cold-standby")
            procs[ident] = subprocess.Popen(
                cmd, env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.STDOUT)

        def submit(s):
            for j in range(JOBS):
                name = f"w{s}-j{j}"
                pg = build_pod_group(name, "bench", min_member=TPJ,
                                     queue="q0")
                pg.status.phase = PodGroupPhase.PENDING
                store.create("podgroups", pg)
                for i in range(TPJ):
                    store.create("pods", build_pod(
                        "bench", f"{name}-{i}", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, name))

        def retire(s):
            for j in range(JOBS):
                name = f"w{s}-j{j}"
                for i in range(TPJ):
                    try:
                        store.delete("pods", f"{name}-{i}", "bench")
                    except NotFoundError:
                        pass
                try:
                    store.delete("podgroups", name, "bench")
                except NotFoundError:
                    pass

        def bound(s):
            return all(
                (p := store.try_get("pods", f"w{s}-j{j}-{i}", "bench"))
                is not None and p.node_name
                for j in range(JOBS) for i in range(TPJ))

        def wait_for(cond, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if cond():
                    return True
                time.sleep(0.02)
            return cond()

        try:
            for s in range(WARMUP_WAVES):
                if s > 0:
                    retire(s - 1)
                submit(s)
                if not wait_for(lambda: bound(s), 180):
                    return {"error": f"warmup wave {s} never bound"}
            # kill the leader while a fresh wave is in flight
            retire(WARMUP_WAVES - 1)
            lease = store.get("leases", "volcano")
            victim = lease.holder_identity
            expiry_at = lease.renew_time + lease.lease_duration_seconds
            survivor = next(i for i in procs if i != victim)
            s = WARMUP_WAVES
            submit(s)
            t_kill = time.time()
            procs[victim].kill()
            if not wait_for(lambda: bound(s), 240):
                return {"error": "post-kill wave never bound",
                        "victim": victim}
            first_bind = min(t for t, _, _ in binds if t > t_kill)
            # the survivor writes its report AFTER run_once returns;
            # the binds land DURING it — wait the report out
            wait_for(lambda: store.try_get(
                "configmaps", f"report-{survivor}", "default") is not None,
                30)
            report = store.try_get("configmaps", f"report-{survivor}",
                                   "default")
            timing = json.loads(report.data["timing"]) if report else {}
            return {
                "victim": victim,
                "survivor": survivor,
                "takeover_from_kill_s": round(first_bind - t_kill, 3),
                "takeover_from_expiry_s": round(
                    first_bind - expiry_at, 3),
                "first_cycle_compiles": timing.get(
                    "first_cycle_compiles", -1.0),
                "first_cycle_solve_ms": round(float(timing.get(
                    "first_cycle_solve_ms", -1.0)), 2),
                "first_cycle_total_ms": round(float(timing.get(
                    "first_cycle_total_ms", -1.0)), 2),
            }
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.terminate()
            for p in procs.values():
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            server.stop()

    warm = run(warm=True)
    cold = run(warm=False)
    ok = ("error" not in warm and "error" not in cold
          and warm["takeover_from_expiry_s"] < LEASE
          and warm["first_cycle_compiles"] == 0.0
          # the cold control proves the compile counter is live: without
          # shadow cycles the first takeover cycle MUST compile
          and cold["first_cycle_compiles"] > 0.0)
    return {
        "lease_duration_s": LEASE,
        "warm": warm,
        "cold": cold,
        # the acceptance line: takeover within one lease duration of
        # expiry, and the warm standby's first cycle compiled NOTHING
        "ok": bool(ok),
    }


def sim_quality():
    """Scheduling-quality A/B on the trace-driven simulator (PR-4
    acceptance config): the SAME seeded workload — >=500 virtual cycles,
    >=5k pods — run against the host oracle, the device solver, and the
    sharded (D=1 mesh) solver, each scored on job wait (mean/p99),
    utilization, Jain fairness across weighted queues, and preemption
    churn. Per-arm fault isolation: one arm crashing records an error
    field, the others' scores survive."""
    from volcano_tpu.sim import run_sim
    from volcano_tpu.sim.workload import Workload, WorkloadSpec

    cycles = 500
    # sized to saturation (~0.9 mean utilization: 14 pods/cycle x ~2.3
    # cpu x ~22 cycle lifetime vs 22x32 cpu) so jobs actually queue —
    # wait_mean ~8 cycles, p99 ~60 on the host arm — and the wait/
    # fairness metrics discriminate between solver arms
    spec = WorkloadSpec(
        seed=123, cycles=cycles, nodes=22, node_cpu="32",
        arrival_rate=4.0, gang_min=2, gang_max=5,
        duration_min=5, duration_max=40,
        queues=(("q0", 1), ("q1", 2), ("q2", 3)))
    workload = Workload(spec)
    out = {"cycles": cycles, "pods": workload.total_pods,
           "jobs": len(workload.events), "seed": spec.seed}
    digests = {}
    for arm, mode in (("host", "host"), ("device", "solver"),
                      ("sharded", "sharded")):
        t0 = time.perf_counter()
        try:
            r = run_sim(workload=workload, cycles=cycles, mode=mode,
                        drain=100)
            digests[arm] = r.digest
            out[arm] = {
                "score": r.score,
                "digest": r.digest,
                "wall_s": round(time.perf_counter() - t0, 1),
            }
        except Exception as e:  # noqa: BLE001 — per-arm isolation
            out[arm] = {"error": f"{type(e).__name__}: {e}"[:300]}
    # do the two device-path arms make identical decisions? (the D=1
    # sharded kernel is proven bitwise-equal at the solve level; this
    # pins it end-to-end through the full cycle)
    if "device" in digests and "sharded" in digests:
        out["device_vs_sharded_identical"] = \
            digests["device"] == digests["sharded"]
    return out


def reschedule_defrag():
    """Defragmentation A/B on the seeded fragmented 500-cycle trace
    (ISSUE 8 acceptance config): the SAME workload run golden
    (no reschedule) and with the global rescheduler enabled, both on the
    binpack conf. Reports utilization / fragmentation_index / wait p99
    per arm plus per-plan budget and cap compliance; ``ok`` asserts the
    acceptance trio (utilization up, fragmentation down, p99 no worse)
    with moves <= budget and per-job caps never exceeded. Per-arm fault
    isolation: one arm crashing records an error field, the other's
    score survives."""
    from volcano_tpu.sim.replay import run_sim
    from volcano_tpu.sim.virtualcluster import BINPACK_CONF
    from volcano_tpu.sim.workload import fragmented_workload

    cycles, nodes, seed = 500, 9, 7
    knobs = {"interval": 5, "max_moves": 8, "max_disruption_per_job": 2}
    out = {"cycles": cycles, "nodes": nodes, "seed": seed, **knobs}
    arms = {}
    for arm, resched in (("golden", None), ("reschedule", knobs)):
        t0 = time.perf_counter()
        try:
            r = run_sim(
                workload=fragmented_workload(seed=seed, cycles=cycles,
                                             nodes=nodes),
                cycles=cycles, scheduler_conf=BINPACK_CONF,
                reschedule=resched)
            arms[arm] = r
            out[arm] = {"score": r.score,
                        "wall_s": round(time.perf_counter() - t0, 1)}
        except Exception as e:  # noqa: BLE001 — per-arm isolation
            out[arm] = {"error": f"{type(e).__name__}: {e}"[:300]}
    if "golden" in arms and "reschedule" in arms:
        g = arms["golden"].score
        r = arms["reschedule"].score
        plans = arms["reschedule"].vc.cache.reschedule_log
        executed = [p for p in plans if p["rejected"] is None]
        out["plans"] = {
            "built": len(plans),
            "executed": len(executed),
            "moves_executed": int(sum(p["executed"] for p in executed)),
            "max_moves_in_plan": max((p["selected"] for p in executed),
                                     default=0),
            "max_disruption": max((p["max_disruption"] for p in executed),
                                  default=0),
            "budget": knobs["max_moves"],
            "per_job_cap": knobs["max_disruption_per_job"],
        }
        out["improved"] = {
            "utilization": r["utilization_mean"] > g["utilization_mean"],
            "fragmentation":
                r["fragmentation_index"] < g["fragmentation_index"],
            "wait_p99_no_worse": r["wait_p99"] <= g["wait_p99"],
            "budget_respected": all(
                p["selected"] <= knobs["max_moves"] for p in plans),
            "caps_respected": all(
                p["max_disruption"] <= knobs["max_disruption_per_job"]
                for p in plans),
            "migrated": r["migrations"] > 0,
        }
        out["ok"] = all(out["improved"].values())
    return out


def store_durability():
    """The durable-store acceptance config (ISSUE 9): (a) churn overhead
    of the WAL vs the in-memory store, per fsync policy, single-op vs
    bulk_apply batches; (b) recovery time vs journal length (pure-WAL
    replay and snapshot+tail); (c) the kill-9 store soak — a durable
    store PROCESS SIGKILLed with a wave's pods committed but unbound,
    restarted on the same port + data dir, scheduler + controllers
    riding through on retry + ``since:`` watch resume — with the
    decision trace compared bind-for-bind to an uninterrupted golden
    run. ``ok`` asserts the soak trio: identical trace, zero lost/dup
    binds, zero crash-only resyncs."""
    import os
    import shutil
    import tempfile
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from helpers import build_pod
    from volcano_tpu.client import ClusterStore, DurableClusterStore

    out = {}
    work = tempfile.mkdtemp(prefix="volcano-store-bench-")
    try:
        # -- (a) churn overhead: create/update/delete cycles ------------
        n_ops = 300

        def churn(store):
            t0 = time.perf_counter()
            for i in range(n_ops // 3):
                pod = build_pod("bench", f"p{i}", "", "Pending",
                                {"cpu": "1"}, "pg")
                store.create("pods", pod)
                pod.node_name = "n0"
                store.update("pods", pod)
                store.delete("pods", f"p{i}", "bench")
            return (n_ops // 3) * 3 / (time.perf_counter() - t0)

        rates = {"memory": churn(ClusterStore())}
        for policy in ("every", "interval", "off"):
            rates[f"wal_{policy}"] = churn(DurableClusterStore(
                os.path.join(work, f"churn-{policy}"), fsync=policy))
        # bulk batches amortize the fsync: one sync per wave
        bulk_store = DurableClusterStore(os.path.join(work, "churn-bulk"),
                                         fsync="every")
        t0 = time.perf_counter()
        for w in range(6):
            bulk_store.bulk_apply(
                [("pods", build_pod("bench", f"w{w}-p{i}", "", "Pending",
                                    {"cpu": "1"}, "pg"), "create")
                 for i in range(50)])
        rates["wal_every_bulk50"] = 300 / (time.perf_counter() - t0)
        out["churn_ops_per_s"] = {k: round(v, 0) for k, v in rates.items()}
        out["wal_overhead_x"] = {
            k: round(rates["memory"] / v, 2)
            for k, v in rates.items() if k != "memory"}

        # -- (b) recovery time vs journal length ------------------------
        recovery = {}
        for n in (1000, 5000):
            d = os.path.join(work, f"rec-{n}")
            s = DurableClusterStore(d, fsync="off",
                                    snapshot_every=10 ** 9)
            for i in range(n):
                s.apply("pods", build_pod("bench", f"p{i % 500}", "",
                                          "Pending", {"cpu": "1"}, "pg"))
            s.close()
            s2 = DurableClusterStore(d)
            recovery[f"wal_{n}_records_ms"] = round(s2.recovery_ms, 1)
        # snapshot + short tail: the compacted steady-state shape
        d = os.path.join(work, "rec-snap")
        s = DurableClusterStore(d, fsync="off", snapshot_every=10 ** 9)
        for i in range(5000):
            s.apply("pods", build_pod("bench", f"p{i % 500}", "",
                                      "Pending", {"cpu": "1"}, "pg"))
        s.snapshot()
        for i in range(100):
            s.apply("pods", build_pod("bench", f"t{i}", "", "Pending",
                                      {"cpu": "1"}, "pg"))
        s.close()
        s2 = DurableClusterStore(d)
        recovery["snapshot_plus_100_tail_ms"] = round(s2.recovery_ms, 1)
        recovery["snapshot_tail_records"] = s2.recovered_records
        out["recovery"] = recovery

        # -- (c) the kill-9 soak vs golden -------------------------------
        from durable_soak import run_store_crash_soak
        waves, kill_at = 5, 2
        golden = run_store_crash_soak(os.path.join(work, "golden"),
                                      waves=waves)
        crash = run_store_crash_soak(os.path.join(work, "crash"),
                                     waves=waves, kill_at_wave=kill_at)
        identical = crash["binds_by_wave"] == golden["binds_by_wave"]
        out["soak"] = {
            "waves": waves, "kill_at_wave": kill_at,
            "store_restart_s": crash["restart_s"],
            "binds": crash["total_binds"],
            "binds_identical_to_golden": bool(identical),
            "lost_binds": crash["lost_binds"],
            "dup_binds": crash["dup_binds"],
            "watch_resumes": crash["watch_resumes"],
            "crash_only_resyncs": crash["crash_only_resyncs"],
            "scheduler_crashes": crash["crashes"],
            "stalls": len(crash["stalls"]) + len(golden["stalls"]),
        }
        out["ok"] = bool(
            identical
            and crash["lost_binds"] == 0 and crash["dup_binds"] == 0
            and crash["crashes"] == 0 and golden["crashes"] == 0
            and crash["watch_resumes"] > 0
            and crash["crash_only_resyncs"] == 0)
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def store_shard_scale():
    """The sharded front-door acceptance config (ISSUE 10). Per arm
    (shards in {1, 4, 8}): the store runs in its OWN process (in-memory,
    a plain StoreServer at shards=1 — the historical path — and a
    ShardRouter above that), 4 writer PROCESSES push chunked bulk pod
    waves in ack mode (tests/store_churn_proc.py; separate processes so
    client encode never shares a GIL with the server or the driver),
    while the driver hosts a mirror counting every event off ONE batched
    bulk_watch stream and a live Scheduler whose RemoteClusterStore
    cache rides the same endpoint — cycle p50 measured idle vs under
    full churn. The burst leg times the BENCH_r03 ``burst_decomp``
    ingest shape (a 10k-pod wave into store + mirror): the historical
    serial per-op path at shards=1 as the baseline vs the chunked
    parallel bulk path per arm. ``ok`` asserts the ISSUE floor at
    shards=8: >= 50k sustained pod-events/sec into the mirror, cycle
    p50 stretched <= 10%, and >= 3x on the burst ingest path vs the
    shards=1 serial baseline. The ``delta8`` arm (ISSUE 16) re-runs the
    proc topology with delta-negotiated watch streams — the shard
    workers emit field-sparse column patches, the mirror and the live
    SchedulerCache apply them straight into the mirrored objects and
    packed arrays — and closes with a per-cycle packed-array
    byte-identity check against an object-path shadow cache on the
    same endpoint."""
    import hashlib
    import os
    import subprocess
    import threading
    TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tests")
    sys.path.insert(0, TESTS)
    from durable_soak import free_port, start_store_proc
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.client import RemoteClusterStore

    WRITERS, WAVES, WAVE = 4, 5, 1250    # 50k churn events per arm
    BURST = 10_000                       # the r03 burst ingest shape

    def p50(ms):
        return round(float(np.percentile(ms, 50)), 2) if ms else None

    def spawn_writers(addr, waves, wave, ns, update=True):
        procs = []
        for w in range(WRITERS):
            cmd = [sys.executable,
                   os.path.join(TESTS, "store_churn_proc.py"),
                   "--addr", addr, "--writer", str(w),
                   "--waves", str(waves), "--wave-size", str(wave),
                   "--namespace", ns]
            if not update:
                cmd.append("--no-update")
            procs.append(subprocess.Popen(
                cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, cwd=os.path.dirname(TESTS)))
        for p in procs:
            line = p.stdout.readline()
            if not line.startswith("READY"):
                raise RuntimeError(f"writer failed to start: {line!r}")
        return procs

    def release_and_join(procs):
        t0 = time.perf_counter()
        for p in procs:
            p.stdin.write("GO\n")
            p.stdin.flush()
        events = 0
        for p in procs:
            parts = p.stdout.readline().split()
            events += int(parts[1])
            p.wait(timeout=30)
        return events, time.perf_counter() - t0, t0

    def one_arm(n_shards, serial_baseline, procs=False, delta=False):
        from volcano_tpu.cache import FakeEvictor, SchedulerCache
        from volcano_tpu.scheduler import Scheduler

        port = free_port()
        server = start_store_proc(port, "", shards=n_shards,
                                  shard_procs=procs)
        addr = f"127.0.0.1:{port}"
        arm = {"shards": n_shards, "procs": procs, "delta": delta}
        dw = {"delta_watch": True} if delta else {}
        clients = []

        def client(**kw):
            # the proc arm's mirror/cache clients route like real
            # deployments: single-key ops direct to the owning worker,
            # watch streams straight off the workers (router bypassed)
            if procs:
                kw.setdefault("direct_watch", True)
            c = RemoteClusterStore(addr, **kw)
            clients.append(c)
            return c

        try:
            # -- the scheduler rides the same endpoint ------------------
            seed = client()
            seed.apply("queues", build_queue("q0", weight=1))
            for i in range(8):
                seed.apply("nodes", build_node(
                    f"n{i}", {"cpu": "32", "memory": "128Gi"}))
            for j in range(4):
                seed.apply("podgroups", build_pod_group(
                    f"job{j}", "bench", min_member=2, queue="q0"))
                for i in range(2):
                    seed.create("pods", build_pod(
                        "bench", f"job{j}-{i}", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, f"job{j}"))
            cache = SchedulerCache(client(**dw))
            cache.evictor = FakeEvictor()
            cache.run()
            cache.wait_for_cache_sync()
            sched = Scheduler(cache)
            sched.run_once()  # warm-up: compiles + binds the workload
            idle = []
            for _ in range(10):
                t0 = time.perf_counter()
                sched.run_once()
                idle.append((time.perf_counter() - t0) * 1e3)
            arm["cycle_p50_idle_ms"] = p50(idle)

            # -- mirror: one batched bulk_watch stream ------------------
            mirror = client(**dw)
            seen = [0]
            churn_done = threading.Event()
            total = WRITERS * WAVES * WAVE * 2  # create + update

            def on_pod(event, obj, old):
                if obj.namespace == "churn":
                    seen[0] += 1
                    if seen[0] >= total:
                        churn_done.set()
            mirror.bulk_watch([("pods", on_pod)])

            # -- churn from writer processes, cycles live ---------------
            writers = spawn_writers(addr, WAVES, WAVE, "churn")
            under = []
            stop = threading.Event()

            def cycles():
                # paced like a real scheduler's period — a hot spin
                # would measure this thread's GIL monopoly, not the
                # store's effect on a cycle
                while not stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        sched.run_once()
                    except Exception:  # noqa: BLE001 — stretch data only
                        break
                    under.append((time.perf_counter() - t0) * 1e3)
                    stop.wait(0.05)

            cyc = threading.Thread(target=cycles)
            cyc.start()
            applied, applied_s, t0 = release_and_join(writers)
            churn_done.wait(timeout=120.0)
            mirrored_s = time.perf_counter() - t0
            stop.set()
            cyc.join()
            arm["churn_events_applied"] = applied
            arm["churn_events_mirrored"] = seen[0]
            arm["churn_mirror_complete"] = churn_done.is_set()
            arm["churn_applied_events_per_sec"] = round(
                applied / applied_s)
            arm["churn_events_per_sec"] = round(seen[0] / mirrored_s)
            arm["cycle_p50_churn_ms"] = p50(under)
            arm["cycle_stretch"] = (
                round(arm["cycle_p50_churn_ms"]
                      / arm["cycle_p50_idle_ms"], 3)
                if under and arm["cycle_p50_idle_ms"] else None)
            # wire bytes the mirror stream actually read — tracked on
            # every arm so the delta arm's byte claim is like-for-like
            ws = mirror.delta_stats
            arm["churn_watch_bytes"] = (
                ws["bytes_delta"] + ws["bytes_object"])
            if delta:
                arm["delta_frames"] = ws["frames"]
                arm["delta_events"] = ws["events"]
                arm["delta_fields"] = ws["fields"]
                arm["delta_vocab"] = ws["vocab"]
                arm["delta_fallbacks"] = dict(ws["fallbacks"])
                arm["delta_decode_ms"] = round(ws["decode_ms"], 2)
                arm["delta_apply_ms"] = round(ws["apply_ms"], 2)

            # -- burst: the r03 burst_decomp ingest shape ---------------
            bseen = [0]
            burst_done = threading.Event()

            def on_burst(event, obj, old):
                if obj.namespace == "burst":
                    bseen[0] += 1
                    if bseen[0] >= BURST:
                        burst_done.set()
            mirror.bulk_watch([("pods", on_burst)])
            writers = spawn_writers(addr, 1, BURST // WRITERS, "burst",
                                    update=False)
            applied, burst_s, t0 = release_and_join(writers)
            burst_done.wait(timeout=60.0)
            arm["burst_pods_applied"] = applied
            arm["burst_bulk_pods_per_sec"] = round(applied / burst_s)
            arm["burst_mirrored_pods_per_sec"] = round(
                bseen[0] / (time.perf_counter() - t0))
            if serial_baseline:
                # the historical ingest path: one client, one op per pod
                c = client()
                n = 2000
                t0 = time.perf_counter()
                for i in range(n):
                    pod = build_pod("serial", f"s{i}", "", "Pending",
                                    {"cpu": "1"}, "")
                    pod.scheduler_name = "churn-rig"
                    c.create("pods", pod)
                arm["burst_serial_pods_per_sec"] = round(
                    n / (time.perf_counter() - t0))

            if delta:
                # -- per-cycle packed-array byte identity (ISSUE 16) ----
                # an object-path shadow cache rides the same live
                # endpoint; each verification cycle churns the
                # scheduler-owned pods through delta-eligible fields
                # (phase, priority, labels), quiesces both mirrors on
                # the round marker, and the packed solver buffers must
                # hash identically — the delta path must not even
                # reorder a dict entry
                from volcano_tpu.ops import flatten_snapshot

                def digest(c):
                    sn = c.snapshot()
                    tasks = [t for j in sn.jobs.values()
                             for t in j.tasks.values()]
                    fbuf, ibuf, layout = flatten_snapshot(
                        sn.jobs, sn.nodes, tasks).packed()
                    h = hashlib.sha256()
                    h.update(fbuf.tobytes())
                    h.update(ibuf.tobytes())
                    h.update(repr(layout).encode())
                    return h.hexdigest()

                shadow = SchedulerCache(client())
                shadow.evictor = FakeEvictor()
                shadow.run()
                shadow.wait_for_cache_sync()
                names = [f"job{j}-{i}"
                         for j in range(4) for i in range(2)]
                rounds, identical = 5, 0
                for r in range(rounds):
                    mark = f"r{r}"
                    for nm in names:
                        cur = seed.get("pods", nm, namespace="bench")
                        cur.phase = ("Running" if r % 2 == 0
                                     else "Pending")
                        cur.priority = (r + 1) % 3 + 1
                        cur.labels = dict(cur.labels or {}, round=mark)
                        seed.update("pods", cur)

                    def settled(c):
                        with c.cluster.locked():
                            got = [t for j in c.jobs.values()
                                   for t in j.tasks.values()
                                   if t.pod.namespace == "bench"]
                            return len(got) == len(names) and all(
                                (t.pod.labels or {}).get("round")
                                == mark for t in got)
                    deadline = time.time() + 30
                    while time.time() < deadline and not (
                            settled(cache) and settled(shadow)):
                        time.sleep(0.02)
                    if digest(cache) == digest(shadow):
                        identical += 1
                arm["packed_identity_cycles"] = \
                    f"{identical}/{rounds}"
                arm["packed_identity"] = identical == rounds
                cst = cache.cluster.delta_stats
                arm["cache_delta_events"] = cst["events"]
                arm["cache_delta_fallbacks"] = \
                    dict(cst["fallbacks"])
            return arm
        finally:
            for c in clients:
                try:
                    c.close()
                except Exception:  # noqa: BLE001
                    pass
            server.kill()
            try:
                server.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass

    # the rig is 6 cooperating PROCESSES (server, driver, 4 writers) —
    # plus, in the proc_shards arm, one process PER SHARD behind the
    # thin router: sustained events/sec scales with cores, so the
    # artifact records how many this box had — on 1 core the 50k floor
    # is unreachable by construction and the per-arm comparison is the
    # signal
    out = {"arms": {}, "cpu_count": os.cpu_count()}
    serial_rate = None
    for label, n_shards, procs, delta in (
            ("1", 1, False, False), ("4", 4, False, False),
            ("8", 8, False, False), ("proc8", 8, True, False),
            ("delta8", 8, True, True)):
        arm = _run_config(f"store_shard_scale[{label}]",
                          lambda n=n_shards, p=procs, d=delta:
                          one_arm(n, n == 1 and not p, procs=p,
                                  delta=d))
        out["arms"][label] = arm
        if label == "1" and "burst_serial_pods_per_sec" in arm:
            serial_rate = arm["burst_serial_pods_per_sec"]
    a8 = out["arms"].get("8", {})
    ap = out["arms"].get("proc8", {})
    ad = out["arms"].get("delta8", {})
    if serial_rate and a8.get("burst_bulk_pods_per_sec"):
        out["burst_ingest_speedup_vs_serial1"] = round(
            a8["burst_bulk_pods_per_sec"] / serial_rate, 2)
    if serial_rate and ap.get("burst_bulk_pods_per_sec"):
        out["proc_burst_ingest_speedup_vs_serial1"] = round(
            ap["burst_bulk_pods_per_sec"] / serial_rate, 2)
    # ISSUE 13 acceptance: real processes beat the one-GIL shards=8 arm
    # on sustained mirror events/sec AND burst ingest, without
    # stretching the live scheduler's cycle more — and the absolute 50k
    # events/sec floor is gated honestly (cpu_count recorded: the
    # multi-process rig is the first topology that can actually scale
    # past one core, but only on a rig that HAS the cores)
    out["proc_beats_inproc"] = bool(
        ap.get("churn_mirror_complete") and a8.get("churn_mirror_complete")
        and (ap.get("churn_events_per_sec") or 0)
        >= (a8.get("churn_events_per_sec") or 0)
        and (ap.get("burst_bulk_pods_per_sec") or 0)
        >= (a8.get("burst_bulk_pods_per_sec") or 0)
        and (ap.get("cycle_stretch") or 9)
        <= (a8.get("cycle_stretch") or 0))
    # bench honesty (ISSUE 14 satellite): the absolute 50k events/sec
    # and cycle-stretch floors need this rig's ~13 processes to actually
    # run in parallel — on a box without the cores they are a rig
    # limitation, not a regression. They split into `core_bound` (values
    # + floors recorded next to cpu_count) and gate `ok` only on rigs
    # that can prove them; the relative comparisons gate everywhere.
    # ISSUE 16 acceptance: the delta-framed arm's mirror ingests >= 5x
    # the object-path proc arm's events/sec (10x the stretch target) —
    # a throughput floor, so it rides the same core_bound honesty rule
    # as the 50k floor — and the per-cycle packed-array byte-identity
    # check (gated everywhere: identity is not a function of cores)
    # must pass with ZERO delta fallbacks mid-churn (a silent demotion
    # to object frames would invalidate the speedup claim)
    if ad.get("churn_events_per_sec") and ap.get("churn_events_per_sec"):
        out["delta_ingest_speedup_vs_proc8"] = round(
            ad["churn_events_per_sec"] / ap["churn_events_per_sec"], 2)
    if ad.get("churn_watch_bytes") and ap.get("churn_watch_bytes"):
        out["delta_wire_bytes_ratio"] = round(
            ap["churn_watch_bytes"] / ad["churn_watch_bytes"], 2)
    floors = {
        "proc_churn_events_per_sec": ap.get("churn_events_per_sec"),
        "proc_cycle_stretch": ap.get("cycle_stretch"),
        "floor_events_per_sec": 50_000,
        "floor_cycle_stretch": 1.10,
        "met": bool((ap.get("churn_events_per_sec") or 0) >= 50_000
                    and (ap.get("cycle_stretch") or 9) <= 1.10),
        "delta_ingest_speedup_vs_proc8":
            out.get("delta_ingest_speedup_vs_proc8"),
        "floor_delta_ingest_speedup": 5.0,
        "delta_met": bool(
            (out.get("delta_ingest_speedup_vs_proc8") or 0) >= 5.0),
    }
    capable_rig = (out["cpu_count"] or 1) >= 8
    out["core_bound"] = None if capable_rig else floors
    out["ok"] = bool(
        out["proc_beats_inproc"]
        and (out.get("proc_burst_ingest_speedup_vs_serial1") or 0)
        >= 3.0
        and ad.get("churn_mirror_complete")
        and ad.get("packed_identity")
        and not (ad.get("delta_fallbacks") or {})
        and not (ad.get("cache_delta_fallbacks") or {})
        and (floors["met"] and floors["delta_met"]
             or not capable_rig))
    return out


def read_replica_fanout():
    """The read-replica acceptance config (ISSUE 12). Per arm (replicas
    in {0, 1, 2}): a DURABLE primary store runs in its own process, a
    live paced Scheduler in the driver rides it, and the read tier —
    WATCHERS watch streams + list storms, generated by
    tests/watch_storm_proc.py in SEPARATE processes so fan-out cost
    never shares a GIL with driver or server — attaches to the primary
    (arm 0) or to N replica processes (tests/replica_proc.py) tailing
    the primary's shipped WAL. Two writer processes churn pods
    throughout. Reported per arm: scheduler cycle p50 idle vs under the
    storm (stretch), read-tier events/sec + lists/sec, and — replica
    arms — apply lag in records sampled against the primary's rv
    (p50/p99, reported honestly). ``ok`` enforces the ISSUE bound:
    with the storm routed to replicas the scheduler's cycle p50
    stretches <= 1.05x idle (the primary-only arm records its own
    degradation for contrast).

    The ``tree_depth2`` arm (ISSUE 17) rebuilds the rig as a fan-out
    TREE — primary -> r1 -> (r2a, r2b) — with a 10x watcher storm on
    the leaves, the scheduler reading from a leaf via ReadTierStore,
    and two writer phases (no-storm, under-storm) whose events/sec
    ratio is the flatness signal; ``tree_ok`` additionally demands
    byte-identical mirrors at every depth, zero primary read-lane
    requests for tree traffic, and replica-fed scheduler binds
    identical to the primary-fed golden."""
    import os
    import shutil
    import subprocess
    import tempfile
    import threading
    TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tests")
    sys.path.insert(0, TESTS)
    from durable_soak import free_port, start_store_proc
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.client import RemoteClusterStore

    WATCHERS = 200                  # the ISSUE floor, spread over targets
    LIST_THREADS = 4
    WRITERS, WAVES, WAVE = 2, 1, 300   # 1200 churn events per arm

    def pct(ms, q):
        return round(float(np.percentile(ms, q)), 2) if ms else None

    def wait_ready(proc, what):
        deadline = time.time() + 60
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("READY"):
                return
            if proc.poll() is not None:
                break
        raise RuntimeError(f"{what} failed to start")

    def rv_scalar(rv):
        # a multi-process router reports {shard: rv}; per-shard rvs sum
        # to the total committed mutations (shards=1: the one lineage)
        return sum(rv.values()) if isinstance(rv, dict) else rv

    def one_arm(n_replicas, proc_primary=False):
        from volcano_tpu.cache import FakeEvictor, SchedulerCache
        from volcano_tpu.scheduler import Scheduler

        work = tempfile.mkdtemp(prefix="volcano-replica-bench-")
        pport = free_port()
        server = start_store_proc(pport, os.path.join(work, "pdata"),
                                  fsync="off", shard_procs=proc_primary)
        addr = f"127.0.0.1:{pport}"
        arm = {"replicas": n_replicas, "proc_primary": proc_primary}
        clients = []
        procs = [server]

        def client(a=addr, **kw):
            c = RemoteClusterStore(a, **kw)
            clients.append(c)
            return c

        try:
            # -- the scheduler rides the primary ------------------------
            seed = client()
            seed.apply("queues", build_queue("q0", weight=1))
            for i in range(8):
                seed.apply("nodes", build_node(
                    f"n{i}", {"cpu": "32", "memory": "128Gi"}))
            for j in range(4):
                seed.apply("podgroups", build_pod_group(
                    f"job{j}", "bench", min_member=2, queue="q0"))
                for i in range(2):
                    seed.create("pods", build_pod(
                        "bench", f"job{j}-{i}", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, f"job{j}"))
            cache = SchedulerCache(client())
            cache.evictor = FakeEvictor()
            cache.run()
            cache.wait_for_cache_sync()
            sched = Scheduler(cache)
            sched.run_once()  # warm-up: compiles + binds the workload
            idle = []
            for _ in range(10):
                t0 = time.perf_counter()
                sched.run_once()
                idle.append((time.perf_counter() - t0) * 1e3)
            arm["cycle_p50_idle_ms"] = pct(idle, 50)

            # -- the read tier: primary, or N WAL-shipped replicas ------
            targets = []
            for r in range(n_replicas):
                rport = free_port()
                cmd = [sys.executable,
                       os.path.join(TESTS, "replica_proc.py"),
                       "--primary", addr, "--port", str(rport)]
                if proc_primary:
                    # tail the shard WORKER directly (resolved via the
                    # router's topology op): ship bytes never traverse
                    # the router process
                    cmd.append("--topology-direct")
                rp = subprocess.Popen(
                    cmd,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True, cwd=os.path.dirname(TESTS))
                wait_ready(rp, f"replica {r}")
                procs.append(rp)
                targets.append(f"127.0.0.1:{rport}")
            if not targets:
                targets = [addr]

            storms = []
            share = WATCHERS // len(targets)
            for t in targets:
                sp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "watch_storm_proc.py"),
                     "--addr", t, "--watchers", str(share),
                     "--list-threads",
                     str(LIST_THREADS // len(targets) or 1)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                wait_ready(sp, "watch storm")
                procs.append(sp)
                storms.append(sp)

            # -- churn + lag sampling + paced cycles --------------------
            writers = []
            for w in range(WRITERS):
                wp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "store_churn_proc.py"),
                     "--addr", addr, "--writer", str(w),
                     "--waves", str(WAVES), "--wave-size", str(WAVE)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                wait_ready(wp, f"writer {w}")
                procs.append(wp)
                writers.append(wp)

            prv_info = client()
            rep_info = [client(t) for t in targets] if n_replicas else []
            lag_samples = []
            stop = threading.Event()

            def sample_lag():
                while not stop.is_set():
                    try:
                        prv = rv_scalar(
                            prv_info._request({"op": "store_info"})["rv"])
                        for ri in rep_info:
                            arv = rv_scalar(ri._request(
                                {"op": "store_info"})["rv"])
                            lag_samples.append(max(0, prv - arv))
                    except Exception:  # noqa: BLE001 — sampling only
                        pass
                    stop.wait(0.05)

            under = []

            def cycles():
                # paced like a real scheduler period — a hot spin would
                # measure this thread's GIL monopoly, not the read storm
                while not stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        sched.run_once()
                    except Exception:  # noqa: BLE001 — stretch data only
                        break
                    under.append((time.perf_counter() - t0) * 1e3)
                    stop.wait(0.05)

            threads = [threading.Thread(target=cycles)]
            if rep_info:
                threads.append(threading.Thread(target=sample_lag))
            for t in threads:
                t.start()
            t0 = time.perf_counter()
            for sp in storms:
                sp.stdin.write("GO\n")
                sp.stdin.flush()
            for wp in writers:
                wp.stdin.write("GO\n")
                wp.stdin.flush()
            applied = 0
            for wp in writers:
                parts = wp.stdout.readline().split()
                applied += int(parts[1])
                wp.wait(timeout=120)
            churn_s = time.perf_counter() - t0

            # let the read tier drain: replicas must catch the primary
            def drained():
                try:
                    prv = rv_scalar(
                        prv_info._request({"op": "store_info"})["rv"])
                    return all(
                        rv_scalar(ri._request({"op": "store_info"})["rv"])
                        == prv for ri in rep_info)
                except Exception:  # noqa: BLE001
                    return False

            deadline = time.time() + 150
            while rep_info and not drained() and time.time() < deadline:
                time.sleep(0.05)
            time.sleep(0.5)
            stop.set()
            for t in threads:
                t.join()

            events = lists = list_errors = 0
            for sp in storms:
                sp.stdin.write("STOP\n")
                sp.stdin.flush()
                parts = sp.stdout.readline().split()
                events += int(parts[1])
                lists += int(parts[2])
                list_errors += int(parts[3])
                sp.wait(timeout=30)
            arm["churn_events_applied"] = applied
            arm["churn_s"] = round(churn_s, 2)
            # the sharpest primary-relief signal on any core budget:
            # with the storm ON the primary, writer throughput collapses
            # (every commit fans out to 200 watch queues in the primary
            # process); with replicas absorbing the fan-out it does not
            arm["writer_events_per_sec"] = round(applied / churn_s)
            arm["watchers"] = share * len(targets)
            arm["read_tier_events"] = events
            arm["read_tier_events_per_sec"] = round(events / churn_s)
            arm["lists_done"] = lists
            arm["list_errors"] = list_errors
            arm["cycle_p50_storm_ms"] = pct(under, 50)
            arm["cycle_stretch"] = (
                round(arm["cycle_p50_storm_ms"]
                      / arm["cycle_p50_idle_ms"], 3)
                if under and arm["cycle_p50_idle_ms"] else None)
            if rep_info:
                arm["replica_lag_records_p50"] = pct(lag_samples, 50)
                arm["replica_lag_records_p99"] = pct(lag_samples, 99)
                arm["replica_caught_up"] = drained()
            # the bench workload's bind map: the cross-arm golden for
            # the tree arm's scheduler-off-the-primary decisions
            arm["binds"] = {p.name: p.node_name
                            for p in seed.list("pods", namespace="bench")}
            return arm
        finally:
            for c in clients:
                try:
                    c.close()
                except Exception:  # noqa: BLE001
                    pass
            for proc in procs:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
            shutil.rmtree(work, ignore_errors=True)

    def tree_arm():
        """The depth-2 fan-out tree (ISSUE 17): primary -> r1 ->
        (r2a, r2b), a 10x watcher storm (vs the ISSUE-12 floor) landing
        ONLY on the leaves, the scheduler reading from a leaf through a
        ReadTierStore (mutations still to the primary), and the
        primary's own per-op request counters as the ground truth that
        the tree absorbed every read. Two writer phases — no-storm,
        then under-storm — make the writer-throughput stretch direct;
        per-depth staleness is sampled against the primary's rv."""
        from volcano_tpu.cache import FakeEvictor, SchedulerCache
        from volcano_tpu.client.codec import encode as _enc
        from volcano_tpu.client.readtier import ReadTierStore
        from volcano_tpu.scheduler import Scheduler

        TREE_WATCHERS = WATCHERS * 10
        TREE_WAVE = 150          # 2 writers x (create+update) per phase
        work = tempfile.mkdtemp(prefix="volcano-tree-bench-")
        pport = free_port()
        server = start_store_proc(pport, os.path.join(work, "pdata"),
                                  fsync="off")
        addr = f"127.0.0.1:{pport}"
        arm = {"tree": "primary->r1->(r2a,r2b)",
               "watchers_target": TREE_WATCHERS}
        clients = []
        procs = [server]

        def client(a=addr, **kw):
            c = RemoteClusterStore(a, **kw)
            clients.append(c)
            return c

        def ready_parts(proc, what, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                line = proc.stdout.readline()
                if line.startswith("READY"):
                    return line.split()
                if proc.poll() is not None:
                    break
            raise RuntimeError(f"{what} failed to start")

        def start_replica(upstream):
            rport = free_port()
            rp = subprocess.Popen(
                [sys.executable,
                 os.path.join(TESTS, "replica_proc.py"),
                 "--primary", upstream, "--port", str(rport)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=os.path.dirname(TESTS))
            ready_parts(rp, f"replica@{upstream}", 180)
            procs.append(rp)
            return f"127.0.0.1:{rport}"

        def run_writers(writer_ids):
            ws = []
            for w in writer_ids:
                wp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "store_churn_proc.py"),
                     "--addr", addr, "--writer", str(w),
                     "--waves", "1", "--wave-size", str(TREE_WAVE)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                ready_parts(wp, f"writer {w}", 60)
                procs.append(wp)
                ws.append(wp)
            t0 = time.perf_counter()
            for wp in ws:
                wp.stdin.write("GO\n")
                wp.stdin.flush()
            applied = 0
            for wp in ws:
                applied += int(wp.stdout.readline().split()[1])
                wp.wait(timeout=300)
            return applied, time.perf_counter() - t0

        try:
            seed = client()
            seed.apply("queues", build_queue("q0", weight=1))
            for i in range(8):
                seed.apply("nodes", build_node(
                    f"n{i}", {"cpu": "32", "memory": "128Gi"}))
            for j in range(4):
                seed.apply("podgroups", build_pod_group(
                    f"job{j}", "bench", min_member=2, queue="q0"))
                for i in range(2):
                    seed.create("pods", build_pod(
                        "bench", f"job{j}-{i}", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, f"job{j}"))

            r1 = start_replica(addr)
            r2a = start_replica(r1)
            r2b = start_replica(r1)
            info_p = client()
            info_by_depth = {1: [client(r1)],
                             2: [client(r2a), client(r2b)]}

            def rv_of(c):
                return rv_scalar(c._request({"op": "store_info"})["rv"])

            def tree_caught_up():
                try:
                    prv = rv_of(info_p)
                    return all(rv_of(c) == prv
                               for cs in info_by_depth.values()
                               for c in cs)
                except Exception:  # noqa: BLE001
                    return False

            deadline = time.time() + 120
            while not tree_caught_up() and time.time() < deadline:
                time.sleep(0.05)

            # -- the scheduler rides the READ TIER: list/watch from a
            # leaf, binds to the primary, read-your-writes via min_rv
            rt = ReadTierStore(client(), client(r2a))
            cache = SchedulerCache(rt)
            cache.evictor = FakeEvictor()
            cache.run()
            cache.wait_for_cache_sync()
            sched = Scheduler(cache)

            def all_bound():
                pods = seed.list("pods", namespace="bench")
                return pods and all(p.node_name for p in pods)

            deadline = time.time() + 120
            while not all_bound() and time.time() < deadline:
                sched.run_once()
                time.sleep(0.05)
            arm["binds"] = {p.name: p.node_name
                            for p in seed.list("pods",
                                               namespace="bench")}
            arm["scheduler_reads_replica"] = rt.reads_replica
            arm["scheduler_read_fallbacks"] = rt.read_fallbacks

            # -- phase 1: writers with the tree attached, NO storm
            applied0, dt0 = run_writers((0, 1))
            arm["writer_events_per_sec_no_storm"] = round(applied0 / dt0)

            # read-lane ground truth from here on: the storm phase must
            # add ZERO of these on the primary
            def read_lane():
                reqs = (info_p._request({"op": "store_info"})
                        .get("requests") or {})
                return {op: int(reqs.get(op, 0))
                        for op in ("list", "get", "watch", "bulk_watch")}

            lane0 = read_lane()

            # -- the storm: TREE_WATCHERS split across the two leaves
            storms = []
            watchers_live = 0
            for t in (r2a, r2b):
                sp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "watch_storm_proc.py"),
                     "--addr", t,
                     "--watchers", str(TREE_WATCHERS // 2),
                     "--list-threads", "2"],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                parts = ready_parts(sp, "tree watch storm", 300)
                watchers_live += int(parts[1])
                procs.append(sp)
                storms.append(sp)
            arm["watchers_live"] = watchers_live

            lag = {1: [], 2: []}
            stop = threading.Event()

            def sample_lag():
                while not stop.is_set():
                    try:
                        prv = rv_of(info_p)
                        for depth, cs in info_by_depth.items():
                            for c in cs:
                                lag[depth].append(
                                    max(0, prv - rv_of(c)))
                    except Exception:  # noqa: BLE001 — sampling only
                        pass
                    stop.wait(0.05)

            sampler = threading.Thread(target=sample_lag)
            sampler.start()
            for sp in storms:
                sp.stdin.write("GO\n")
                sp.stdin.flush()
            # -- phase 2: the same writer volume under the tree storm
            applied1, dt1 = run_writers((2, 3))
            arm["writer_events_per_sec_storm"] = round(applied1 / dt1)
            arm["writer_stretch"] = (
                round((applied0 / dt0) / (applied1 / dt1), 3)
                if applied1 else None)

            # in-storm drain: a capable rig catches the primary with
            # all 2000 watchers still subscribed; a 1-core host can
            # legitimately still be fanning deliveries out, so after
            # the grace window release the storm and require full
            # catch-up (zero lost records) before the identity check
            deadline = time.time() + 60
            while not tree_caught_up() and time.time() < deadline:
                time.sleep(0.05)
            arm["tree_caught_up_in_storm"] = tree_caught_up()
            stop.set()
            sampler.join()
            events = 0
            for sp in storms:
                sp.stdin.write("STOP\n")
                sp.stdin.flush()
                events += int(sp.stdout.readline().split()[1])
                sp.wait(timeout=60)
            arm["read_tier_events"] = events
            deadline = time.time() + 180
            while not tree_caught_up() and time.time() < deadline:
                time.sleep(0.05)
            arm["tree_caught_up"] = tree_caught_up()
            for depth in (1, 2):
                arm[f"lag_records_depth{depth}_p50"] = pct(lag[depth], 50)
                arm[f"lag_records_depth{depth}_p99"] = pct(lag[depth], 99)
            lane1 = read_lane()
            arm["primary_read_lane_delta"] = {
                op: lane1[op] - lane0[op] for op in lane1}
            arm["primary_read_lane_zero"] = all(
                v == 0 for v in arm["primary_read_lane_delta"].values())

            # -- byte identity: every mirror in the tree vs the primary
            def wire_dump(c):
                objs = sorted(c.list("pods"),
                              key=lambda o: ((o.namespace or ""), o.name))
                return [_enc(o) for o in objs]

            golden = wire_dump(info_p)
            arm["pods_total"] = len(golden)
            arm["mirrors_identical"] = all(
                wire_dump(c) == golden
                for cs in info_by_depth.values() for c in cs)
            return arm
        finally:
            for c in clients:
                try:
                    c.close()
                except Exception:  # noqa: BLE001
                    pass
            for proc in procs:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
            shutil.rmtree(work, ignore_errors=True)

    # the rig is up to 8 cooperating processes; cycle stretch vs the
    # read storm is the signal, and it depends on the storm NOT sharing
    # the scheduler's GIL — record the core budget honestly
    out = {"arms": {}, "cpu_count": os.cpu_count()}
    for label, n_replicas, proc in (
            ("replicas_0", 0, False), ("replicas_1", 1, False),
            ("replicas_2", 2, False), ("replicas_1_proc", 1, True)):
        out["arms"][label] = _run_config(
            f"read_replica_fanout[{label}]",
            lambda n=n_replicas, p=proc: one_arm(n, proc_primary=p))
    out["arms"]["tree_depth2"] = _run_config(
        "read_replica_fanout[tree_depth2]", tree_arm)
    r1 = out["arms"].get("replicas_1", {})
    r0 = out["arms"].get("replicas_0", {})
    r1p = out["arms"].get("replicas_1_proc", {})
    out["primary_only_stretch"] = r0.get("cycle_stretch")
    # the multi-process arm: the primary's one shard is a real worker
    # process and the replica tails ITS endpoint directly, so ship
    # fan-out shares neither the router's nor the scheduler's GIL —
    # gated with the same stretch floor, recorded per cpu_count
    # bench honesty (ISSUE 14 satellite): the stretch <= 1.05 floor
    # requires the co-located replica/storm processes to NOT share the
    # scheduler's core — on a 1-core rig it is core-bound by
    # construction, so it moves into `core_bound` (values recorded) and
    # gates `ok` only on rigs with the cores to isolate properly
    floors = {
        "replicas_1_cycle_stretch": r1.get("cycle_stretch"),
        "replicas_1_proc_cycle_stretch": r1p.get("cycle_stretch"),
        "floor_cycle_stretch": 1.05,
        "met": bool((r1.get("cycle_stretch") or 9) <= 1.05),
    }
    tree = out["arms"].get("tree_depth2", {})
    tree_floors = {
        "tree_writer_stretch": tree.get("writer_stretch"),
        "floor_writer_stretch": 1.10,
        "tree_stretch_met": bool(
            (tree.get("writer_stretch") or 9) <= 1.10),
    }
    capable_rig = (out["cpu_count"] or 1) >= 4
    out["core_bound"] = (None if capable_rig
                         else {**floors, **tree_floors})
    out["proc_arm_ok"] = bool(
        r1p.get("replica_caught_up")
        and ((r1p.get("cycle_stretch") or 9) <= 1.05
             or not capable_rig))
    # the ISSUE-17 tree gate: the depth-2 tree absorbed a 10x storm —
    # every mirror byte-identical, the primary served ZERO read-lane
    # requests for it, the scheduler's replica-fed decisions match the
    # primary-fed golden — with the writer-flatness floor gated on
    # rigs with the cores to isolate the tree's processes
    out["tree_binds_match_golden"] = bool(
        tree.get("binds") and tree.get("binds") == r0.get("binds"))
    out["tree_ok"] = bool(
        tree.get("tree_caught_up")
        and tree.get("mirrors_identical")
        and tree.get("primary_read_lane_zero")
        and (tree.get("watchers_live") or 0) >= WATCHERS * 10
        and out["tree_binds_match_golden"]
        and (tree_floors["tree_stretch_met"] or not capable_rig))
    out["ok"] = bool(
        r1.get("replica_caught_up")
        and (r1.get("watchers") or 0) >= 200
        and out["tree_ok"]
        and (floors["met"] or not capable_rig))
    return out


def overload_shed():
    """The overload-protection acceptance config (ISSUE 15): the
    ``read_replica_fanout`` storm rig — 200 watchers + a list storm
    (tests/watch_storm_proc.py) aimed straight AT the primary, two
    bulk-lane writer processes churning, a live paced Scheduler in the
    driver — run against three primaries: ``golden`` (gate at defaults,
    NO storm: the bind baseline), ``ungated_storm`` (admission gate
    disabled — the pre-overload front door; PR 12 recorded writers
    collapsing ~20x to 29 events/sec here), and ``gated_storm``
    (read lane bounded at 8 inflight / 64 queued / 16 live streams:
    the storm sheds TYPED at the gate while bulk writers, control-lane
    scheduler traffic and system-lane work pass untouched).

    ``ok`` enforces the ISSUE bounds: gated writers sustain >= 10x the
    ungated collapse floor AND >= 300 events/sec (both floors move into
    ``core_bound`` on rigs without >= 4 cores, the PR-14 honesty rule —
    the storm processes must not share the scheduler's core for the
    absolute number to mean anything); ``system``-lane sheds == 0
    across the run; every storm-side refusal is a typed OverloadedError
    with a retry-after hint (zero untyped list errors, watchers either
    admitted or shed typed — zero hangs, zero silent drops); and the
    scheduler's decisions stay bind-for-bind identical to the unloaded
    golden."""
    import os
    import shutil
    import subprocess
    import tempfile
    import threading
    TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tests")
    sys.path.insert(0, TESTS)
    from durable_soak import free_port, start_store_proc
    from helpers import build_node, build_pod, build_pod_group, build_queue
    from volcano_tpu.client import RemoteClusterStore

    WATCHERS = 200
    LIST_THREADS = 4
    WRITERS, WAVES, WAVE = 2, 1, 300   # 1200 churn events per arm
    GATED_LANES = "read=8:64:16"

    def pct(ms, q):
        return round(float(np.percentile(ms, q)), 2) if ms else None

    def wait_ready(proc, what):
        deadline = time.time() + 60
        line = ""
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("READY"):
                return line.split()
            if proc.poll() is not None:
                break
        raise RuntimeError(f"{what} failed to start: {line!r}")

    def one_arm(label, storm, gated, disabled=False):
        from volcano_tpu.cache import (
            FakeEvictor, RecordingBinder, SchedulerCache,
        )
        from volcano_tpu.scheduler import Scheduler

        work = tempfile.mkdtemp(prefix="volcano-overload-bench-")
        pport = free_port()
        server = start_store_proc(
            pport, os.path.join(work, "pdata"), fsync="off",
            admission_lanes=GATED_LANES if gated else None,
            admission_disabled=disabled)
        addr = f"127.0.0.1:{pport}"
        arm = {"label": label, "storm": storm, "gated": gated,
               "ungated": disabled}
        clients = []
        procs = [server]

        def client(a=addr, **kw):
            c = RemoteClusterStore(a, **kw)
            clients.append(c)
            return c

        try:
            # -- seed + scheduler (control-lane client, like a real
            # control plane's own traffic) ------------------------------
            seed = client(lane="control")
            seed.apply("queues", build_queue("q0", weight=1))
            for i in range(8):
                seed.apply("nodes", build_node(
                    f"n{i}", {"cpu": "32", "memory": "128Gi"}))
            for j in range(4):
                seed.apply("podgroups", build_pod_group(
                    f"job{j}", "bench", min_member=2, queue="q0"))
                for i in range(2):
                    seed.create("pods", build_pod(
                        "bench", f"job{j}-{i}", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, f"job{j}"))
            cache = SchedulerCache(client(lane="control"))
            cache.evictor = FakeEvictor()
            recorder = RecordingBinder(inner=cache.binder)
            cache.binder = recorder
            cache.run()
            cache.wait_for_cache_sync()
            sched = Scheduler(cache)
            sched.run_once()  # warm-up: compiles + binds the workload
            idle = []
            for _ in range(10):
                t0 = time.perf_counter()
                sched.run_once()
                idle.append((time.perf_counter() - t0) * 1e3)
            arm["cycle_p50_idle_ms"] = pct(idle, 50)

            # -- the storm, aimed at the primary ------------------------
            storms = []
            if storm:
                sp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "watch_storm_proc.py"),
                     "--addr", addr, "--watchers", str(WATCHERS),
                     "--list-threads", str(LIST_THREADS)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                ready = wait_ready(sp, "watch storm")
                arm["watchers_live"] = int(ready[1])
                arm["watch_sheds"] = int(ready[2])
                procs.append(sp)
                storms.append(sp)

            writers = []
            for w in range(WRITERS):
                wp = subprocess.Popen(
                    [sys.executable,
                     os.path.join(TESTS, "store_churn_proc.py"),
                     "--addr", addr, "--writer", str(w),
                     "--waves", str(WAVES), "--wave-size", str(WAVE)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, cwd=os.path.dirname(TESTS))
                wait_ready(wp, f"writer {w}")
                procs.append(wp)
                writers.append(wp)

            under = []
            stop = threading.Event()

            def cycles():
                # paced like a real scheduler period
                while not stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        sched.run_once()
                    except Exception:  # noqa: BLE001 — stretch data only
                        break
                    under.append((time.perf_counter() - t0) * 1e3)
                    stop.wait(0.05)

            cyc = threading.Thread(target=cycles)
            cyc.start()
            t0 = time.perf_counter()
            for sp in storms:
                sp.stdin.write("GO\n")
                sp.stdin.flush()
            for wp in writers:
                wp.stdin.write("GO\n")
                wp.stdin.flush()
            applied = 0
            for wp in writers:
                parts = wp.stdout.readline().split()
                applied += int(parts[1])
                wp.wait(timeout=120)
            churn_s = time.perf_counter() - t0
            time.sleep(0.3)
            stop.set()
            cyc.join()

            for sp in storms:
                sp.stdin.write("STOP\n")
                sp.stdin.flush()
                parts = sp.stdout.readline().split()
                arm["read_tier_events"] = int(parts[1])
                arm["lists_done"] = int(parts[2])
                arm["list_errors"] = int(parts[3])
                arm["list_sheds"] = int(parts[4])
                arm["watch_sheds"] = int(parts[5])
                arm["watchers_live"] = int(parts[6])
                sp.wait(timeout=30)

            arm["churn_events_applied"] = applied
            arm["churn_s"] = round(churn_s, 2)
            arm["writer_events_per_sec"] = round(applied / churn_s)
            arm["cycle_p50_storm_ms"] = pct(under, 50)
            arm["cycle_stretch"] = (
                round(arm["cycle_p50_storm_ms"]
                      / arm["cycle_p50_idle_ms"], 3)
                if under and arm["cycle_p50_idle_ms"] else None)
            arm["binds"] = sorted(recorder.binds.items())

            # the primary's own admission table: what shed, in which
            # lane, for which reason — and that system shed NOTHING
            try:
                info = client().admission_info()
                lanes = info.get("lanes") or {}
                arm["admission_enabled"] = bool(info.get("enabled"))
                arm["admission"] = {
                    lane: {"admitted": st["admitted"],
                           "sheds": st["sheds"],
                           "shed_reasons": st["shed_reasons"],
                           "deadline_expired": st["deadline_expired"]}
                    for lane, st in lanes.items()}
                arm["system_sheds"] = (lanes.get("system") or {}).get(
                    "sheds", 0)
            except Exception as e:  # noqa: BLE001 — recorded honestly
                arm["admission_error"] = f"{type(e).__name__}: {e}"
            return arm
        finally:
            for c in clients:
                try:
                    c.close()
                except Exception:  # noqa: BLE001
                    pass
            for proc in procs:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
            shutil.rmtree(work, ignore_errors=True)

    out = {"arms": {}, "cpu_count": os.cpu_count(),
           "gated_lanes": GATED_LANES, "watchers": WATCHERS}
    for label, storm, gated, disabled in (
            ("golden", False, False, False),
            ("ungated_storm", True, False, True),
            ("gated_storm", True, True, False)):
        out["arms"][label] = _run_config(
            f"overload_shed[{label}]",
            lambda s=storm, g=gated, d=disabled, la=label:
            one_arm(la, s, g, d))
    golden = out["arms"].get("golden", {})
    ungated = out["arms"].get("ungated_storm", {})
    gated = out["arms"].get("gated_storm", {})

    g_eps = gated.get("writer_events_per_sec") or 0
    u_eps = ungated.get("writer_events_per_sec") or 0
    out["writer_eps_ungated"] = u_eps
    out["writer_eps_gated"] = g_eps
    out["writer_relief"] = round(g_eps / u_eps, 2) if u_eps else None
    out["binds_identical_to_golden"] = bool(
        golden.get("binds") and gated.get("binds") == golden.get("binds"))
    out["system_sheds"] = gated.get("system_sheds")
    # zero hangs, zero silent drops: every storm-side refusal was a
    # typed OverloadedError (watchers admitted or shed typed; list
    # refusals typed; no untyped errors)
    out["all_sheds_typed"] = bool(
        gated.get("list_errors", 1) == 0
        and (gated.get("watchers_live", 0)
             + gated.get("watch_sheds", 0)) == WATCHERS)
    # ISSUE floors; core-bound honesty per the PR-14 rule — on a rig
    # where storm + writers + scheduler share one core, the absolute
    # and relative throughput floors measure the core, not the gate
    floors = {
        "floor_gated_eps": 300,
        "floor_relief_x": 10.0,
        "gated_eps": g_eps,
        "relief_x": out["writer_relief"],
        "met": bool(g_eps >= 300 and u_eps and g_eps >= 10 * u_eps),
    }
    capable_rig = (out["cpu_count"] or 1) >= 4
    out["core_bound"] = None if (capable_rig or floors["met"]) \
        else dict(floors)
    out["ok"] = bool(
        out["binds_identical_to_golden"]
        and gated.get("system_sheds") == 0
        and out["all_sheds_typed"]
        and gated.get("admission_enabled")
        and (floors["met"] or not capable_rig))
    out["floors"] = floors
    return out


def _run_config(name, fn, retries: int = 1):
    """Per-config fault isolation (see module docstring): retry once on a
    transient transport drop (volcano_tpu.resilience.transient's markers;
    a device runtime error is never one), and convert anything that
    still fails into a {"error": ...} record so the configs already
    measured are never discarded."""
    import traceback

    from volcano_tpu.resilience.transient import is_transient

    for attempt in range(retries + 1):
        try:
            return fn()
        except KeyboardInterrupt:
            raise
        except Exception as e:  # noqa: BLE001 — the artifact IS the report
            msg = f"{type(e).__name__}: {e}"
            if attempt < retries and is_transient(e):
                print(f"# {name}: transient failure, retrying: "
                      f"{msg.splitlines()[0][:200]}", file=sys.stderr)
                time.sleep(2.0)
                continue
            return {
                "error": msg.strip()[:500],
                "traceback_tail":
                    traceback.format_exc().strip().splitlines()[-3:],
                "attempts": attempt + 1,
            }


def _main_inner() -> dict:
    from volcano_tpu.ops import precompile
    precompile.configure_compilation_cache(
        default_dir=precompile.ENTRY_POINT_CACHE_DIR)
    t_setup = time.time()

    h = _run_config("headline", headline)
    headline_ok = "error" not in h
    single_dev_ms = h.get("device_ms_per_session", -1.0)
    configs = {}
    for name, fn in (
        ("config2_parity_500x50", config2_parity),
        ("config4_preempt_2k_1k", config4_preempt),
        ("config5_hier_5k_1k", config5_hierarchical),
        ("sharded_path_10k_2k",
         lambda: sharded_path_compare(single_dev_ms)),
        ("sharded_100k_10k", sharded_scale),
        ("full_cycle_10k_2k", full_cycle),
        ("steady_churn_1p5k_400", steady_churn),
        ("flatten_event_path", flatten_event_path),
        ("cycle_start_scale", cycle_start_scale),
        ("chaos_churn_50", chaos_churn),
        ("failover_ha", failover),
        ("sim_quality_500c", sim_quality),
        ("reschedule_defrag", reschedule_defrag),
        ("store_durability", store_durability),
        ("store_shard_scale", store_shard_scale),
        ("read_replica_fanout", read_replica_fanout),
        ("overload_shed", overload_shed),
    ):
        configs[name] = _run_config(name, fn)
    setup_s = time.time() - t_setup

    try:
        import jax
        device = str(jax.devices()[0])
    except Exception as e:  # noqa: BLE001
        device = f"unavailable: {e}"
    # headline value: steady-state wall p50 with the three-phase pipeline
    # (the per-cycle cost a steady production scheduler pays); the
    # synchronous single-session latency of BENCH_r01-r05 remains in
    # extra.sync_p50_ms for series continuity
    p50 = h.get("steady_wall_p50_ms") if headline_ok else None
    return {
        "metric": "steady-state wall p50 session latency @10k pods/2k "
                  "nodes (pipelined)",
        "value": p50,
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / p50, 2) if p50 else None,
        "extra": {
            **h,
            "configs": configs,
            "setup_s": round(setup_s, 1),
            "device": device,
        },
    }


def main() -> int:
    """Always exits 0 with ONE JSON line on stdout — a crash anywhere
    (jax import, a config escaping its wrapper, serialization) downgrades
    to an {"error": ...} artifact instead of rc!=0 with no JSON
    (BENCH_r05's `rc=1, parsed=null` failure mode)."""
    import traceback

    try:
        result = _main_inner()
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the artifact IS the report
        result = {
            "metric": "p50 session latency @10k pods/2k nodes",
            "value": None,
            "unit": "ms",
            "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}".strip()[:500],
            "traceback_tail":
                traceback.format_exc().strip().splitlines()[-3:],
        }
    try:
        print(json.dumps(result))
    except (TypeError, ValueError) as e:
        print(json.dumps({"metric": "p50 session latency @10k pods/2k "
                                    "nodes", "value": None,
                          "error": f"artifact not serializable: {e}"}))
    return 0


if __name__ == "__main__":
    rc = main()
    # hard-exit once the artifact is printed: interpreter teardown with
    # live daemon threads (prewarm workers, XLA runtime) can SIGABRT
    # nondeterministically, which would turn a fully-successful run into
    # rc=134 with the JSON already on stdout. os._exit skips teardown;
    # flush first so the artifact is actually out.
    sys.stdout.flush()
    sys.stderr.flush()
    import os
    os._exit(rc)
