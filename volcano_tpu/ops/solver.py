"""Allocate solvers: batched task x node constraint satisfaction on TPU.

Replaces the reference's per-task hot loop (actions/allocate/allocate.go:43-266
+ util/scheduler_helper.go PredicateNodes/PrioritizeNodes 16-goroutine fan-out)
with jitted whole-snapshot kernels:

- ``solve_allocate``      round-based parallel solver (the fast path): each
  round every unassigned task picks its best feasible node (scores are
  matmuls -> MXU), per-node admission happens by priority-ordered prefix
  sums, resources are debited with segment-sums, and a gang fixpoint loop
  reverts jobs that can't reach min_available (the Statement.Discard
  semantics, in-kernel). Converges in O(rounds) ~ contention, not O(tasks).

- ``solve_allocate_sequential``  lax.scan over tasks in priority order,
  reproducing the reference's sequential greedy semantics (allocation of
  task k is visible to task k+1, job-boundary gang revert) for parity tests.

Both run under jit with static padded shapes; all control flow is
lax.while_loop/scan — no host round-trips inside a solve.

Semantics notes (mirroring the Go data model):
- fit check uses the launch request (InitResreq <= Idle, LessEqual with
  per-dim thresholds: l < r + thr; scalar dims with request <= 10 milli are
  ignored) — resource_info.go LessEqual.
- accounting debits the running request (NodeInfo.AddTask subtracts Resreq).
- tasks that don't fit Idle anywhere may pipeline onto FutureIdle =
  Idle + Releasing - Pipelined (node_info.go:57-59).
- gang: a job commits only if ready_base + newly_allocated >= min_available;
  pipelined tasks do not count toward readiness (job_info.go:317-377).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = jnp.float32(-1e30)
BIG_KEY = jnp.int32(2**31 - 1)

#: scale-aware fit tolerance (float32 ulp compensation): the reference
#: compares in float64 with a 1-BYTE memory threshold
#: (resource_info.go:70-72), but this kernel's idle accounting subtracts
#: in float32, where one ulp at a 10-GiB node is ~1 KiB — an exact fit
#: can drift a few hundred bytes below the request and strand the last
#: placement the float64 reference makes. A few-ulp relative term keeps
#: exact fits feasible at any magnitude; at milli-CPU magnitudes it is
#: far below the 10-milli threshold, so only huge-magnitude dims
#: (memory) see it, and at worst it over-admits by ~5e-7 of a node.
REL_FIT_TOL = jnp.float32(5e-7)


class SolveResult(NamedTuple):
    assigned: jnp.ndarray   # [T] int32 node index or -1
    kind: jnp.ndarray       # [T] int32: 0 = allocate, 1 = pipeline, -1 = none
    job_ready: jnp.ndarray  # [J] bool: job committed (gang-satisfied)
    rounds: jnp.ndarray     # [] int32 diagnostic
    compact: jnp.ndarray = None  # [T] int16: node | (kind << 14), -1 = none
                                 # — the wire-cheap readback (decode with
                                 # decode_compact); assigned/kind stay for
                                 # in-kernel consumers and tests


COMPACT_KIND_SHIFT = 14        # node index < 2^14; kind bit above it
COMPACT_UNAVAILABLE = -2       # whole-array sentinel: N too large to pack


def _compact(assigned, kind, n_nodes: int):
    if n_nodes > (1 << COMPACT_KIND_SHIFT):
        # node indices don't fit 14 bits: emit a detectable sentinel so a
        # consumer that forgets the N guard fails loudly in decode_compact
        # instead of silently mis-decoding wrapped values
        return jnp.full(assigned.shape, COMPACT_UNAVAILABLE, jnp.int16)
    return jnp.where(
        assigned < 0, jnp.int16(-1),
        (assigned + kind * (1 << COMPACT_KIND_SHIFT)).astype(jnp.int16))


def decode_compact(compact):
    """host-side: compact int16 -> (assigned int32, kind int32)."""
    c = np.asarray(compact).astype(np.int32)
    if c.size and c[0] == COMPACT_UNAVAILABLE:
        raise ValueError(
            "compact result unavailable (node count exceeds the int16 "
            "packing); read res.assigned / res.kind instead")
    none = c < 0
    kind = np.where(none, -1, c >> COMPACT_KIND_SHIFT)
    assigned = np.where(none, -1, c & ((1 << COMPACT_KIND_SHIFT) - 1))
    return assigned, kind


def collect_assignment(res, n_nodes: int):
    """host-side: block on any allocate solve's result -> (assigned,
    kind, rounds). One int16 readback of ``compact`` where the solve
    packed one and ``n_nodes`` fits its node index; otherwise the int32
    ``assigned``/``kind`` (the sharded solve packs none, and more than
    ``1 << COMPACT_KIND_SHIFT`` nodes overflow the packing)."""
    if res.compact is not None and n_nodes <= (1 << COMPACT_KIND_SHIFT):
        assigned, kind = decode_compact(res.compact)
    else:
        assigned, kind = np.asarray(res.assigned), np.asarray(res.kind)
    return assigned, kind, int(res.rounds)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def le_fits(lhs, avail, thr, scalar_mask, ignore_req=None):
    """Threshold-tolerant LessEqual reduced over the trailing resource axis
    (resource_info.go LessEqual): a dim fits iff lhs < avail + thr OR
    lhs <= avail — the <= disjunct keeps exact fits feasible, because at
    memory magnitudes the threshold vanishes in float32 (2^30 + 1 rounds to
    2^30). Scalar dims whose request (ignore_req, default lhs) is <= 10
    milli are ignored entirely. All inputs broadcast against [..., R].

    Single source of truth for the fit rule — the round solver, sequential
    solver, queue caps, and sharded admission all call this so a semantics
    tweak can't desynchronize them.
    """
    dim_ok = (lhs < avail + (thr + REL_FIT_TOL * jnp.abs(avail))) \
        | (lhs <= avail)
    req = lhs if ignore_req is None else ignore_req
    return jnp.all(dim_ok | (scalar_mask & (req <= 10.0)), axis=-1)


def fits_matrix(req, avail, thr, scalar_mask):
    """LessEqual(req, avail) per (task, node): [T,N] bool."""
    return le_fits(req[:, None, :], avail[None, :, :], thr, scalar_mask)


def score_matrix(init_req, idle, used, alloc, params,
                 families: Tuple[str, ...] = ("binpack", "kube")):
    """Plugin scoring families as dense linear algebra: [T,N] float32.

    binpack  (binpack.go:111-260):  100 * sum_r w_r (used_r+req_r)/alloc_r / sum_w
    least-requested (k8s scorer):   100 * mean_r (alloc-used-req)/alloc over cpu,mem
    most-requested:                 100 * mean_r (used+req)/alloc over cpu,mem
    balanced-allocation:            100 * (1 - |cpu_frac - mem_frac|)

    The per-task terms become [T,R] @ [R,N] matmuls (MXU); per-node terms are
    broadcast vectors. ``families`` is static so zero-weight families cost
    nothing (a binpack-only session skips the [T,N,2] fraction tensors).
    """
    inv_alloc = 1.0 / alloc                    # [N,R]
    score = jnp.zeros((init_req.shape[0], idle.shape[0]), jnp.float32)

    if "binpack" in families:
        w = params["binpack_res_weights"]      # [R]
        wsum = jnp.maximum(jnp.sum(w), 1e-9)
        # binpack: (sum_r req*(w/alloc) + sum_r used*w/alloc) * 100/sum_w.
        # The task term is an explicit per-dimension broadcast sum, NOT a
        # matmul: R is 2-4 (no MXU win) and jnp.dot's default matmul
        # precision is reduced on some backends, which would break bitwise
        # parity with the fused pallas kernel (exact f32 VPU arithmetic).
        R_ = init_req.shape[1]
        wial = w[None, :] * inv_alloc                              # [N,R]
        bp_node = jnp.sum(used * w[None, :] * inv_alloc, axis=-1)  # [N]
        bp_task = jnp.zeros((init_req.shape[0], idle.shape[0]),
                            jnp.float32)
        for r in range(R_):
            bp_task = bp_task + (init_req[:, r][:, None]
                                 * wial[:, r][None, :])
        score += (params["binpack_weight"]
                  * (bp_task + bp_node[None, :]) * (100.0 / wsum))

    if "kube" in families:
        # least/most requested + balanced use cpu(0), mem(1) only
        frac = ((used[None, :, 0:2] + init_req[:, None, 0:2])
                * inv_alloc[None, :, 0:2])                         # [T,N,2]
        least = jnp.mean(jnp.clip(1.0 - frac, 0.0, 1.0), axis=-1) * 100.0
        most = jnp.mean(jnp.clip(frac, 0.0, 1.0), axis=-1) * 100.0
        balanced = (1.0 - jnp.abs(frac[..., 0] - frac[..., 1])) * 100.0
        score += (params["least_req_weight"] * least
                  + params["most_req_weight"] * most
                  + params["balanced_weight"] * balanced)

    score += params["node_static"][None, :]
    return score


def water_fill_deserved(total, weight, cap, request, thr, max_iters: int):
    """Iterative weighted water-filling of per-queue deserved resources
    (proportion.go:137-197), vectorized over queues on device.

    total [R]; weight [Q] (0 = absent/padded queue); cap [Q,R] with +inf on
    uncapped dims; request [Q,R]. Each pass hands every unmet queue its
    weight-proportional slice of the remaining pool simultaneously (the
    reference's inner for-loop reads one `remaining` snapshot per pass, so
    the pass is order-free); queues clamp at capability or request and stop
    participating. Terminates when the pool is sub-threshold or all queues
    met — at most Q+1 passes (an all-unmet pass drains the pool).
    """

    def cond(s):
        deserved, meet, remaining, it = s
        tw = jnp.sum(jnp.where(meet, 0.0, weight))
        return (tw > 0) & jnp.any(remaining >= thr) & (it < max_iters)

    def body(s):
        deserved, meet, remaining, it = s
        tw = jnp.sum(jnp.where(meet, 0.0, weight))
        frac = jnp.where(meet, 0.0, weight) / jnp.maximum(tw, 1e-9)
        old = deserved
        grown = deserved + frac[:, None] * remaining[None, :]
        cap_viol = jnp.any(grown > cap, axis=1)
        req_less = jnp.all(request < grown, axis=1)
        clamped = jnp.where(
            cap_viol[:, None],
            jnp.minimum(jnp.minimum(grown, cap), request),
            jnp.where(req_less[:, None], jnp.minimum(grown, request), grown))
        deserved = jnp.where(meet[:, None], deserved, clamped)
        meet = meet | cap_viol | req_less
        remaining = jnp.maximum(
            remaining - jnp.sum(deserved - old, axis=0), 0.0)
        return deserved, meet, remaining, it + 1

    Q = weight.shape[0]
    init = (jnp.zeros_like(request), weight <= 0, total, jnp.int32(0))
    deserved, _, _, _ = jax.lax.while_loop(cond, body, init)
    return deserved


def drf_state(a, rank):
    """Shared prelude for in-kernel DRF ordering (single-device and
    mesh-sharded solvers): returns (jobres0, drf_rank, drf_cap). All the
    math is replicated-safe — shares are [J] reductions, ranks [T] sorts.

    drf_rank(jobres): dense per-task priority from live dominant shares
    (lower-share jobs first, original order within a job and among ties).
    drf_cap(eligible, jobres): progressive-filling headroom — per round a
    job may only grow its dominant share to (the minimum competing share)
    + one step, at least one task and at least 1/(8 x competing jobs), so
    a saturated cluster converges to equal shares in a handful of rounds
    (drf.go's per-placement job re-sort, in round-sized bites)."""
    T = a["task_rank"].shape[0]
    J = a["job_min"].shape[0]
    rank = a["task_rank"] if rank is None else rank
    first_rank = jnp.full((J,), T, jnp.int32).at[a["task_job"]].min(rank)
    within_rank = rank - first_rank[a["task_job"]]
    drf_total = jnp.maximum(a["drf_total"], 1e-9)
    incr_t = jnp.max(
        jnp.where(a["drf_total"][None, :] > 0.0,
                  a["task_req"] / drf_total[None, :], 0.0), axis=1)
    j_seg_start = jnp.concatenate(
        [jnp.array([True]), a["task_job"][1:] != a["task_job"][:-1]])

    def drf_share(jobres):
        share = jnp.max(
            jnp.where(a["drf_total"][None, :] > 0.0,
                      jobres / drf_total[None, :], 0.0), axis=1)     # [J]
        return jnp.where(a["job_valid"], share, jnp.inf)

    # static MAJOR key from the job-order providers preceding drf in the
    # tiers (priority/gang): live shares only break its ties, so a strict
    # priority never inverts under the share re-rank. Zeros when nothing
    # precedes drf (pure share order, the original behavior). .get():
    # hand-built array dicts (fuzz/bench) predate the key.
    prerank = a.get("job_drf_prerank")
    if prerank is None:
        prerank = jnp.zeros(J, jnp.int32)

    def drf_rank(jobres):
        order_j = jnp.lexsort((drf_share(jobres), prerank))
        job_pos = jnp.zeros(J, jnp.int32).at[order_j].set(
            jnp.arange(J, dtype=jnp.int32))
        order_t = jnp.lexsort((within_rank, job_pos[a["task_job"]]))
        return jnp.zeros(T, jnp.int32).at[order_t].set(
            jnp.arange(T, dtype=jnp.int32))

    def drf_cap(eligible, jobres):
        share = drf_share(jobres)
        elig_job = jnp.zeros(J, jnp.int32).at[a["task_job"]].max(
            eligible.astype(jnp.int32)) > 0
        n_elig = jnp.maximum(jnp.sum(elig_job), 1)
        # progressive filling competes WITHIN a prerank group: a
        # higher-priority job must not be throttled against (or yield
        # headroom to) lower-priority shares
        grp = jnp.clip(prerank, 0, J - 1)
        m_grp = jax.ops.segment_min(
            jnp.where(elig_job, share, jnp.inf), grp, num_segments=J)
        m = m_grp[grp]                                           # [J]
        max_incr = jnp.max(jnp.where(eligible, incr_t, 0.0))
        step = jnp.maximum(max_incr, 1.0 / (8.0 * n_elig))
        allowed = jnp.maximum(share, m) + step                   # [J]
        cum = _segment_prefix((incr_t * eligible)[:, None],
                              j_seg_start)[:, 0] + incr_t
        # absolute comparison (share + cum vs allowed): subtracting share
        # from allowed first loses a float32 ulp and starves exact steps
        return eligible & (share[a["task_job"]] + cum
                           <= allowed[a["task_job"]] + 1e-6)

    return a["job_drf_allocated"], drf_rank, drf_cap


def queue_cap_state(a, rank, thr, total, ease_unrequested: bool = True):
    """Shared prelude for in-kernel queue fair share (used by the
    single-device and mesh-sharded solvers — only the cluster `total`
    source differs): water-filled deserved, the task->queue map, and the
    static (queue, rank) sort for per-round prefix caps."""
    q = a["queue_weight"].shape[0]
    deserved = water_fill_deserved(
        total, a["queue_weight"], a["queue_capability"],
        a["queue_request"], thr, max_iters=q + 1)
    if ease_unrequested:
        # dims a queue never requested must not bind its cap: a queue
        # whose workloads don't use a dim should not be throttled at its
        # (meaningless) water-filled deserved there, so those dims are
        # replaced by +inf for the per-round caps. (One of two deliberate
        # strandings-avoidance improvements over the reference's any-dim
        # overused rule; see phase_rounds' overflow pass. Disabled by
        # work_conserving=False for strict reference parity.)
        deserved = jnp.where(a["queue_request"] > thr[None, :],
                             deserved, jnp.inf)
    task_queue = a["job_queue"][a["task_job"]]
    t = task_queue.shape[0]
    q_perm = jnp.argsort(task_queue * (t + 1) + rank)
    s_q = task_queue[q_perm]
    q_seg_start = jnp.concatenate(
        [jnp.array([True]), s_q[1:] != s_q[:-1]])
    return q, deserved, task_queue, q_perm, q_seg_start


def _queue_cap_mask(eligible, task_queue, req, qrem, thr, scalar_mask,
                    q_perm, q_seg_start, s_q=None, s_req_raw=None):
    """Per-round queue admission cap: among eligible tasks in (queue, rank)
    order, a task passes iff its queue's running prefix of *eligible*
    requests + its own request still fits the queue's remaining deserved
    (threshold-tolerant, like fits_matrix). Conservative like node prefix
    admission: a blocked task waits for the next round's recomputed
    remaining.

    q_perm/q_seg_start are the static (queue, rank) sort and its queue
    segment boundaries — task_queue and rank never change within a solve,
    so the sort is hoisted out of the round loop (one argsort per solve
    instead of one per round); only the eligibility mask varies here.
    s_q/s_req_raw are the sorted task_queue/req gathers — also static for
    a static q_perm, so callers hoist them too (live-DRF callers, whose
    q_perm changes per round, leave them None)."""
    T = req.shape[0]
    if s_q is None:
        s_q = task_queue[q_perm]
    if s_req_raw is None:
        s_req_raw = req[q_perm]
    s_act = eligible[q_perm]
    s_rem = qrem[s_q]
    # a task whose own request can never fit the queue's remaining deserve
    # must not hold budget in the prefix — the sequential reference only
    # charges the queue on actual placement, so a too-big task ahead in
    # rank order doesn't starve feasible tasks behind it
    s_fits_alone = le_fits(s_req_raw, s_rem, thr, scalar_mask,
                           ignore_req=s_req_raw) & s_act
    s_req = s_req_raw * s_fits_alone[:, None]
    prefix = _segment_prefix(s_req, q_seg_start)
    ok_sorted = le_fits(prefix + s_req, s_rem, thr, scalar_mask,
                        ignore_req=s_req) & s_fits_alone
    return jnp.zeros(T, dtype=bool).at[q_perm].set(ok_sorted)


def _segment_prefix(sorted_vals, seg_start_mask):
    """Exclusive prefix-sum of sorted_vals [T,R] within segments delimited by
    seg_start_mask [T] bool."""
    csum = jnp.cumsum(sorted_vals, axis=0)
    excl = csum - sorted_vals
    idx = jnp.arange(sorted_vals.shape[0])
    start_idx = jnp.where(seg_start_mask, idx, -1)
    start_idx = jax.lax.associative_scan(jnp.maximum, start_idx)
    base = excl[jnp.maximum(start_idx, 0)]
    return excl - base


def _waterfall_choice(eligible, node_score, fit_req, avail, npods,
                      max_pods, thr, scalar_mask, mode: str):
    """Spread a herd across nodes in one round.

    When many tasks prefer the same node (binpack's global argmax, or
    least-requested's identical-nodes tie), per-task argmax fills one node
    per round. Instead, order nodes by their herd desirability
    (``node_score`` = per-node max of the masked score — computed by the
    dense path or the fused pallas kernel) and pre-assign task *positions*
    to nodes:

    - pack mode: task position p lands on the node where cumulative slot
      capacity first exceeds p (fills best node to capacity, then next) —
      matches the reference's sequential binpack fill for uniform tasks.
    - spread mode: position p lands on node p mod m (striping) — matches
      sequential least-requested round-robin for uniform tasks.

    Tasks for which the pre-assigned node is infeasible fall back to their
    personal argmax; prefix admission corrects slot overestimates.
    """
    T = eligible.shape[0]
    N = node_score.shape[0]
    # mean eligible request estimates per-node slot counts (the estimate
    # only steers TARGETING — prefix admission is exact; quantile
    # estimators were tried and lose to the mean across the parity corpus)
    n_elig = jnp.maximum(jnp.sum(eligible), 1)
    mean_req = jnp.sum(fit_req * eligible[:, None], axis=0) / n_elig  # [R]
    sig = mean_req > jnp.where(scalar_mask, 10.0, 0.0)
    slots_dim = jnp.where(
        sig[None, :],
        jnp.floor((avail + thr[None, :]) / jnp.maximum(mean_req[None, :], 1e-9)),
        jnp.inf)
    slots = jnp.min(slots_dim, axis=1)                              # [N]
    slots = jnp.minimum(slots, (max_pods - npods).astype(jnp.float32))
    slots = jnp.clip(slots, 0.0, float(T))
    has_slot = slots > 0

    order = jnp.argsort(-jnp.where(has_slot, node_score, NEG))      # [N]
    slots_o = slots[order]
    pos = jnp.cumsum(eligible.astype(jnp.int32)) - 1                # [T]
    if mode == "spread":
        # stripe only across nodes whose herd score ties the best:
        # sequential least-requested alternates between EQUAL nodes but
        # keeps filling a strictly-better node until another catches up,
        # so striping across unequal nodes would scatter a gang the
        # reference packs (and revert it under contention)
        masked_score = jnp.where(has_slot, node_score, NEG)
        best_s = jnp.max(masked_score)
        eps = 1e-5 * jnp.maximum(jnp.abs(best_s), 1.0)
        near = has_slot & (masked_score >= best_s - eps)
        m = jnp.maximum(jnp.sum(near), 1)
        target = order[jnp.mod(jnp.maximum(pos, 0), m)]
    else:
        cum = jnp.cumsum(slots_o)
        idx = jnp.searchsorted(cum, pos.astype(jnp.float32), side="right")
        target = order[jnp.clip(idx, 0, N - 1)]
    return target.astype(jnp.int32)


def _admission_round(eligible, feas, score, fit_req, acct_req, avail,
                     rank, thr, scalar_mask, npods, max_pods,
                     per_node_cap: int = 0, herd_mode: str = "pack"):
    """One parallel round: choose best node per task (waterfall-corrected),
    admit by priority prefix within each node, return (new_assign[T]
    node/-1, debit[N,R], pod_inc[N])."""
    pods_ok = (npods < max_pods)[None, :]
    feas = feas & pods_ok & eligible[:, None]
    masked = jnp.where(feas, score, NEG)
    personal = jnp.argmax(masked, axis=1).astype(jnp.int32)        # [T]
    if herd_mode in ("pack", "spread") and per_node_cap == 0:
        node_score = jnp.max(masked, axis=0)                       # [N]
        target = _waterfall_choice(eligible, node_score, fit_req, avail,
                                   npods, max_pods, thr, scalar_mask,
                                   herd_mode)
        t_ok = jnp.take_along_axis(feas, target[:, None], axis=1)[:, 0]
        choice = jnp.where(t_ok, target, personal)
    else:
        choice = personal
    has = jnp.take_along_axis(feas, choice[:, None], axis=1)[:, 0]
    choice = jnp.where(has, choice, -1)
    return _admit_prefix(choice, fit_req, acct_req, avail, rank, thr,
                         scalar_mask, npods, max_pods, per_node_cap)


def _admission_round_fused(eligible, a, avail, used_now, sig_feas, sig_i8,
                           inv_alloc, node_static, pars, acct_req, rank,
                           thr, scalar_mask, npods, herd_mode: str,
                           score_families):
    """The fused-kernel form of _admission_round: the [T,N] feasibility/
    score/argmax/node-max pass runs in ONE pallas kernel (HBM traffic per
    round drops from several [T,N] float32 matrices to the int8 signature
    mask + [T]/[N] vectors); the feasibility of the two *chosen* nodes is
    re-derived pointwise. Only the waterfall herd modes take this path
    (per_node_cap fidelity mode stays dense)."""
    from .pallas_kernels import fused_choice

    fit_req = a["task_init_req"]
    max_pods = a["node_max_pods"]
    pods_ok = npods < max_pods
    best_s, best_i, node_score = fused_choice(
        fit_req, avail, used_now, inv_alloc, node_static,
        eligible.astype(jnp.float32), pods_ok.astype(jnp.float32),
        sig_i8, pars, score_families)
    has_any = best_s > NEG * 0.5
    personal = best_i

    def feas_point(node_idx):
        """feasibility of (task, node_idx[task]) — identical rule to the
        dense feas matrix, evaluated at one node per task."""
        av = avail[node_idx]                                   # [T,R]
        fit = le_fits(fit_req, av, thr, scalar_mask)
        sig = jnp.take_along_axis(sig_feas, node_idx[:, None],
                                  axis=1)[:, 0]
        return fit & sig & pods_ok[node_idx] & eligible

    target = _waterfall_choice(eligible, node_score, fit_req, avail,
                               npods, max_pods, thr, scalar_mask,
                               herd_mode)
    t_ok = feas_point(target)
    choice = jnp.where(t_ok, target,
                       jnp.where(has_any, personal, -1))
    return _admit_prefix(choice, fit_req, acct_req, avail, rank, thr,
                         scalar_mask, npods, max_pods, 0)


def _admit_prefix(choice, fit_req, acct_req, avail, rank, thr,
                  scalar_mask, npods, max_pods, per_node_cap: int):
    """Priority-prefix admission for a round's per-task node choices
    (shared by the dense and fused choice paths)."""
    T = choice.shape[0]
    N = avail.shape[0]
    # sort by (node, rank); inactive last
    key = jnp.where(choice >= 0, choice * (T + 1) + rank, BIG_KEY)
    perm = jnp.argsort(key)
    s_choice = choice[perm]
    s_active = s_choice >= 0
    s_fit = fit_req[perm] * s_active[:, None]
    seg_start = jnp.concatenate(
        [jnp.array([True]), s_choice[1:] != s_choice[:-1]])
    prefix = _segment_prefix(s_fit, seg_start)                     # [T,R]

    s_avail = avail[jnp.maximum(s_choice, 0)]                      # [T,R]
    fits = le_fits(prefix + s_fit, s_avail, thr, scalar_mask,
                   ignore_req=s_fit) & s_active
    # pod-count prefix: position within segment
    ones = jnp.ones_like(s_choice)
    pos = _segment_prefix(ones[:, None].astype(jnp.float32), seg_start)[:, 0]
    pods_fit = (npods[jnp.maximum(s_choice, 0)] + pos) < max_pods[jnp.maximum(s_choice, 0)]
    admit_sorted = fits & pods_fit
    if per_node_cap > 0:
        # fidelity mode: at most cap admissions per node per round, so
        # scoring sees updated node state between admissions (closer to the
        # reference's sequential greedy)
        admit_sorted = admit_sorted & (pos < per_node_cap)

    # NOTE: prefix admission is conservative: a blocked task simply waits for
    # the next round, after the node's idle has been debited for real.
    admit = jnp.zeros(T, dtype=bool).at[perm].set(admit_sorted)
    new_assign = jnp.where(admit, choice, -1)

    debit = jax.ops.segment_sum(
        acct_req * admit[:, None], jnp.maximum(choice, 0), num_segments=N)
    pod_inc = jax.ops.segment_sum(
        admit.astype(jnp.int32), jnp.maximum(choice, 0), num_segments=N)
    return new_assign, debit, pod_inc


# ---------------------------------------------------------------------------
# fast round-based solver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_rounds", "max_gang_iters",
                                             "per_node_cap", "herd_mode",
                                             "score_families",
                                             "use_queue_cap",
                                             "use_drf_order",
                                             "use_hdrf_order",
                                             "work_conserving",
                                             "fused"))
def solve_allocate(arrays: Dict[str, jnp.ndarray],
                   score_params: Dict[str, jnp.ndarray],
                   max_rounds: int = 64,
                   max_gang_iters: int = 12,
                   per_node_cap: int = 0,
                   herd_mode: str = "pack",
                   score_families: Tuple[str, ...] = ("binpack", "kube"),
                   use_queue_cap: bool = False,
                   use_drf_order: bool = False,
                   use_hdrf_order: bool = False,
                   work_conserving: bool = True,
                   fused: str = "auto") -> SolveResult:
    """Round-based allocate+pipeline solve with in-kernel gang semantics.

    With ``use_queue_cap`` (proportion plugin active) per-queue deserved is
    water-filled on device from queue_weight/capability/request and each
    round's admissions are capped at deserved per queue, so a 3:1 weight
    split of a saturated cluster yields a 3:1 allocation split.

    With ``use_drf_order`` (drf plugin active) the admission priority is
    recomputed every round from live dominant shares (SURVEY §7 stage 4:
    DRF shares as on-device reductions for ordering): each job's share is
    max_r(allocated_r / total_r) including this solve's placements, jobs
    sort ascending by share, and tasks inherit their job's position — so a
    saturated cluster splits between equal competitors instead of the
    static snapshot order handing everything to the first job.
    """
    a = arrays
    T = a["task_init_req"].shape[0]
    N = a["node_idle"].shape[0]
    J = a["job_min"].shape[0]
    thr = a["thresholds"]
    scalar_mask = a["scalar_dim_mask"]
    sig_feas = a["sig_masks"][a["task_sig"]] & a["node_valid"][None, :]  # [T,N]
    rank = a["task_rank"]
    counts_ready = a["task_counts_ready"].astype(jnp.int32)

    # fused pallas choice kernel (TPU): the per-round [T,N] feasibility/
    # score/argmax pass in one VMEM-resident kernel. "auto" = on-device
    # when the shape tiles cleanly and the round uses the waterfall herd
    # modes; "on"/"off" force (tests exercise the kernel in interpret
    # mode on CPU via "on").
    from .pallas_kernels import fused_choice_auto, use_interpret
    use_fused = fused == "on" or (
        fused == "auto" and not use_interpret()
        and fused_choice_auto(T, N)
        and herd_mode in ("pack", "spread") and per_node_cap == 0)
    if use_fused and (herd_mode not in ("pack", "spread")
                      or per_node_cap != 0):
        use_fused = False  # fused path implements only the herd modes
    if use_fused:
        from .pallas_kernels import fused_choice, fused_setup
        sig_i8, inv_alloc, fused_pars, node_static = fused_setup(
            {"sig_feas": sig_feas, "node_alloc": a["node_alloc"]},
            score_params, a["task_init_req"].shape[1])

    if use_queue_cap:
        total = jnp.sum(
            a["node_alloc"] * a["node_valid"][:, None].astype(jnp.float32),
            axis=0)
        Q, deserved, task_queue, q_perm, q_seg_start = queue_cap_state(
            a, rank, thr, total, ease_unrequested=work_conserving)
        qalloc0 = a["queue_allocated"]
        # static-sort gathers hoisted out of the round loop (the live-DRF
        # re-sorted path recomputes them per round inside the mask)
        qs_q = task_queue[q_perm]
        qs_req = a["task_req"][q_perm]
    else:
        task_queue = None
        deserved = None
        q_perm = q_seg_start = None
        qs_q = qs_req = None
        qalloc0 = jnp.zeros((1, a["node_idle"].shape[1]), jnp.float32)

    if use_drf_order:
        jobres0, drf_rank, drf_cap = drf_state(a, rank)
        if use_hdrf_order:
            # hierarchical mode: the comparator AND the progressive cap
            # both come from the weighted tree (ops.hdrf.hdrf_state) —
            # one tree recursion per round feeds the re-rank and the
            # per-ancestor-level growth gate, so weighted hierarchies
            # converge to the reference's weighted split
            from .hdrf import hdrf_state
            hdrf_rank_cap = hdrf_state(a, rank)
    else:
        jobres0 = jnp.zeros((1, a["node_idle"].shape[1]), jnp.float32)
        drf_rank = drf_cap = None

    def phase_rounds(st, use_future: bool, capped: bool = True, gate=None):
        """Run admission rounds to fixpoint against idle (allocate) or
        future-idle (pipeline). st: 9-tuple carry (idle, pipe, npods,
        qalloc, jobres, assigned, kind, excluded, rounds). capped=False is
        the work-conserving overflow pass: fair-share deserved caps are
        relaxed (hard capability quotas still bind) so capacity no
        competing queue wants is not stranded. This deliberately improves
        on the reference, whose any-dim overused check
        (proportion.go:245 `!allocated.LessEqual(deserved)`) strands the
        same capacity — the host path reproduces that faithfully."""

        def cond(s):
            changed, rounds = s[-1], s[-2]
            return changed & (rounds < max_rounds)

        def body(s):
            (idle, pipe, npods, qalloc, jobres, assigned, kind, excluded,
             rounds, _) = s
            avail = (idle + a["node_extra_future"] - pipe) if use_future else idle
            eligible = (a["task_valid"] & (assigned < 0)
                        & ~excluded[a["task_job"]])
            # per-round admission priority: live DRF shares when active
            used_now = a["node_used"] + (a["node_idle"] - idle)
            feas0 = None
            if use_drf_order:
                if use_hdrf_order:
                    # placeability prefilter: a task no node can take this
                    # round must not hold its sibling group's min key or
                    # pin its subtree's budget (the reference's queue loop
                    # skips a queue whose job can't place and pops the
                    # next — hard cap-blocking against an unplaceable
                    # sibling would strand capacity instead). The dense
                    # path reuses this round's feasibility matrix; the
                    # fused path pays one extra kernel pass (hdrf only).
                    pods_ok_v = npods < a["node_max_pods"]
                    if use_fused:
                        best_s0, _, _ = fused_choice(
                            a["task_init_req"], avail, used_now,
                            inv_alloc, node_static,
                            eligible.astype(jnp.float32),
                            pods_ok_v.astype(jnp.float32),
                            sig_i8, fused_pars, score_families)
                        placeable = best_s0 > NEG * 0.5
                    else:
                        feas0 = fits_matrix(a["task_init_req"], avail,
                                            thr, scalar_mask) & sig_feas
                        placeable = jnp.any(
                            feas0 & pods_ok_v[None, :], axis=1)
                    r_rank, eligible = hdrf_rank_cap(
                        eligible & placeable, jobres)
                else:
                    r_rank = drf_rank(jobres)
                    eligible = drf_cap(eligible, jobres)
            else:
                r_rank = rank
            if use_queue_cap:
                # capped phases enforce fair-share deserved; the overflow
                # pass relaxes deserved but NEVER the hard capability
                # quota (a queue must not exceed its capability just
                # because capacity is otherwise idle)
                bound = deserved if capped else a["queue_capability"]
                qrem = jnp.maximum(bound - qalloc, 0.0)
                if use_drf_order:
                    qp = jnp.lexsort((r_rank, task_queue))
                    eligible = eligible & _queue_cap_mask(
                        eligible, task_queue, a["task_req"], qrem, thr,
                        scalar_mask, qp, q_seg_start)
                else:
                    eligible = eligible & _queue_cap_mask(
                        eligible, task_queue, a["task_req"], qrem, thr,
                        scalar_mask, q_perm, q_seg_start, qs_q, qs_req)
            if use_fused:
                new_assign, debit, pod_inc = _admission_round_fused(
                    eligible, a, avail, used_now, sig_feas, sig_i8,
                    inv_alloc, node_static, fused_pars, a["task_req"],
                    r_rank, thr, scalar_mask, npods, herd_mode,
                    score_families)
            else:
                feas = feas0 if feas0 is not None else (
                    fits_matrix(a["task_init_req"], avail, thr,
                                scalar_mask) & sig_feas)
                score = score_matrix(a["task_init_req"], avail, used_now,
                                     a["node_alloc"], score_params,
                                     score_families)
                new_assign, debit, pod_inc = _admission_round(
                    eligible, feas, score, a["task_init_req"],
                    a["task_req"], avail, r_rank, thr, scalar_mask, npods,
                    a["node_max_pods"], per_node_cap, herd_mode)
            got = new_assign >= 0
            assigned = jnp.where(got, new_assign, assigned)
            kind = jnp.where(got, jnp.int32(1 if use_future else 0), kind)
            if use_queue_cap:
                # pipelined tasks count toward queue allocated too (the
                # reference fires AllocateFunc handlers on ssn.Pipeline)
                qalloc = qalloc + jax.ops.segment_sum(
                    a["task_req"] * got[:, None], task_queue,
                    num_segments=Q)
            if use_drf_order:
                jobres = jobres + jax.ops.segment_sum(
                    a["task_req"] * got[:, None], a["task_job"],
                    num_segments=J)
            if use_future:
                pipe = pipe + debit
            else:
                idle = idle - debit
                npods = npods + pod_inc
            return (idle, pipe, npods, qalloc, jobres, assigned, kind,
                    excluded, rounds + 1, jnp.any(got))

        # skip the phase outright when no task is still eligible (e.g. the
        # pipeline phase after everything allocated): one [T] reduction
        # instead of a full wasted [T,N] round. `gate` adds a caller-side
        # cheap impossibility check (no future capacity / no capped task).
        _, _, _, _, _, assigned0, _, excluded0, _ = st
        any_eligible = jnp.any(a["task_valid"] & (assigned0 < 0)
                               & ~excluded0[a["task_job"]])
        if gate is not None:
            any_eligible = any_eligible & gate
        out = jax.lax.while_loop(cond, body, st + (any_eligible,))
        return out[:-1]

    # job order position for the gang-exclusion tie-break: first valid
    # task's rank (static snapshot order)
    job_first_rank = jnp.full((J,), T, jnp.int32).at[a["task_job"]].min(
        jnp.where(a["task_valid"], rank, T))
    # loop-invariant: pipeline phases only matter when some node's
    # FutureIdle can exceed its Idle (releasing > pipelined somewhere)
    has_future = jnp.any(a["node_extra_future"] > 0.0)

    def gang_body(s):
        (idle, pipe, npods, qalloc, jobres, assigned, kind, excluded,
         rounds, _, it, revert_count, deferred, processed) = s
        # deferred-retry queue: jobs that reverted twice in the parallel
        # phases sit out while the best-ranked of them retries ALONE —
        # the batched equivalent of the sequential reference, where the
        # earliest discarded gang gets first claim on capacity later
        # discards free. One deferred job resolves per iteration.
        unproc = deferred & ~processed & ~excluded
        cur = jnp.argmin(jnp.where(unproc, job_first_rank, BIG_KEY))
        solo = unproc & (jnp.arange(J) == cur)
        barred = deferred & ~solo
        st = (idle, pipe, npods, qalloc, jobres, assigned, kind,
              excluded | barred, rounds)
        st = phase_rounds(st, use_future=False)
        st = phase_rounds(st, use_future=True, gate=has_future)
        if use_queue_cap and work_conserving:
            # work-conserving overflow: leftovers no competing queue could
            # take under its cap go to whoever still wants them — run only
            # when some leftover task is BLOCKED by the capped eligibility
            # mask. The mask is monotone in the queue bound, so if every
            # leftover already passes it under `deserved`, the overflow
            # phases would see the exact eligibility the capped phases
            # converged on and admit nothing: two full-width [T,N] rounds
            # skipped for one [T,R] mask evaluation. (Under live DRF
            # ordering the mask is rank-dependent; keep the phases then.)
            if use_drf_order:
                # rank-dependent mask: no cheap exactness argument, keep
                # the phases (their own any-eligible check still applies)
                st = phase_rounds(st, use_future=False, capped=False)
                st = phase_rounds(st, use_future=True, capped=False,
                                  gate=has_future)
            else:
                (_i, _p, _n, qalloc_c, _j, assigned_c, _k, excl_c,
                 _r) = st
                rem = (a["task_valid"] & (assigned_c < 0)
                       & ~excl_c[a["task_job"]])
                qrem_now = jnp.maximum(deserved - qalloc_c, 0.0)
                elig_capped = _queue_cap_mask(
                    rem, task_queue, a["task_req"], qrem_now, thr,
                    scalar_mask, q_perm, q_seg_start, qs_q, qs_req)
                capped_out = jnp.any(rem & ~elig_capped)
                st = phase_rounds(st, use_future=False, capped=False,
                                  gate=capped_out)
                st = phase_rounds(st, use_future=True, capped=False,
                                  gate=capped_out & has_future)
        (idle, pipe, npods, qalloc, jobres, assigned, kind, _masked,
         rounds) = st

        # gang check: allocated (kind 0, counts_ready) per job
        alloc_counts = jax.ops.segment_sum(
            ((assigned >= 0) & (kind == 0)).astype(jnp.int32) * counts_ready,
            a["task_job"], num_segments=J)
        ready = (a["job_ready_base"] + alloc_counts) >= a["job_min"]
        ready = ready & a["job_valid"]
        # revert unready jobs that DID get allocations (Statement.Discard);
        # pipelined tasks are NOT statement ops in the reference
        # (allocate.go pipelines via ssn.Pipeline) so they survive discard
        # and keep holding FutureIdle. Unready jobs with nothing allocated
        # stay eligible — resources a revert frees may let them place in the
        # next gang iteration.
        has_alloc = jax.ops.segment_sum(
            ((assigned >= 0) & (kind == 0)).astype(jnp.int32), a["task_job"],
            num_segments=J) > 0
        revert_job = ~ready & a["job_valid"] & ~excluded & ~barred \
            & has_alloc
        revert_task = (revert_job[a["task_job"]] & (assigned >= 0)
                       & (kind == 0))
        credit = jax.ops.segment_sum(
            a["task_req"] * revert_task[:, None],
            jnp.maximum(assigned, 0), num_segments=N)
        pod_credit = jax.ops.segment_sum(
            revert_task.astype(jnp.int32),
            jnp.maximum(assigned, 0), num_segments=N)
        idle = idle + credit
        npods = npods - pod_credit
        if use_queue_cap:
            qalloc = qalloc - jax.ops.segment_sum(
                a["task_req"] * revert_task[:, None], task_queue,
                num_segments=Q)
        if use_drf_order:
            jobres = jobres - jax.ops.segment_sum(
                a["task_req"] * revert_task[:, None], a["task_job"],
                num_segments=J)
        assigned = jnp.where(revert_task, -1, assigned)
        kind = jnp.where(revert_task, -1, kind)
        # retry policy: a first revert leaves the job eligible for the
        # next parallel iteration (another job's revert — often the cause
        # of its failure — may have freed room); a second revert defers
        # the job to the one-at-a-time queue above. A solo retry that
        # reverts again is excluded for good; either way the job counts
        # as processed, so the queue drains one job per iteration and the
        # fixpoint stays bounded.
        revert_count = revert_count + revert_job.astype(jnp.int32)
        excluded = excluded | (solo & revert_job)
        processed = processed | (solo & jnp.any(unproc))
        deferred = deferred | (revert_job & (revert_count >= 2))
        any_more = jnp.any(revert_job) | jnp.any(
            deferred & ~processed & ~excluded)
        return (idle, pipe, npods, qalloc, jobres, assigned, kind, excluded,
                rounds, any_more, it + 1, revert_count, deferred, processed)

    init = (a["node_idle"], jnp.zeros_like(a["node_idle"]), a["node_npods"],
            qalloc0, jobres0,
            jnp.full((T,), -1, jnp.int32), jnp.full((T,), -1, jnp.int32),
            ~a["job_valid"], jnp.int32(0), jnp.bool_(True), jnp.int32(0),
            jnp.zeros(J, jnp.int32), jnp.zeros(J, dtype=bool),
            jnp.zeros(J, dtype=bool))
    # bounded gang fixpoint: rerun phases while any job got reverted (its
    # freed resources may admit other jobs) or deferred jobs await their
    # solo retry
    s = jax.lax.while_loop(
        lambda s: s[-5] & (s[-4] < max_gang_iters), gang_body, init)

    (idle, pipe, npods, _, _, assigned, kind, excluded, rounds,
     _, _, _, _, _) = s
    alloc_counts = jax.ops.segment_sum(
        ((assigned >= 0) & (kind == 0)).astype(jnp.int32) * counts_ready,
        a["task_job"], num_segments=J)
    job_ready = ((a["job_ready_base"] + alloc_counts) >= a["job_min"]) \
        & a["job_valid"]
    return SolveResult(assigned=assigned, kind=kind, job_ready=job_ready,
                       rounds=rounds, compact=_compact(assigned, kind, N))


# ---------------------------------------------------------------------------
# sequential parity solver (reference greedy semantics)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("score_families",
                                             "use_queue_cap",
                                             "overflow_pass",
                                             "work_conserving"))
def solve_allocate_sequential(arrays: Dict[str, jnp.ndarray],
                              score_params: Dict[str, jnp.ndarray],
                              score_families: Tuple[str, ...] = ("binpack", "kube"),
                              use_queue_cap: bool = False,
                              overflow_pass: bool = False,
                              work_conserving: bool = True) -> SolveResult:
    """lax.scan over tasks in rank order: task k's allocation is visible to
    task k+1 and job-boundary gang revert mirrors Statement.Discard.

    Requires tasks grouped by job in rank order (flatten_snapshot guarantees
    this). O(T) sequential steps — use for parity tests and small problems.

    overflow_pass (with use_queue_cap): after the strict deserved-capped
    scan, run a SECOND scan over the leftover tasks with the caps relaxed
    to hard capability — the sequential oracle for the round solver's
    work-conserving overflow phases (capacity no competing queue could
    take under its cap goes to whoever still wants it).
    """
    a = arrays
    T = a["task_init_req"].shape[0]
    N = a["node_idle"].shape[0]
    J = a["job_min"].shape[0]
    thr = a["thresholds"]
    scalar_mask = a["scalar_dim_mask"]
    sig_feas_all = a["sig_masks"][a["task_sig"]] & a["node_valid"][None, :]

    if use_queue_cap:
        total = jnp.sum(
            a["node_alloc"] * a["node_valid"][:, None].astype(jnp.float32),
            axis=0)
        Q, deserved, _, _, _ = queue_cap_state(
            a, a["task_rank"], thr, total,
            ease_unrequested=work_conserving)
        qalloc0 = a["queue_allocated"]
    else:
        deserved = None
        qalloc0 = jnp.zeros((1, a["node_idle"].shape[1]), jnp.float32)

    def fits_one(req, avail):
        return le_fits(req[None, :], avail, thr, scalar_mask)

    def make_pass(bound, base_alloc):
        """One sequential scan over the tasks. bound: per-queue cap table
        (deserved for the strict pass, hard capability for the overflow
        pass); base_alloc [J]: allocations a prior pass already committed
        — ready checks include them, reverts never touch them."""

        def finalize_job(carry, jidx):
            (idle, pipe, npods, qalloc, assigned, kind, jalloc,
             snap_idle, snap_pipe, snap_npods, snap_assigned) = carry
            ready = (a["job_ready_base"][jidx] + base_alloc[jidx]
                     + jalloc) >= a["job_min"][jidx]
            is_job = (a["task_job"] == jidx)
            # only THIS pass's allocations revert (a prior pass's are
            # already dispatched): exactly the entries assigned since the
            # job-boundary snapshot. Pipelined tasks survive discard,
            # mirroring ssn.Pipeline being outside the Statement.
            revert = (is_job & (assigned >= 0) & (kind == 0) & ~ready
                      & (snap_assigned < 0))
            idle = jnp.where(ready, idle, snap_idle)
            npods = jnp.where(ready, npods, snap_npods)
            if use_queue_cap:
                amt = jnp.sum(a["task_req"] * revert[:, None], axis=0)
                jq = a["job_queue"][jidx]
                qalloc = qalloc - (jnp.arange(Q) == jq)[:, None] \
                    * amt[None, :]
            assigned = jnp.where(revert, -1, assigned)
            kind = jnp.where(revert, -1, kind)
            return (idle, pipe, npods, qalloc, assigned, kind)

        def step(carry, i):
            (idle, pipe, npods, qalloc, assigned, kind, cur_job, jalloc,
             snap_idle, snap_pipe, snap_npods, snap_assigned) = carry
            jidx = a["task_job"][i]
            boundary = (jidx != cur_job)

            def at_boundary(args):
                (idle, pipe, npods, qalloc, assigned, kind, jalloc,
                 snap_idle, snap_pipe, snap_npods, snap_assigned) = args
                idle, pipe, npods, qalloc, assigned, kind = \
                    finalize_job(args, cur_job)
                return (idle, pipe, npods, qalloc, assigned, kind,
                        jnp.int32(0), idle, pipe, npods, assigned)

            (idle, pipe, npods, qalloc, assigned, kind, jalloc,
             snap_idle, snap_pipe, snap_npods, snap_assigned) = jax.lax.cond(
                boundary, at_boundary, lambda args: args,
                (idle, pipe, npods, qalloc, assigned, kind, jalloc,
                 snap_idle, snap_pipe, snap_npods, snap_assigned))
            cur_job = jidx

            # the overflow pass only visits leftovers
            valid = a["task_valid"][i] & (assigned[i] < 0)
            req_fit = a["task_init_req"][i]
            req_acct = a["task_req"][i]
            sig_feas = sig_feas_all[i]
            pods_ok = npods < a["node_max_pods"]
            if use_queue_cap:
                jq = a["job_queue"][jidx]
                valid = valid & le_fits(qalloc[jq] + req_acct, bound[jq],
                                        thr, scalar_mask,
                                        ignore_req=req_acct)

            feas_idle = fits_one(req_fit, idle) & sig_feas & pods_ok & valid
            future = idle + a["node_extra_future"] - pipe
            feas_fut = fits_one(req_fit, future) & sig_feas & pods_ok & valid

            used_now = a["node_used"] + (a["node_idle"] - idle)
            score = score_matrix(req_fit[None, :], idle, used_now,
                                 a["node_alloc"], score_params,
                                 score_families)[0]

            pick_idle = jnp.any(feas_idle)
            pick_fut = ~pick_idle & jnp.any(feas_fut)
            feas = jnp.where(pick_idle, feas_idle, feas_fut)
            node = jnp.argmax(jnp.where(feas, score, NEG)).astype(jnp.int32)
            got = pick_idle | pick_fut
            node = jnp.where(got, node, -1)

            debit = jnp.where(got, req_acct, 0.0)
            onehot = (jnp.arange(N) == node)[:, None]
            idle = idle - jnp.where(pick_idle, debit[None, :] * onehot, 0.0)
            pipe = pipe + jnp.where(pick_fut, debit[None, :] * onehot, 0.0)
            npods = npods + jnp.where(pick_idle,
                                      onehot[:, 0].astype(jnp.int32), 0)
            if use_queue_cap:
                q_onehot = (jnp.arange(Q) == a["job_queue"][jidx])[:, None]
                qalloc = qalloc + q_onehot * debit[None, :]
            # never clobber a prior pass's assignment
            prev_a, prev_k = assigned[i], kind[i]
            assigned = assigned.at[i].set(
                jnp.where(prev_a >= 0, prev_a, node))
            kind = kind.at[i].set(jnp.where(
                prev_a >= 0, prev_k,
                jnp.where(pick_idle, 0, jnp.where(pick_fut, 1, -1))))
            jalloc = jalloc + jnp.where(
                pick_idle & a["task_counts_ready"][i], 1, 0)
            return (idle, pipe, npods, qalloc, assigned, kind, cur_job,
                    jalloc, snap_idle, snap_pipe, snap_npods,
                    snap_assigned), None

        return finalize_job, step

    def run_pass(bound, base_alloc, state):
        idle, pipe, npods, qalloc, assigned, kind = state
        finalize_job, step = make_pass(bound, base_alloc)
        init = (idle, pipe, npods, qalloc, assigned, kind,
                a["task_job"][0], jnp.int32(0),
                idle, pipe, npods, assigned)
        carry, _ = jax.lax.scan(step, init, jnp.arange(T))
        (idle, pipe, npods, qalloc, assigned, kind, cur_job, jalloc,
         snap_idle, snap_pipe, snap_npods, snap_assigned) = carry
        return finalize_job(
            (idle, pipe, npods, qalloc, assigned, kind, jalloc,
             snap_idle, snap_pipe, snap_npods, snap_assigned), cur_job)

    counts_ready = a["task_counts_ready"].astype(jnp.int32)
    state = (a["node_idle"], jnp.zeros_like(a["node_idle"]),
             a["node_npods"], qalloc0,
             jnp.full((T,), -1, jnp.int32), jnp.full((T,), -1, jnp.int32))
    state = run_pass(deserved, jnp.zeros(J, jnp.int32), state)
    if overflow_pass and use_queue_cap:
        idle, pipe, npods, qalloc, assigned, kind = state
        base1 = jax.ops.segment_sum(
            ((assigned >= 0) & (kind == 0)).astype(jnp.int32)
            * counts_ready, a["task_job"], num_segments=J)
        state = run_pass(a["queue_capability"], base1,
                         (idle, pipe, npods, qalloc, assigned, kind))
    idle, pipe, npods, qalloc, assigned, kind = state
    alloc_counts = jax.ops.segment_sum(
        ((assigned >= 0) & (kind == 0)).astype(jnp.int32) * counts_ready,
        a["task_job"], num_segments=J)
    job_ready = ((a["job_ready_base"] + alloc_counts) >= a["job_min"]) \
        & a["job_valid"]
    return SolveResult(assigned=assigned, kind=kind, job_ready=job_ready,
                       rounds=jnp.int32(T), compact=_compact(assigned, kind, N))


# ---------------------------------------------------------------------------
# packed-transfer entry point
# ---------------------------------------------------------------------------

def _unpack(fbuf, ibuf, layout):
    d = {}
    for k, kind, off, size, shape in layout:
        if kind == "f":
            d[k] = jax.lax.dynamic_slice(fbuf, (off,), (size,)).reshape(shape)
        else:
            v = jax.lax.dynamic_slice(ibuf, (off,), (size,)).reshape(shape)
            d[k] = v.astype(bool) if kind == "b" else v
    return d


@functools.partial(jax.jit, static_argnames=(
    "layout", "max_rounds", "max_gang_iters", "per_node_cap", "herd_mode",
    "score_families", "use_queue_cap", "use_drf_order", "use_hdrf_order",
    "work_conserving"))
def solve_allocate_packed2d(f2d, i2d, layout,
                            score_params: Dict[str, jnp.ndarray],
                            max_rounds: int = 64,
                            max_gang_iters: int = 12,
                            per_node_cap: int = 0,
                            herd_mode: str = "pack",
                            score_families: Tuple[str, ...] = ("binpack",),
                            use_queue_cap: bool = False,
                            use_drf_order: bool = False,
                            use_hdrf_order: bool = False,
                            work_conserving: bool = True) -> SolveResult:
    """solve_allocate over the chunked device-resident buffers kept by
    ops.device_cache.PackedDeviceCache: per-session upload is only the
    dirty chunks; the flatten+slice here fuses away on device."""
    nf = max(off + size for k, kind, off, size, shape in layout
             if kind == "f")
    ni = max(off + size for k, kind, off, size, shape in layout
             if kind != "f")
    fbuf = f2d.reshape(-1)[:nf]
    ibuf = i2d.reshape(-1)[:ni]
    arrays = _unpack(fbuf, ibuf, layout)
    return solve_allocate(arrays, score_params, max_rounds, max_gang_iters,
                          per_node_cap, herd_mode, score_families,
                          use_queue_cap, use_drf_order, use_hdrf_order,
                          work_conserving)


@functools.partial(jax.jit, static_argnames=(
    "layout", "max_rounds", "max_gang_iters", "per_node_cap", "herd_mode",
    "score_families", "use_queue_cap", "use_drf_order", "use_hdrf_order",
    "work_conserving"), donate_argnums=(0, 1))
def solve_allocate_delta(f2d, i2d, f_idx, f_vals, i_idx, i_vals, layout,
                         score_params: Dict[str, jnp.ndarray],
                         max_rounds: int = 64,
                         max_gang_iters: int = 12,
                         per_node_cap: int = 0,
                         herd_mode: str = "pack",
                         score_families: Tuple[str, ...] = ("binpack",),
                         use_queue_cap: bool = False,
                         use_drf_order: bool = False,
                         use_hdrf_order: bool = False,
                         work_conserving: bool = True):
    """Fused dirty-chunk scatter + solve: the whole session is ONE device
    dispatch (this call) plus ONE readback (res.compact) — the delta
    upload (ops.device_cache) rides the solve's argument transfer
    instead of paying its own two scatter dispatches.

    f2d/i2d are the donated device-resident chunked buffers; f_idx/f_vals
    (and i_idx/i_vals) are the dirty chunk indices and replacement chunk
    contents (duplicate indices write identical values, so power-of-two
    padding is a no-op). Returns (result, new_f2d, new_i2d) — the caller
    must retain the returned buffers (donation invalidates the inputs).
    """
    f2d = f2d.at[f_idx].set(f_vals)
    i2d = i2d.at[i_idx].set(i_vals)
    nf = max(off + size for k, kind, off, size, shape in layout
             if kind == "f")
    ni = max(off + size for k, kind, off, size, shape in layout
             if kind != "f")
    arrays = _unpack(f2d.reshape(-1)[:nf], i2d.reshape(-1)[:ni], layout)
    res = solve_allocate(arrays, score_params, max_rounds, max_gang_iters,
                         per_node_cap, herd_mode, score_families,
                         use_queue_cap, use_drf_order, use_hdrf_order,
                         work_conserving)
    return res, f2d, i2d
