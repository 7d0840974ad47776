"""Node-axis-sharded allocate solver: shard_map over a device mesh.

Scaling axis (SURVEY.md §5.7-5.8): the reference bounds per-task work on
big clusters by sampling nodes; the TPU build shards the node axis of the
task x node problem across the mesh instead. Layout:

- node arrays ([N,R] idle/used/alloc, [N] npods/valid, sig_masks[S,N]) are
  sharded along the mesh 'n' axis;
- task/job arrays ([T,*], [J]) are replicated;
- each device computes feasibility/scores for its node shard only (the
  [T, N/D] matrices are the memory hog), admission prefix-sums run
  node-locally, and the small cross-device exchanges are [N] score/slot
  vectors (all_gather) and [T] choice/admit vectors (psum/pmax) over ICI.

The gang fixpoint and round loop conditions depend only on replicated
values, so every device executes identical trip counts.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.solver import (
    NEG, BIG_KEY, SolveResult, _queue_cap_mask, _segment_prefix,
    drf_state, fits_matrix, le_fits, queue_cap_state, score_matrix,
)


def shard_map(f, **kwargs):
    """``jax.shard_map`` with replication checking off. The body must read
    every array from its own arguments: under jit, JAX refuses to capture
    an auto-sharded tracer from the enclosing scope into the manual mesh
    context."""
    return jax.shard_map(f, check_vma=False, **kwargs)


def make_mesh(devices=None, axis: str = "n") -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (axis,))


def arena_mesh(devices=None, axis: str = "n", max_devices: int = 0) -> Mesh:
    """Mesh over the largest power-of-two device prefix: the padded node
    axis is always a multiple of 8 (ops.arrays.bucket quarter-steps,
    floor 8), so any power-of-two D <= 8 divides it evenly — a 6-device
    host would otherwise fail the sharded solver's N % D check."""
    if devices is None:
        devices = jax.devices()
    if max_devices:
        devices = devices[:max_devices]
    d = 1
    while d * 2 <= len(devices) and d < 8:
        d *= 2
    return make_mesh(list(devices)[:d], axis)


#: static solve flags solve_allocate_sharded_packed2d accepts — a strict
#: subset of the single-device entries' (no per_node_cap); the bucket
#: prewarmer filters a session's flag set against this before warming the
#: sharded variant (ops.precompile.BucketPrewarmer)
PACKED2D_FLAGS = ("max_rounds", "max_gang_iters", "herd_mode",
                  "score_families", "use_queue_cap", "use_drf_order",
                  "use_hdrf_order", "work_conserving", "fused")


@functools.partial(jax.jit, static_argnames=("mesh", "max_rounds",
                                             "max_gang_iters", "herd_mode",
                                             "score_families",
                                             "use_queue_cap",
                                             "use_drf_order",
                                             "use_hdrf_order",
                                             "work_conserving", "fused"))
def solve_allocate_sharded(arrays: Dict[str, jnp.ndarray],
                           score_params: Dict[str, jnp.ndarray],
                           mesh: Mesh,
                           max_rounds: int = 64,
                           max_gang_iters: int = 12,
                           herd_mode: str = "pack",
                           score_families: Tuple[str, ...] = ("binpack",),
                           use_queue_cap: bool = False,
                           use_drf_order: bool = False,
                           use_hdrf_order: bool = False,
                           work_conserving: bool = True,
                           fused: str = "auto") -> SolveResult:
    """The node-axis-sharded twin of ``ops.solver.solve_allocate``: the
    same flags (``per_node_cap`` aside) give the same decisions."""
    a = arrays
    T = a["task_init_req"].shape[0]
    N = a["node_idle"].shape[0]
    J = a["job_min"].shape[0]
    D = mesh.devices.size
    assert N % D == 0, f"node axis {N} must divide device count {D}"
    # fused pallas choice kernel PER SHARD (ops/pallas_kernels.py): each
    # device's [T, N/D] feasibility/score/argmax pass runs in one VMEM
    # kernel; only the [T]/[N/D] reductions cross the ICI. Same gate as
    # the single-device solver, applied to the SHARD's node width.
    from ..ops.pallas_kernels import fused_choice_auto, use_interpret
    use_fused = fused == "on" or (
        fused == "auto" and not use_interpret()
        and fused_choice_auto(T, N // D)
        and herd_mode in ("pack", "spread"))

    in_specs = {
        "task_init_req": P(), "task_req": P(), "task_job": P(),
        "task_rank": P(), "task_sig": P(), "task_counts_ready": P(),
        "task_valid": P(), "job_min": P(), "job_ready_base": P(),
        "job_queue": P(), "job_valid": P(),
        "node_idle": P("n", None), "node_extra_future": P("n", None),
        "node_used": P("n", None), "node_alloc": P("n", None),
        "node_npods": P("n"), "node_max_pods": P("n"), "node_valid": P("n"),
        "sig_masks": P(None, "n"), "thresholds": P(), "scalar_dim_mask": P(),
    }
    if use_queue_cap:
        # queue state is tiny and fairness is a global property: replicate
        # it and keep every device's bookkeeping identical (the only
        # cross-device input is the cluster-total capacity, one psum)
        in_specs.update({"queue_weight": P(), "queue_capability": P(),
                         "queue_allocated": P(), "queue_request": P()})
    if use_drf_order:
        # live DRF ordering: shares are [J] reductions over replicated
        # job state, identical on every device
        in_specs.update({"job_drf_allocated": P(), "drf_total": P(),
                         "job_drf_prerank": P()})
    if use_hdrf_order:
        # hierarchical DRF: the queue-path tree is tiny and its share
        # recursion runs on replicated [H]/[J] state (ops/hdrf.py).
        # Meaningless without the DRF ordering machinery it replaces.
        assert use_drf_order, "use_hdrf_order requires use_drf_order"
        in_specs.update({
            "hdrf_parent": P(), "hdrf_weight": P(), "hdrf_depth": P(),
            "hdrf_is_leaf": P(), "hdrf_leaf_req": P(),
            "hdrf_job_leaf": P(), "hdrf_ancestors": P(),
            "hdrf_total_allocated": P()})
    params_spec = {k: (P("n") if k == "node_static" else P())
                   for k in score_params}

    # D == 1 is a static property of the mesh: every collective below
    # degrades to identity, so they are skipped at TRACE time — the
    # compiled 1-device program contains no all_gather/psum/pmax at all
    # and the shard_map wrapper costs nothing beyond the call itself
    # (tests/test_parallel.py asserts the jaxpr is collective-free)
    D1 = D == 1

    def kernel(a, sp):
        thr = a["thresholds"]
        scalar_mask = a["scalar_dim_mask"]
        counts_ready = a["task_counts_ready"].astype(jnp.int32)
        rank = a["task_rank"]
        n_loc = a["node_idle"].shape[0]
        my_base = jnp.int32(0) if D1 \
            else jax.lax.axis_index("n") * n_loc
        sig_feas = a["sig_masks"][a["task_sig"]] & a["node_valid"][None, :]
        if use_fused:
            from ..ops.pallas_kernels import fused_choice, fused_setup
            sig_i8, inv_alloc, fused_pars, node_static = fused_setup(
                {"sig_feas": sig_feas, "node_alloc": a["node_alloc"]},
                sp, a["task_init_req"].shape[1])

        if use_queue_cap:
            total_loc = jnp.sum(
                a["node_alloc"]
                * a["node_valid"][:, None].astype(jnp.float32), axis=0)
            total = total_loc if D1 else jax.lax.psum(total_loc, "n")
            Q, deserved, task_queue, q_perm, q_seg_start = queue_cap_state(
                a, rank, thr, total, ease_unrequested=work_conserving)
            qalloc0 = a["queue_allocated"]
            # static-sort gathers hoisted out of the round loop (see
            # ops/solver.py — the live-DRF path re-sorts per round)
            qs_q = task_queue[q_perm]
            qs_req = a["task_req"][q_perm]
        else:
            qalloc0 = jnp.zeros((1, a["node_idle"].shape[1]), jnp.float32)

        if use_drf_order:
            jobres0, drf_rank, drf_cap = drf_state(a, rank)
            if use_hdrf_order:
                # replicated [H]/[J]/[T] math: every device runs the
                # identical tree recursion + cap (ops/hdrf.py hdrf_state)
                from ..ops.hdrf import hdrf_state
                hdrf_rank_cap = hdrf_state(a, rank)
        else:
            jobres0 = jnp.zeros((1, a["node_idle"].shape[1]), jnp.float32)

        def feas_at(eligible, avail, npods, t_loc, mine):
            """Feasibility of (task, local node t_loc[task]) for this
            shard — the pointwise re-derivation the fused path uses in
            place of materializing the [T, N_loc] matrix."""
            av = avail[jnp.clip(t_loc, 0, n_loc - 1)]
            fit = le_fits(a["task_init_req"], av, thr, scalar_mask)
            sig = jnp.take_along_axis(
                sig_feas, jnp.clip(t_loc, 0, n_loc - 1)[:, None],
                axis=1)[:, 0]
            pods = (npods < a["node_max_pods"])[
                jnp.clip(t_loc, 0, n_loc - 1)]
            return fit & sig & pods & eligible & mine

        def choose(eligible, avail, idle, npods, feas0=None):
            """Global choice per task: local scoring + cross-device argmax,
            with the waterfall herd spread computed on gathered [N]
            vectors. feas0: optional precomputed fits & sig & pods mask
            (the hdrf prefilter already paid for it this round). In fused
            mode the local [T, N_loc] pass runs in the pallas kernel and
            target feasibility re-derives pointwise."""
            used_now = a["node_used"] + (a["node_idle"] - idle)
            if use_fused:
                pods_ok_v = npods < a["node_max_pods"]
                loc_val, loc_idx_l, node_score_loc = fused_choice(
                    a["task_init_req"], avail, used_now, inv_alloc,
                    node_static, eligible.astype(jnp.float32),
                    pods_ok_v.astype(jnp.float32), sig_i8, fused_pars,
                    score_families)
                loc_idx = loc_idx_l + my_base
                feas = None  # fused: no [T,N_loc] matrix materialized
            else:
                if feas0 is None:
                    pods_ok = (npods < a["node_max_pods"])[None, :]
                    feas0 = (fits_matrix(a["task_init_req"], avail, thr,
                                         scalar_mask)
                             & sig_feas & pods_ok)
                feas = feas0 & eligible[:, None]
                score = score_matrix(a["task_init_req"], avail, used_now,
                                     a["node_alloc"], sp, score_families)
                masked = jnp.where(feas, score, NEG)
                loc_val = jnp.max(masked, axis=1)                 # [T]
                loc_idx = jnp.argmax(masked, axis=1).astype(jnp.int32) \
                    + my_base
                node_score_loc = jnp.max(masked, axis=0)          # [N_loc]

            # personal best across devices (D=1: the local best IS global)
            if D1:
                has_any = loc_val > NEG / 2
                personal = jnp.where(has_any, loc_idx, -1)
            else:
                vals = jax.lax.all_gather(loc_val, "n")           # [D,T]
                idxs = jax.lax.all_gather(loc_idx, "n")           # [D,T]
                best_dev = jnp.argmax(vals, axis=0)               # [T]
                personal = jnp.take_along_axis(
                    idxs, best_dev[None, :], axis=0)[0]           # [T]
                has_any = jnp.max(vals, axis=0) > NEG / 2
                personal = jnp.where(has_any, personal, -1)

            if herd_mode in ("pack", "spread"):
                n_elig = jnp.maximum(jnp.sum(eligible), 1)
                mean_req = jnp.sum(a["task_init_req"] * eligible[:, None],
                                   axis=0) / n_elig
                sig = mean_req > jnp.where(scalar_mask, 10.0, 0.0)
                slots_dim = jnp.where(
                    sig[None, :],
                    jnp.floor((avail + thr[None, :])
                              / jnp.maximum(mean_req[None, :], 1e-9)),
                    jnp.inf)
                slots_loc = jnp.min(slots_dim, axis=1)
                slots_loc = jnp.minimum(
                    slots_loc, (a["node_max_pods"] - npods).astype(jnp.float32))
                slots_loc = jnp.clip(slots_loc, 0.0, float(T))

                if D1:
                    node_score, slots = node_score_loc, slots_loc
                else:
                    node_score = jax.lax.all_gather(
                        node_score_loc, "n", tiled=True)          # [N]
                    slots = jax.lax.all_gather(slots_loc, "n",
                                               tiled=True)
                has_slot = slots > 0
                order = jnp.argsort(-jnp.where(has_slot, node_score, NEG))
                pos = jnp.cumsum(eligible.astype(jnp.int32)) - 1
                if herd_mode == "spread":
                    # near-best striping (ops/solver.py _waterfall_choice):
                    # stripe only across nodes tying the best herd score
                    masked_ns = jnp.where(has_slot, node_score, NEG)
                    best_s = jnp.max(masked_ns)
                    eps = 1e-5 * jnp.maximum(jnp.abs(best_s), 1.0)
                    near = has_slot & (masked_ns >= best_s - eps)
                    m = jnp.maximum(jnp.sum(near), 1)
                    target = order[jnp.mod(jnp.maximum(pos, 0), m)]
                else:
                    cum = jnp.cumsum(slots[order])
                    idx = jnp.searchsorted(cum, pos.astype(jnp.float32),
                                           side="right")
                    target = order[jnp.clip(idx, 0, N - 1)]
                target = target.astype(jnp.int32)
                # feasibility of each task at its (possibly remote) target
                t_loc = target - my_base
                mine = (t_loc >= 0) & (t_loc < n_loc)
                if feas is None:  # fused path: pointwise re-derivation
                    t_ok_loc = feas_at(eligible, avail, npods, t_loc, mine)
                else:
                    t_ok_loc = jnp.take_along_axis(
                        feas, jnp.clip(t_loc, 0, n_loc - 1)[:, None],
                        axis=1)[:, 0] & mine
                t_ok = t_ok_loc if D1 else (
                    jax.lax.psum(t_ok_loc.astype(jnp.int32), "n") > 0)
                choice = jnp.where(t_ok, target, personal)
            else:
                choice = personal
            return choice

        def admit_local(choice, avail, npods, r_rank):
            """Admission for choices landing in this device's shard
            (feasibility of the chosen node was already established by
            choose(); the prefix re-checks capacity only)."""
            c_loc = choice - my_base
            mine = (c_loc >= 0) & (c_loc < n_loc) & (choice >= 0)
            c_loc = jnp.where(mine, c_loc, -1)
            key = jnp.where(mine, c_loc * (T + 1) + r_rank, BIG_KEY)
            perm = jnp.argsort(key)
            s_choice = c_loc[perm]
            s_active = s_choice >= 0
            s_fit = a["task_init_req"][perm] * s_active[:, None]
            seg_start = jnp.concatenate(
                [jnp.array([True]), s_choice[1:] != s_choice[:-1]])
            prefix = _segment_prefix(s_fit, seg_start)
            s_avail = avail[jnp.maximum(s_choice, 0)]
            fits = le_fits(prefix + s_fit, s_avail, thr, scalar_mask,
                           ignore_req=s_fit) & s_active
            ones = jnp.ones_like(s_choice)
            pos = _segment_prefix(
                ones[:, None].astype(jnp.float32), seg_start)[:, 0]
            pods_fit = (npods[jnp.maximum(s_choice, 0)] + pos) \
                < a["node_max_pods"][jnp.maximum(s_choice, 0)]
            admit_sorted = fits & pods_fit
            admit = jnp.zeros(T, dtype=bool).at[perm].set(admit_sorted)
            debit = jax.ops.segment_sum(
                a["task_req"] * admit[:, None], jnp.maximum(c_loc, 0),
                num_segments=n_loc)
            pod_inc = jax.ops.segment_sum(
                admit.astype(jnp.int32), jnp.maximum(c_loc, 0),
                num_segments=n_loc)
            # global admitted assignment: each task admitted on one device
            new_assign = jnp.where(admit, choice, -1)
            if not D1:
                new_assign = jax.lax.pmax(new_assign, "n")        # [T]
            return new_assign, debit, pod_inc

        def phase_rounds(st, use_future, capped=True):
            def cond(s):
                return s[-1] & (s[-2] < max_rounds)

            def body(s):
                (idle, pipe, npods, qalloc, jobres, assigned, kind,
                 excluded, rounds, _) = s
                avail = (idle + a["node_extra_future"] - pipe) if use_future \
                    else idle
                eligible = (a["task_valid"] & (assigned < 0)
                            & ~excluded[a["task_job"]])
                feas0 = None
                if use_drf_order:
                    if use_hdrf_order:
                        # placeability prefilter (see ops/solver.py): a
                        # task no node in ANY shard can take must not
                        # hold its sibling group's min key or budget.
                        # Dense mode hands feas0 to choose() so the
                        # [T,N_loc] matrix is built once per round; fused
                        # mode pays one extra kernel pass instead.
                        pods_ok_v = npods < a["node_max_pods"]
                        if use_fused:
                            used_now0 = a["node_used"] \
                                + (a["node_idle"] - idle)
                            best_s0, _, _ = fused_choice(
                                a["task_init_req"], avail, used_now0,
                                inv_alloc, node_static,
                                eligible.astype(jnp.float32),
                                pods_ok_v.astype(jnp.float32), sig_i8,
                                fused_pars, score_families)
                            if not D1:
                                best_s0 = jax.lax.pmax(best_s0, "n")
                            placeable = best_s0 > NEG * 0.5
                        else:
                            feas0 = (fits_matrix(a["task_init_req"],
                                                 avail, thr, scalar_mask)
                                     & sig_feas & pods_ok_v[None, :])
                            any_loc = jnp.any(feas0, axis=1)
                            placeable = any_loc if D1 else (
                                jax.lax.psum(any_loc.astype(jnp.int32),
                                             "n") > 0)
                        r_rank, eligible = hdrf_rank_cap(
                            eligible & placeable, jobres)
                    else:
                        r_rank = drf_rank(jobres)
                        eligible = drf_cap(eligible, jobres)
                else:
                    r_rank = rank
                if use_queue_cap:
                    # overflow pass relaxes deserved, never capability
                    bound = deserved if capped else a["queue_capability"]
                    qrem = jnp.maximum(bound - qalloc, 0.0)
                    if use_drf_order:
                        qp = jnp.lexsort((r_rank, task_queue))
                        eligible = eligible & _queue_cap_mask(
                            eligible, task_queue, a["task_req"], qrem,
                            thr, scalar_mask, qp, q_seg_start)
                    else:
                        eligible = eligible & _queue_cap_mask(
                            eligible, task_queue, a["task_req"], qrem,
                            thr, scalar_mask, q_perm, q_seg_start,
                            qs_q, qs_req)
                choice = choose(eligible, avail, idle, npods, feas0)
                new_assign, debit, pod_inc = admit_local(
                    choice, avail, npods, r_rank)
                got = new_assign >= 0
                assigned = jnp.where(got, new_assign, assigned)
                kind = jnp.where(got, jnp.int32(1 if use_future else 0), kind)
                if use_queue_cap:
                    # got is replicated (pmax in admit_local), so every
                    # device books identical queue allocations
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

            out = jax.lax.while_loop(cond, body, st + (jnp.bool_(True),))
            return out[:-1]

        # job order position for the gang-exclusion tie-break (replicated)
        job_first_rank = jnp.full((J,), T, jnp.int32).at[a["task_job"]].min(
            jnp.where(a["task_valid"], rank, T))

        def gang_body(s):
            (idle, pipe, npods, qalloc, jobres, assigned, kind, excluded,
             rounds, _, it, revert_count, deferred, processed) = s
            # deferred-retry queue, replicated math (see ops/solver.py
            # gang_body): doubly-reverted jobs retry one at a time in rank
            # order while the rest sit out
            unproc = deferred & ~processed & ~excluded
            cur = jnp.argmin(jnp.where(unproc, job_first_rank, BIG_KEY))
            solo = unproc & (jnp.arange(J) == cur)
            barred = deferred & ~solo
            st = (idle, pipe, npods, qalloc, jobres, assigned, kind,
                  excluded | barred, rounds)
            st = phase_rounds(st, False)
            st = phase_rounds(st, True)
            if use_queue_cap and work_conserving:
                # work-conserving overflow (see ops/solver.py phase_rounds)
                st = phase_rounds(st, False, capped=False)
                st = phase_rounds(st, True, capped=False)
            (idle, pipe, npods, qalloc, jobres, assigned, kind, _masked,
             rounds) = st
            alloc_counts = jax.ops.segment_sum(
                ((assigned >= 0) & (kind == 0)).astype(jnp.int32)
                * counts_ready, a["task_job"], num_segments=J)
            ready = ((a["job_ready_base"] + alloc_counts) >= a["job_min"]) \
                & a["job_valid"]
            has_alloc = jax.ops.segment_sum(
                ((assigned >= 0) & (kind == 0)).astype(jnp.int32),
                a["task_job"], num_segments=J) > 0
            revert_job = ~ready & a["job_valid"] & ~excluded & ~barred \
                & has_alloc
            revert_task = (revert_job[a["task_job"]] & (assigned >= 0)
                           & (kind == 0))
            # credit back to this shard's nodes only
            rv_loc = jnp.where(revert_task, assigned - my_base, -1)
            rv_mine = (rv_loc >= 0) & (rv_loc < n_loc)
            credit = jax.ops.segment_sum(
                a["task_req"] * rv_mine[:, None], jnp.maximum(rv_loc, 0),
                num_segments=n_loc)
            pod_credit = jax.ops.segment_sum(
                rv_mine.astype(jnp.int32), jnp.maximum(rv_loc, 0),
                num_segments=n_loc)
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
            # retry policy matches the single-device gang fixpoint
            # (ops/solver.py gang_body): first revert retries in parallel,
            # second defers to the solo queue, a failed solo excludes
            revert_count = revert_count + revert_job.astype(jnp.int32)
            excluded = excluded | (solo & revert_job)
            processed = processed | (solo & jnp.any(unproc))
            deferred = deferred | (revert_job & (revert_count >= 2))
            any_more = jnp.any(revert_job) | jnp.any(
                deferred & ~processed & ~excluded)
            return (idle, pipe, npods, qalloc, jobres, assigned, kind,
                    excluded, rounds, any_more, it + 1,
                    revert_count, deferred, processed)

        init = (a["node_idle"], jnp.zeros_like(a["node_idle"]),
                a["node_npods"], qalloc0, jobres0,
                jnp.full((T,), -1, jnp.int32),
                jnp.full((T,), -1, jnp.int32), ~a["job_valid"],
                jnp.int32(0), jnp.bool_(True), jnp.int32(0),
                jnp.zeros(J, jnp.int32), jnp.zeros(J, dtype=bool),
                jnp.zeros(J, dtype=bool))
        s = jax.lax.while_loop(
            lambda s: s[-5] & (s[-4] < max_gang_iters), gang_body, init)
        (idle, pipe, npods, _, _, assigned, kind, excluded, rounds,
         _, _, _, _, _) = s
        alloc_counts = jax.ops.segment_sum(
            ((assigned >= 0) & (kind == 0)).astype(jnp.int32) * counts_ready,
            a["task_job"], num_segments=J)
        job_ready = ((a["job_ready_base"] + alloc_counts) >= a["job_min"]) \
            & a["job_valid"]
        return assigned, kind, job_ready, rounds

    mapped = shard_map(
        kernel, mesh=mesh,
        in_specs=(in_specs, params_spec),
        out_specs=(P(), P(), P(), P()))
    # device_dict may carry extra arrays (queue fairness) this kernel
    # doesn't consume; keep the pytree congruent with in_specs
    assigned, kind, job_ready, rounds = mapped(
        {k: a[k] for k in in_specs}, dict(score_params))
    return SolveResult(assigned=assigned, kind=kind, job_ready=job_ready,
                       rounds=rounds)


@functools.partial(jax.jit, static_argnames=(
    "layout", "mesh", "max_rounds", "max_gang_iters", "herd_mode",
    "score_families", "use_queue_cap", "use_drf_order", "use_hdrf_order",
    "work_conserving", "fused"))
def solve_allocate_sharded_packed2d(f2d, i2d, layout,
                                    score_params, mesh: Mesh,
                                    max_rounds: int = 64,
                                    max_gang_iters: int = 12,
                                    herd_mode: str = "pack",
                                    score_families=("binpack",),
                                    use_queue_cap: bool = False,
                                    use_drf_order: bool = False,
                                    use_hdrf_order: bool = False,
                                    work_conserving: bool = True,
                                    fused: str = "auto") -> SolveResult:
    """Sharded solve over the chunked device-resident buffers kept by
    ops.device_cache.PackedDeviceCache: the unpack slices fuse away on
    device, so a sharded deployment ships only dirty chunks per session
    exactly like the single-device path — no host re-upload and, at D=1,
    no re-sharding of the resident buffers on entry."""
    from ..ops.solver import _unpack

    nf = max(off + size for k, kind, off, size, shape in layout
             if kind == "f")
    ni = max(off + size for k, kind, off, size, shape in layout
             if kind != "f")
    arrays = _unpack(f2d.reshape(-1)[:nf], i2d.reshape(-1)[:ni], layout)
    return solve_allocate_sharded(arrays, score_params, mesh, max_rounds,
                                  max_gang_iters, herd_mode,
                                  score_families, use_queue_cap,
                                  use_drf_order, use_hdrf_order,
                                  work_conserving, fused)


@functools.partial(jax.jit, static_argnames=(
    "rep_layout", "node_layout", "mesh", "max_rounds", "max_gang_iters",
    "herd_mode", "score_families", "use_queue_cap", "use_drf_order",
    "use_hdrf_order", "work_conserving", "fused"))
def solve_allocate_sharded_arena(f_rep, i_rep, f_node, i_node,
                                 rep_layout, node_layout,
                                 score_params, mesh: Mesh,
                                 max_rounds: int = 64,
                                 max_gang_iters: int = 12,
                                 herd_mode: str = "pack",
                                 score_families=("binpack",),
                                 use_queue_cap: bool = False,
                                 use_drf_order: bool = False,
                                 use_hdrf_order: bool = False,
                                 work_conserving: bool = True,
                                 fused: str = "auto") -> SolveResult:
    """Sharded solve over the SHARDED device-resident arena
    (ops.device_cache.ShardedDeviceCache): ``f_rep``/``i_rep`` are the
    replicated chunked task/job buffers, ``f_node``/``i_node`` the
    ``[D, C, chunk]`` node buffers sharded along the mesh 'n' axis (one
    resident slab per device). The unpack below is sharding-preserving —
    slicing the chunked slabs and merging the leading shard axis keeps
    every node array split exactly as the shard_map in_specs demand, so a
    steady sharded session dispatches straight off the resident shards
    with no host re-upload and no cross-device resharding."""
    from ..ops.device_cache import NODE_COL_KEYS
    from ..ops.solver import _unpack

    D = mesh.devices.size
    nf = max((off + size for _k, kind, off, size, _s in rep_layout
              if kind == "f"), default=0)
    ni = max((off + size for _k, kind, off, size, _s in rep_layout
              if kind != "f"), default=0)
    arrays = _unpack(f_rep.reshape(-1)[:max(nf, 1)],
                     i_rep.reshape(-1)[:max(ni, 1)],
                     tuple(e for e in rep_layout))
    fn = f_node.reshape(D, -1)
    im = i_node.reshape(D, -1)
    for key, kind, off, size, pshape in node_layout:
        src = fn if kind == "f" else im
        v = src[:, off:off + size].reshape((D,) + tuple(pshape))
        if kind == "b":
            v = v.astype(bool)
        if key in NODE_COL_KEYS:
            # [D, S, N/D] -> [S, N]: the merged axis stays sharded on 'n'
            v = v.transpose(1, 0, 2).reshape(pshape[0], D * pshape[1])
        else:
            v = v.reshape((D * pshape[0],) + tuple(pshape[1:]))
        arrays[key] = v
    return solve_allocate_sharded(arrays, score_params, mesh, max_rounds,
                                  max_gang_iters, herd_mode,
                                  score_families, use_queue_cap,
                                  use_drf_order, use_hdrf_order,
                                  work_conserving, fused)
