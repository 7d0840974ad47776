"""Node-axis-sharded eviction solve (preempt/reclaim at mesh scale).

Victims partition naturally by the node that hosts them, so the victim
axis shards EXACTLY like the node axis of the allocate solver
(parallel/sharded_solver.py): the host re-lays victims out per node
shard (shard_victims), each device runs the per-job closed-form
eviction-minimal solve (ops/evict.py solve_evict_uniform) over its own
nodes + victims, and the only cross-device traffic per job step is one
psum of the absorbable-count total plus [N]-vector all_gathers for the
score-ordered spread — the same ICI profile as the allocate kernel.

The per-task scan kernel (solve_evict) stays single-device: its victim
prefix walk is sequential per claimer and does not dominate at scale;
the uniform gang path here is the scale path (BENCH config #4).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.evict import EvictResult, absorb_counts, spread_counts
from ..ops.solver import NEG, _segment_prefix, le_fits, score_matrix
from .sharded_solver import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def shard_victims(victims: Dict[str, np.ndarray], N: int, D: int):
    """Re-lay victim arrays so each device's slice holds exactly the
    victims of its node shard (cheapest-first order per node preserved).
    Returns (sharded victims dict, perm) where perm[i] = original victim
    index at sharded slot i (-1 for padding)."""
    v_node = np.asarray(victims["v_node"])
    v_valid = np.asarray(victims["v_valid"])
    n_loc = N // D
    shard_of = np.where(v_valid, v_node // n_loc, -1)
    per_shard = [np.nonzero(shard_of == d)[0] for d in range(D)]
    v_cap = max((len(p) for p in per_shard), default=1)
    v_cap = max(v_cap, 1)
    V2 = v_cap * D
    R = victims["v_req"].shape[1]
    J = victims["elig"].shape[0]
    out = {
        "v_req": np.zeros((V2, R), np.float32),
        "v_node": np.zeros(V2, np.int32),
        "v_valid": np.zeros(V2, bool),
        "elig": np.zeros((J, V2), bool),
        "job_need": np.asarray(victims["job_need"]),
        "job_req": np.asarray(victims["job_req"]),
        "job_acct": np.asarray(victims["job_acct"]),
        "job_count": np.asarray(victims["job_count"]),
    }
    perm = np.full(V2, -1, np.int32)
    for d, idxs in enumerate(per_shard):
        sl = slice(d * v_cap, d * v_cap + len(idxs))
        out["v_req"][sl] = victims["v_req"][idxs]
        out["v_node"][sl] = v_node[idxs]
        out["v_valid"][sl] = True
        out["elig"][:, sl] = np.asarray(victims["elig"])[:, idxs]
        perm[d * v_cap:d * v_cap + len(idxs)] = idxs
    return out, perm


@functools.partial(jax.jit, static_argnames=(
    "mesh", "score_families", "require_freed_covers", "stop_at_need"))
def _solve_sharded(arrays, victims, score_params, mesh,
                   score_families, require_freed_covers, stop_at_need):
    a = arrays
    v = victims
    T = a["task_init_req"].shape[0]
    N = a["node_idle"].shape[0]
    J = a["job_min"].shape[0]
    D = mesh.devices.size

    in_specs_a = {
        "task_init_req": P(), "task_req": P(), "task_job": P(),
        "task_rank": P(), "task_sig": P(), "task_valid": P(),
        "job_min": P(), "job_valid": P(),
        "node_idle": P("n", None), "node_extra_future": P("n", None),
        "node_used": P("n", None), "node_alloc": P("n", None),
        "node_valid": P("n"),
        "sig_masks": P(None, "n"), "thresholds": P(),
        "scalar_dim_mask": P(),
    }
    in_specs_v = {
        "v_req": P("n", None), "v_node": P("n"), "v_valid": P("n"),
        "elig": P(None, "n"), "job_need": P(), "job_req": P(),
        "job_acct": P(), "job_count": P(),
    }
    params_spec = {k: (P("n") if k == "node_static" else P())
                   for k in score_params}

    # static D=1 fast path: every all_gather degrades to identity and is
    # skipped at trace time (same contract as parallel/sharded_solver.py)
    D1 = D == 1

    def kernel(a, v, sp):
        thr = a["thresholds"]
        sm = a["scalar_dim_mask"]
        n_loc = a["node_idle"].shape[0]
        my_base = jnp.int32(0) if D1 \
            else jax.lax.axis_index("n") * n_loc

        def gather(x):
            return x if D1 else jax.lax.all_gather(x, "n", tiled=True)
        v_req = v["v_req"]
        v_node_loc = v["v_node"] - my_base          # local node index
        v_valid = v["v_valid"]
        elig = v["elig"]
        need = v["job_need"]
        job_req = v["job_req"]
        job_acct = v["job_acct"]
        job_count = v["job_count"]
        V = v_req.shape[0]
        future0 = a["node_idle"] + a["node_extra_future"]
        job_score_loc = score_matrix(job_req, future0, a["node_used"],
                                     a["node_alloc"], sp, score_families)
        seg_start = jnp.concatenate(
            [jnp.array([True]), v_node_loc[1:] != v_node_loc[:-1]])
        vidx = jnp.arange(V)
        sig_feas_t = a["sig_masks"][a["task_sig"]] | ~a["task_valid"][:, None]
        job_feas_loc = jnp.ones((J, n_loc), jnp.int32).at[a["task_job"]].min(
            sig_feas_t.astype(jnp.int32)) > 0
        first_task = jnp.full((J,), T - 1, jnp.int32).at[
            a["task_job"]].min(jnp.arange(T, dtype=jnp.int32))
        task_pos = jnp.arange(T, dtype=jnp.int32) - first_task[a["task_job"]]

        def step(carry, j):
            future, alive, evby, assigned, jalloc = carry
            r = job_req[j]
            sig = jnp.where(sm, r > 10.0, r > 0.0)
            r_fit = jnp.where(sig, r, 0.0)
            count = (jnp.minimum(job_count[j], need[j]) if stop_at_need
                     else job_count[j])
            active = a["job_valid"][j] & (count > 0)

            elig_v = elig[j] & alive & v_valid
            vreq_m = v_req * elig_v[:, None]
            prefix_incl = _segment_prefix(vreq_m, seg_start) + vreq_m
            ptot = jax.ops.segment_sum(
                vreq_m, jnp.clip(v_node_loc, 0, n_loc - 1),
                num_segments=n_loc)
            has_v = jax.ops.segment_max(
                elig_v.astype(jnp.int32), jnp.clip(v_node_loc, 0, n_loc - 1),
                num_segments=n_loc) > 0
            base = (jnp.zeros_like(future) if require_freed_covers
                    else future)
            # per-node absorption counts: SAME math as the single-device
            # kernel (ops/evict.py absorb_counts), on this shard's nodes
            feas_n = job_feas_loc[j] & a["node_valid"]
            m_all_loc, f_loc, cap_loc = absorb_counts(
                r, r_fit, sig, base, ptot, has_v, feas_n, thr, sm,
                float(T))

            # replicated spread over gathered [N] vectors (same math as
            # ops/evict.py spread_counts)
            score_all = gather(job_score_loc[j])
            m_all = gather(m_all_loc)
            f_all = gather(f_loc)
            cap_extra = gather(cap_loc)

            total = jnp.sum(m_all).astype(jnp.int32)
            satisfied = (total >= need[j]) if stop_at_need \
                else jnp.bool_(True)
            do = active & satisfied & (total > 0)
            count = jnp.where(do, jnp.minimum(count, total), 0)

            score_j = jnp.where(m_all > 0, score_all, NEG)
            c, order, cum = spread_counts(count, score_j, m_all, f_all,
                                          cap_extra)

            is_mine = (a["task_job"] == j) & a["task_valid"]
            p = task_pos
            node_for_p = order[jnp.clip(
                jnp.searchsorted(cum, p.astype(cum.dtype), side="right"),
                0, N - 1)]
            placed_t = is_mine & (p < count)
            assigned = jnp.where(placed_t, node_for_p.astype(jnp.int32),
                                 assigned)

            # local eviction for this shard's slice of c
            c_loc = jax.lax.dynamic_slice(c, (my_base,), (n_loc,))
            demand_fit = c_loc.astype(jnp.float32)[:, None] \
                * r_fit[None, :]
            demand_acct = c_loc.astype(jnp.float32)[:, None] \
                * job_acct[j][None, :]
            fit_now_n = le_fits(demand_fit, base, thr, sm,
                                ignore_req=demand_fit)
            need_evict_n = (c_loc > 0) & ~fit_now_n
            vloc = jnp.clip(v_node_loc, 0, n_loc - 1)
            fit_at = le_fits(demand_fit[vloc], base[vloc] + prefix_incl,
                             thr, sm, ignore_req=demand_fit[vloc]) & elig_v
            cut = jax.ops.segment_min(jnp.where(fit_at, vidx, V), vloc,
                                      num_segments=n_loc)
            ev = (elig_v & need_evict_n[vloc] & (vidx <= cut[vloc])
                  & (cut[vloc] < V))
            freed = jax.ops.segment_sum(v_req * ev[:, None], vloc,
                                        num_segments=n_loc)
            future = future + freed - demand_acct
            alive = alive & ~ev
            evby = jnp.where(ev, j, evby)
            jalloc = jalloc.at[j].add(count)
            return (future, alive, evby, assigned, jalloc), None

        init = (future0, v_valid, jnp.full((V,), -1, jnp.int32),
                jnp.full((T,), -1, jnp.int32), jnp.zeros(J, jnp.int32))
        carry, _ = jax.lax.scan(step, init, jnp.arange(J))
        future, alive, evby, assigned, jalloc = carry
        # gather local victim verdicts into the sharded global layout
        evby_all = gather(evby)
        return assigned, evby_all, jalloc

    mapped = shard_map(
        kernel, mesh=mesh,
        in_specs=(in_specs_a, in_specs_v, params_spec),
        out_specs=(P(), P(), P()))
    assigned, evby, jalloc = mapped(
        {k: a[k] for k in in_specs_a}, {k: v[k] for k in in_specs_v},
        dict(score_params))
    return assigned, evby, jalloc


def solve_evict_uniform_sharded(arrays, victims, score_params, mesh: Mesh,
                                score_families: Tuple[str, ...] = ("kube",),
                                require_freed_covers: bool = False,
                                stop_at_need: bool = True) -> EvictResult:
    """Host wrapper: shard the victims by node shard, run the mesh kernel,
    scatter the verdicts back to the caller's victim order."""
    N = arrays["node_idle"].shape[0]
    D = mesh.devices.size
    assert N % D == 0, \
        f"device count {D} must divide the node axis {N}"
    sharded, perm = shard_victims(victims, N, D)
    assigned, evby_s, jalloc = _solve_sharded(
        arrays, sharded, score_params, mesh, score_families,
        require_freed_covers, stop_at_need)
    evby_s = np.asarray(evby_s)
    V = victims["v_req"].shape[0]
    evby = np.full(V, -1, np.int32)
    live = perm >= 0
    evby[perm[live]] = evby_s[live]
    return EvictResult(assigned=np.asarray(assigned), evicted_by=evby,
                       job_placed=np.asarray(jalloc))
