"""Fused choice kernel (Pallas/TPU): feasibility + scoring + argmax in one
VMEM pass.

The round solver's per-round cost is HBM bandwidth: the XLA path
materializes several [T,N] float32/bool matrices per round (feasibility,
score, masked score, argmax input, per-node max — XLA's cost analysis
reports ~3.6 GB accessed per round body at 10k x 2k). This kernel fuses
the whole (task, node) pass: each (bt, bn) tile computes feasibility and
the plugin score families on the fly from the [R]-vector inputs, and only
[T]-sized argmax results and an [N]-sized per-node max ever touch HBM.

Semantics vs the dense path in ops.solver:
- feasibility == le_fits(req, avail) & sig_feas & pods_ok & eligible
  with the positional threshold rule (cpu=10 milli, mem=1 byte, scalars
  10 milli ignored when the request is <= 10);
- score mirrors score_matrix(...) term for term in the same operation
  order. On the REAL TPU backend the results are bitwise identical
  (verified across a 40-seed corpus: identical assignments); under the
  CPU interpret path XLA's FMA contraction can differ by 1 ulp, which
  may flip argmax TIES — the CPU parity tests therefore assert
  outcome equivalence (equal scores at divergent choices) rather than
  bit equality. The kernel only runs for real on TPU (the solver's
  auto gate checks the backend).
- best_idx == argmax semantics of jnp.argmax (first max wins: in-tile
  the min index among max-achievers, cross-tile strictly-greater);
- node_max == max over tasks of the masked score.

Layout: the [R]-indexed inputs arrive TRANSPOSED ([R,T] / [R,N]) so the
long axis sits on lanes; the round-invariant signature mask is an int8
[T,N] (one read per round instead of several float32 matrices). Grid is
(T/bt, N/bn) with the node axis fastest: per-task running (best, idx)
accumulate in a revisited VMEM output block; the per-node max block is
revisited across the slow axis (HBM round trip, [N]-sized).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def platform() -> str:
    """The platform the solve traces for. Tests that compile for a
    described (unattached) TPU monkeypatch this one function."""
    return jax.default_backend()


def use_interpret() -> bool:
    """Pallas interpret mode: taken on the CPU backend only. A platform
    with no Mosaic backend raises instead of silently interpreting. The
    solvers' ``fused="auto"`` gate takes the kernel exactly when this is
    False."""
    p = platform()
    if p not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas kernel path for platform {p!r}")
    return p == "cpu"


#: positional thresholds (api.resource): cpu millicores, memory bytes,
#: scalar milli-units. Scalar dims (r >= 2) are ignored when the request
#: itself is <= 10 milli.
_THR_CPU = 10.0
_THR_MEM = 1.0
_THR_SCALAR = 10.0
_REL_FIT_TOL = 5e-7  # mirrors ops.solver.REL_FIT_TOL (see its rationale)


def _pick_tile(n: int, full_cap: int = 2048) -> int:
    """Mosaic requires block dims divisible by (8, 128) or spanning the
    whole axis; small axes take the whole-axis block."""
    for p in (512, 256, 128):
        if n % p == 0 and n >= p:
            return p
    return n if n <= full_cap else 0


def fused_choice_supported(T: int, N: int) -> bool:
    """Shapes the kernel tiles cleanly; anything else uses the dense path."""
    return _pick_tile(T) > 0 and _pick_tile(N) > 0


def fused_choice_auto(T: int, N: int) -> bool:
    """The solver's auto gate: take the kernel only at the scale where it
    pays AND where the tiles are the well-trodden 128-multiples — small
    odd shapes exercise Mosaic relayout corners (observed: i1 relayout
    failures on 40-row tiles) for no measurable win."""
    return (T >= 1024 and N >= 256 and T % 128 == 0 and N % 128 == 0
            and fused_choice_supported(T, N))


def _kernel(reqT_ref, elig_ref, sig_ref, availT_ref, usedT_ref, invT_ref,
            nstat_ref, podsok_ref, pars_ref,
            best_s_ref, best_i_ref, node_max_ref,
            *, R: int, bn: int, families: Tuple[str, ...]):
    i = pl.program_id(0)
    j = pl.program_id(1)

    sig = sig_ref[:] != 0                                     # [bt,bn]
    # reshape the 32-bit values BEFORE comparing: Mosaic can't insert a
    # minor dim on 1-bit vectors
    elig = elig_ref[0, :][:, None] != 0.0                     # [bt,1]
    podsok = podsok_ref[0, :][None, :] != 0.0                 # [1,bn]

    feas = sig & elig & podsok
    for r in range(R):
        req_r = reqT_ref[r, :][:, None]                       # [bt,1]
        av_r = availT_ref[r, :][None, :]                      # [1,bn]
        thr = _THR_CPU if r == 0 else (_THR_MEM if r == 1 else _THR_SCALAR)
        # same expression order as ops.solver.le_fits (incl. the float32
        # scale-aware REL_FIT_TOL term) so the fused path stays bitwise
        # identical to the dense one
        ok = (req_r < av_r + (thr + _REL_FIT_TOL * jnp.abs(av_r))) \
            | (req_r <= av_r)
        if r >= 2:
            ok = ok | (req_r <= 10.0)
        feas = feas & ok

    bt = sig.shape[0]
    score = jnp.zeros((bt, bn), jnp.float32)
    # pars layout: [0]=binpack_weight, [1]=least, [2]=most, [3]=balanced,
    # [4]=100/sum(w), [5:5+R]=binpack_res_weights.
    # The float operation ORDER below mirrors ops.solver.score_matrix
    # term for term (task/node sums accumulated separately, kube terms
    # summed before joining score) so the result is bitwise identical —
    # a different grouping flips argmax tie-breaks.
    if "binpack" in families:
        bp_task = jnp.zeros((bt, bn), jnp.float32)
        bp_node = jnp.zeros((1, bn), jnp.float32)
        for r in range(R):
            inv_r = invT_ref[r, :][None, :]
            w_r = pars_ref[0, 5 + r]
            # task term multiplies req by (w*inv), node term multiplies
            # (used*w) by inv — the dense path's exact groupings
            bp_task = bp_task + reqT_ref[r, :][:, None] * (w_r * inv_r)
            bp_node = bp_node + (usedT_ref[r, :][None, :] * w_r) * inv_r
        score = score + (pars_ref[0, 0]
                         * (bp_task + bp_node) * pars_ref[0, 4])
    if "kube" in families:
        f0 = ((usedT_ref[0, :][None, :] + reqT_ref[0, :][:, None])
              * invT_ref[0, :][None, :])
        f1 = ((usedT_ref[1, :][None, :] + reqT_ref[1, :][:, None])
              * invT_ref[1, :][None, :])
        least = ((jnp.clip(1.0 - f0, 0.0, 1.0)
                  + jnp.clip(1.0 - f1, 0.0, 1.0)) / 2.0) * 100.0
        most = ((jnp.clip(f0, 0.0, 1.0)
                 + jnp.clip(f1, 0.0, 1.0)) / 2.0) * 100.0
        balanced = (1.0 - jnp.abs(f0 - f1)) * 100.0
        score = score + (pars_ref[0, 1] * least + pars_ref[0, 2] * most
                         + pars_ref[0, 3] * balanced)
    score = score + nstat_ref[0, :][None, :]

    masked = jnp.where(feas, score, NEG)

    loc_best = jnp.max(masked, axis=1)                        # [bt]
    # explicit first-index tie rule: Mosaic's argmax lowering does not
    # guarantee the lowest index on ties (XLA's does), so take min over
    # the max-achieving columns
    col = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    cand = jnp.where(masked == loc_best[:, None], col,
                     jnp.int32(2 ** 30))
    loc_idx = jnp.min(cand, axis=1) + j * bn

    @pl.when(j == 0)
    def _():
        best_s_ref[0, :] = loc_best
        best_i_ref[0, :] = loc_idx

    @pl.when(j > 0)
    def _():
        prev = best_s_ref[0, :]
        better = loc_best > prev                  # strict: first max wins
        best_s_ref[0, :] = jnp.where(better, loc_best, prev)
        best_i_ref[0, :] = jnp.where(better, loc_idx, best_i_ref[0, :])

    colmax = jnp.max(masked, axis=0)                          # [bn]

    @pl.when(i == 0)
    def _():
        node_max_ref[0, :] = colmax

    @pl.when(i > 0)
    def _():
        node_max_ref[0, :] = jnp.maximum(node_max_ref[0, :], colmax)


@functools.partial(jax.jit, static_argnames=("families",))
def fused_choice(init_req, avail, used_now, inv_alloc, node_static,
                 eligible, pods_ok, sig_feas_i8, pars,
                 families: Tuple[str, ...]):
    """Fused (feasibility & score & argmax & node-max) over [T,N].

    init_req [T,R] f32; avail/used_now/inv_alloc [N,R] f32; node_static
    [N] f32; eligible [T] f32 (0/1); pods_ok [N] f32 (0/1); sig_feas_i8
    [T,N] int8 (round-invariant predicate mask); pars [5+R] f32 (see
    kernel). Returns (best_score [T], best_idx [T], node_max [N]).
    """
    T, R = init_req.shape
    N = avail.shape[0]
    bt = _pick_tile(T)
    bn = _pick_tile(N)
    if not bt or not bn:
        raise ValueError(f"unsupported fused-choice shape T={T} N={N}")

    reqT = init_req.T                     # [R,T]
    availT = avail.T                      # [R,N]
    usedT = used_now.T
    invT = inv_alloc.T
    grid = (T // bt, N // bn)

    kernel = functools.partial(_kernel, R=R, bn=bn, families=families)
    vm = pltpu.VMEM
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, bt), lambda i, j: (0, i), memory_space=vm),
            pl.BlockSpec((1, bt), lambda i, j: (0, i), memory_space=vm),
            pl.BlockSpec((bt, bn), lambda i, j: (i, j), memory_space=vm),
            pl.BlockSpec((R, bn), lambda i, j: (0, j), memory_space=vm),
            pl.BlockSpec((R, bn), lambda i, j: (0, j), memory_space=vm),
            pl.BlockSpec((R, bn), lambda i, j: (0, j), memory_space=vm),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=vm),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=vm),
            pl.BlockSpec((1, 5 + R), lambda i, j: (0, 0), memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda i, j: (0, i), memory_space=vm),
            pl.BlockSpec((1, bt), lambda i, j: (0, i), memory_space=vm),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, T), jnp.float32),
            jax.ShapeDtypeStruct((1, T), jnp.int32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=use_interpret(),
    )(reqT, eligible[None, :], sig_feas_i8, availT, usedT, invT,
      node_static[None, :], pods_ok[None, :], pars[None, :])
    best_s, best_i, node_max = out
    return best_s[0], best_i[0], node_max[0]


def fused_setup(a, score_params, R: int):
    """The fused path's per-solve prelude, shared by the single-device and
    sharded solvers so their parity-critical inputs cannot diverge:
    (sig_i8, inv_alloc, fused_pars, node_static). `a` needs sig_feas
    pre-composed ([T,N] bool) and node_alloc."""
    import jax.numpy as jnp

    sig_i8 = a["sig_feas"].astype(jnp.int8)
    inv_alloc = 1.0 / a["node_alloc"]
    fused_pars = pack_pars(score_params, R)
    node_static = jnp.asarray(score_params["node_static"], jnp.float32)
    return sig_i8, inv_alloc, fused_pars, node_static


def pack_pars(params, R: int):
    """Build the kernel's flat parameter vector from the solver's score
    params dict (device-friendly: one tiny array instead of many
    scalars)."""
    w = jnp.asarray(params["binpack_res_weights"], jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-9)
    head = jnp.stack([
        jnp.asarray(params["binpack_weight"], jnp.float32),
        jnp.asarray(params["least_req_weight"], jnp.float32),
        jnp.asarray(params["most_req_weight"], jnp.float32),
        jnp.asarray(params["balanced_weight"], jnp.float32),
        100.0 / wsum,
    ])
    return jnp.concatenate([head, w[:R]])
