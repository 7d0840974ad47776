"""Device-resident packed solver arena with chunked delta upload.

Every host->device transfer costs a dispatch: re-shipping the full packed
snapshot (~0.5 MB at 10k tasks / 2k nodes) every session moves bytes that
mostly did not change, while the cluster typically changes a few rows per
cycle.
This cache keeps the two packed buffers (ops.arrays.SnapshotArrays.packed)
resident on device ACROSS scheduling sessions and ships only the chunks
whose bytes changed since the previous session, applied with a donated
in-place scatter — the TPU-native analog of the reference's informer
deltas (client-go list-watch keeps the scheduler's mirror warm instead of
re-listing the cluster, pkg/scheduler/cache/cache.go:319-402).

Arena contract (what survives what):

- **Chunked packed buffers** (``_dev_f``/``_dev_i``): device-resident
  across sessions; donated into the fused solve each dispatch. Lost on
  ``invalidate()``/``reset()`` — a donated dispatch that failed at
  readback has already consumed them.
- **Score params** (``params_device``): device-resident across sessions,
  NEVER donated — they survive a collect failure and are re-validated
  (not re-uploaded) on the next session via ``invalidate()``'s suspect
  flag. Only content changes or actual device-side deletion re-pin them.
- **Host mirror** (``_host_f``/``_host_i``): host memory; survives
  ``invalidate()`` untouched (it is rebuilt by the full re-ship anyway)
  and exists so per-session diffs are chunk-exact.

Accounting (``last_shipped_bytes``, ``arena_hit_rate`` …) feeds the
``volcano_arena_*`` metrics, ``Scheduler.last_cycle_timing`` and the
bench's bytes-shipped-per-session artifact fields.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)


def _pow2_bucket(n: int) -> int:
    """Strict powers of two, deliberately NOT ops.arrays.bucket (whose
    quarter-steps minimize padding): dirty-chunk counts vary every session,
    so the scatter kernel wants the fewest possible compiled variants."""
    b = 1
    while b < n:
        b <<= 1
    return b


_APPLY = None  # lazily created singleton so the jit caches across sessions
_APPLY_KEEP = None  # non-donating variant (sharded arena: buffers alias)


def _scatter(dev, idx, vals):
    global _APPLY
    if _APPLY is None:
        import jax
        _APPLY = jax.jit(lambda d, i, v: d.at[i].set(v), donate_argnums=(0,))
    return _APPLY(dev, idx, vals)


def _scatter_keep(dev, idx, vals):
    """Non-donating chunk scatter: the sharded arena's per-device shard
    buffers are aliased by the previously assembled global array (an
    in-flight pipelined solve may still read it), so donation would
    poison a live session's inputs. ``dev`` is a replicated [C, chunk]
    buffer or a per-device [1, C, chunk] slab; ``idx`` indexes its chunk
    axis and ``vals`` is [k, chunk] either way."""
    return _keep_fn()(dev, idx, vals)


def _keep_fn():
    global _APPLY_KEEP
    if _APPLY_KEEP is None:
        import jax
        _APPLY_KEEP = jax.jit(lambda d, i, v: d.at[..., i, :].set(v))
    return _APPLY_KEEP


class PackedDeviceCache:
    """update(fbuf, ibuf, layout) -> (f2d, i2d) device arrays [C, chunk].

    First call (or any layout/shape change) ships everything; later calls
    diff against the previously shipped host copy chunk-wise and scatter
    only dirty chunks. Chunk-index uploads are bucketed to powers of two so
    the scatter kernel compiles a handful of times, not per session.
    """

    def __init__(self, chunk: int = 512):
        self.chunk = chunk
        self._host_f: Optional[np.ndarray] = None  # padded copy, [Cf*chunk]
        self._host_i: Optional[np.ndarray] = None
        self._dev_f = None                         # [Cf, chunk] on device
        self._dev_i = None
        self._layout = None
        self._params_blob = None
        self._params_dev = None
        #: device buffers untrusted (collect failure after a donated
        #: dispatch): next session full-ships and re-validates params
        self._params_suspect = False
        # previous mirror buffers recycled as diff scratch (the diff
        # allocated two full-buffer copies per session before)
        self._scratch_f: Optional[np.ndarray] = None
        self._scratch_i: Optional[np.ndarray] = None
        # -- arena accounting (diagnostics + volcano_arena_* metrics) ----
        self.last_shipped_chunks = 0
        self.last_shipped_bytes = 0     # wire bytes of the last delta/ship
        self.last_full_ship = False
        self.sessions = 0               # update/plan_delta calls
        self.full_ships = 0             # sessions that re-shipped everything
        self.delta_sessions = 0         # sessions that shipped a delta
        self.invalidations = 0          # soft resets (collect failures)
        self.params_repins = 0          # device params re-uploaded
        self.total_shipped_bytes = 0
        # the last plan's pinned params and flags (with its layout)
        self.last_params: Optional[dict] = None
        self.last_solve_flags: Optional[dict] = None
        #: the largest padded task and job axes planned so far: the
        #: flatten pads up to them (flatten_snapshot's ``floor``), so the
        #: executables an arena dispatches only ever grow — a snapshot
        #: that shrinks reuses a compiled solve, and a churning cluster
        #: settles on a finite set of variants
        self.dims_floor: Dict[str, int] = {}
        self._floor_lock = threading.Lock()
        #: grow_ahead's thread, once started
        self.grow_thread: Optional[threading.Thread] = None

    # -- arena introspection -------------------------------------------

    @property
    def arena_hit_rate(self) -> float:
        """Fraction of sessions served by a delta against the resident
        arena (1.0 = never re-shipped after the first session)."""
        if not self.sessions:
            return 0.0
        return self.delta_sessions / self.sessions

    def full_upload_bytes(self) -> int:
        """Wire cost of one full padded-buffer upload at the current
        layout (the denominator of the <10%-of-full acceptance check)."""
        if self._host_f is None or self._host_i is None:
            return 0
        return int(self._host_f.nbytes + self._host_i.nbytes)

    def reset(self) -> None:
        """Hard reset: drop the mirror, the device-resident state AND the
        pinned params so the next session rebuilds everything. Used when
        the HOST-side mirror itself may have desynced from the device (a
        partial scatter failure mid-apply) — after that, nothing this
        object remembers can be trusted."""
        self._host_f = self._host_i = None
        self._dev_f = self._dev_i = None
        self._layout = None
        self._params_blob = None
        self._params_dev = None
        self._params_suspect = False

    def invalidate(self) -> None:
        """Soft reset after an async-collect failure: by the time the
        error surfaced, a donated dispatch had already consumed the
        chunked buffers, so they are gone — but the score params were
        NEVER donated and usually survive, and the host mirror is host
        memory. Drop exactly what the donation poisoned: the next session
        full-ships the chunked buffers (one expensive upload, not a
        permanent cold path) and re-validates the pinned params in place
        instead of re-uploading them."""
        self._dev_f = self._dev_i = None
        self._layout = None  # forces the full re-ship
        self._params_suspect = True
        self.invalidations += 1

    # -- shared mirror maintenance (update + plan_delta flows) ----------

    def _full_ship(self, fbuf, ibuf, layout, cf: int, ci: int):
        """(Re)establish the host mirror and device buffers wholesale."""
        c = self.chunk
        hf = np.zeros(cf * c, np.float32)
        hf[:fbuf.size] = fbuf
        hi = np.zeros(ci * c, np.int32)
        hi[:ibuf.size] = ibuf
        self._host_f, self._host_i = hf, hi
        self._layout = layout
        self._ship_mirror()

    def _ship_mirror(self) -> None:
        """device_put the host mirror whole as the resident buffers."""
        import jax

        c = self.chunk
        hf, hi = self._host_f, self._host_i
        self._dev_f = jax.device_put(hf.reshape(-1, c))
        self._dev_i = jax.device_put(hi.reshape(-1, c))
        self._account((hf.size + hi.size) // c, hf.nbytes + hi.nbytes,
                      full=True)

    def _account(self, chunks: int, wire_bytes: int, full: bool) -> None:
        self.sessions += 1
        self.last_shipped_chunks = int(chunks)
        self.last_shipped_bytes = int(wire_bytes)
        self.last_full_ship = bool(full)
        self.total_shipped_bytes += int(wire_bytes)
        if full:
            self.full_ships += 1
        else:
            self.delta_sessions += 1

    def _needs_full_ship(self, layout, cf: int, ci: int) -> bool:
        c = self.chunk
        return (self._layout != layout or self._host_f is None
                or self._host_f.size != cf * c
                or self._host_i.size != ci * c)

    def _diff(self, fbuf, ibuf, cf: int, ci: int):
        """Pad new content into mirror-shaped buffers and locate dirty
        chunks: (f2, i2, df, di). Does NOT update the mirror (see
        _commit_mirror). The padded buffers come from the scratch pool —
        the previous session's mirror, recycled — so a steady session
        allocates no full-size arrays."""
        c = self.chunk
        f2, i2 = self._scratch_f, self._scratch_i
        if f2 is None or f2.size != cf * c:
            f2 = np.zeros(cf * c, np.float32)
        else:
            f2[fbuf.size:] = 0.0
        if i2 is None or i2.size != ci * c:
            i2 = np.zeros(ci * c, np.int32)
        else:
            i2[ibuf.size:] = 0
        self._scratch_f = self._scratch_i = None
        f2[:fbuf.size] = fbuf
        i2[:ibuf.size] = ibuf
        df = np.nonzero((f2.reshape(cf, c)
                         != self._host_f.reshape(cf, c)).any(axis=1))[0]
        di = np.nonzero((i2.reshape(ci, c)
                         != self._host_i.reshape(ci, c)).any(axis=1))[0]
        return f2, i2, df, di

    def _commit_mirror(self, f2, i2) -> None:
        """Adopt the diffed buffers as the new mirror; the old mirror
        becomes next session's diff scratch."""
        self._scratch_f, self._scratch_i = self._host_f, self._host_i
        self._host_f, self._host_i = f2, i2

    def update(self, fbuf: np.ndarray, ibuf: np.ndarray,
               layout) -> Tuple[object, object]:
        c = self.chunk
        cf = -(-max(fbuf.size, 1) // c)
        ci = -(-max(ibuf.size, 1) // c)
        if self._needs_full_ship(layout, cf, ci):
            self._full_ship(fbuf, ibuf, layout, cf, ci)
            return self._dev_f, self._dev_i

        f2, i2, df, di = self._diff(fbuf, ibuf, cf, ci)
        try:
            new_f = self._apply(self._dev_f, df, f2.reshape(cf, c))
            new_i = self._apply(self._dev_i, di, i2.reshape(ci, c))
        except Exception:
            # a partial scatter (or a donated-buffer loss) would desync the
            # device copy from the host mirror: drop everything so the next
            # session re-ships in full instead of solving on stale data
            self.reset()
            raise
        self._dev_f, self._dev_i = new_f, new_i
        self._commit_mirror(f2, i2)
        self._account(df.size + di.size,
                      self._scatter_wire_bytes(df, di), full=False)
        return self._dev_f, self._dev_i

    def _scatter_wire_bytes(self, df, di) -> int:
        """Wire bytes of the separate-scatter path: each dirty set is
        padded to a power of two (padded chunks repeat real content but
        still cross the wire)."""
        c = self.chunk
        nf = _pow2_bucket(df.size) if df.size else 0
        ni = _pow2_bucket(di.size) if di.size else 0
        return (nf + ni) * c * 4 + (nf + ni) * 4

    @staticmethod
    def _apply(dev, idx, host2d):
        if idx.size == 0:
            return dev
        k = _pow2_bucket(idx.size)
        # pad with repeats of the first dirty chunk: duplicate scatter
        # indices write the same value, so the pad is a no-op
        pad = np.full(k, idx[0], np.int32)
        pad[:idx.size] = idx.astype(np.int32)
        return _scatter(dev, pad, host2d[pad])

    # ------------------------------------------------------------------
    # fused-dispatch flow: plan the delta, let the SOLVE jit apply it
    # (ops.solver.solve_allocate_delta), then commit the returned buffers
    # ------------------------------------------------------------------

    #: fixed delta-slot count for the fused dispatch: the chunk-index
    #: shape is part of the fused solve's jit signature, so EVERY distinct
    #: size would compile another full-solve executable (~tens of seconds
    #: each on TPU). One fixed size = exactly one fused variant; a session
    #: dirtying more chunks re-ships the buffers whole instead.
    FUSED_SLOTS = 16

    def plan_delta(self, fbuf: np.ndarray, ibuf: np.ndarray, layout):
        """Diff against the host mirror WITHOUT dispatching the solve.

        Returns the arguments of solve_allocate_delta, (f2d, i2d, f_idx,
        f_vals, i_idx, i_vals): every session of a layout dispatches that
        one executable, which scatters the dirty chunks inside the solve.
        At most FUSED_SLOTS dirty chunks ride in its fixed slots. An
        unchanged snapshot, the first call, a layout change, or more dirty
        chunks than FUSED_SLOTS send a no-op delta (chunk 0 rewritten with
        its own bytes); the last three device_put both buffers whole first,
        which compiles nothing (a scatter padded to the dirty count
        compiled once per count, and a second solve entry once per
        layout). The caller must commit() the returned (donated) buffers,
        and on a dispatch failure call invalidate() so the next session
        re-ships the chunked buffers in full (reset() only if the host
        mirror itself is suspect).
        """
        c = self.chunk
        cf = -(-max(fbuf.size, 1) // c)
        ci = -(-max(ibuf.size, 1) // c)
        k = self.FUSED_SLOTS
        if self._needs_full_ship(layout, cf, ci):
            self._full_ship(fbuf, ibuf, layout, cf, ci)
        else:
            f2, i2, df, di = self._diff(fbuf, ibuf, cf, ci)
            self._commit_mirror(f2, i2)
            if int(df.size) > k or int(di.size) > k:
                self._ship_mirror()
            else:
                f_idx = self._pad_idx(df, k)
                i_idx = self._pad_idx(di, k)
                fv = f2.reshape(cf, c)[f_idx]
                iv = i2.reshape(ci, c)[i_idx]
                # fused wire cost: both value blocks always ship k chunks
                # (the fixed jit signature), plus the two index vectors
                self._account(df.size + di.size,
                              fv.nbytes + iv.nbytes + f_idx.nbytes
                              + i_idx.nbytes, full=False)
                return self._dev_f, self._dev_i, f_idx, fv, i_idx, iv
        zero = np.zeros(k, np.int32)
        return (self._dev_f, self._dev_i,
                zero, np.broadcast_to(
                    self._host_f.reshape(cf, c)[0], (k, c)).copy(),
                zero, np.broadcast_to(
                    self._host_i.reshape(ci, c)[0], (k, c)).copy())

    @staticmethod
    def _pad_idx(idx: np.ndarray, k: int) -> np.ndarray:
        """Chunk indices padded to k (duplicates write identical values so
        the pad is a no-op scatter)."""
        pad = np.full(k, idx[0] if idx.size else 0, np.int32)
        pad[:idx.size] = idx.astype(np.int32)
        return pad

    def commit(self, f2d, i2d) -> None:
        """Store the buffers returned by solve_allocate_delta (the inputs
        were donated and are now invalid)."""
        self._dev_f, self._dev_i = f2d, i2d

    # -- the allocate solve over this arena: plan, then dispatch ---------

    #: the turn record's ``arena_mode`` for a session this arena served
    MODE = "packed"

    def plan(self, fbuf: np.ndarray, ibuf: np.ndarray, layout,
             params: dict, flags: dict):
        """Stage one session's solve: pin the score params and diff the
        snapshot against the resident buffers. ``flags`` are the solve
        entries' static flags; with ``layout`` they become
        ``last_solve_flags``, which the bucket prewarmer reads. Returns
        what ``dispatch`` takes."""
        params = self.last_params = self.params_device(params)
        self.last_solve_flags = dict(layout=layout, **flags)
        self._raise_floor(layout)
        return self.plan_delta(fbuf, ibuf, layout), layout, params, flags

    def _raise_floor(self, layout) -> None:
        shapes = {k: shape for k, _kind, _off, _size, shape in layout}
        with self._floor_lock:
            for dim, key in (("T", "task_valid"), ("J", "job_valid")):
                n = int(shapes[key][0])
                if n > self.dims_floor.get(dim, 0):
                    self.dims_floor[dim] = n

    def grow_ahead(self) -> bool:
        """Called when a solve of this arena left tasks waiting. A backlog
        that outlasts a solve churns, and its mix drifts toward the gangs
        that wait longest, the largest: its task count climbs past the
        first layout's within tens of turns. Once per arena, this compiles
        the task axis's growth past the floor (flatten_snapshot's
        ``floor``: a power of two at least twice it) on a thread of its
        own, and raises the floor to it once compiled. The one growth a
        backlog needs then lands in its first turns, off the scheduler's
        thread, rather than at whichever later turn the backlog outgrows
        its first size. Returns True when it started."""
        from .arrays import _floored
        from .precompile import (
            SOLVE_FLAG_NAMES, layout_dims, predict_next_layout,
            warm_delta_solve, watcher,
        )

        if self.grow_thread is not None or self._layout is None:
            return False
        dims = layout_dims(self._layout)
        if dims is None:
            return False  # hdrf layout: nothing to predict
        t = self.dims_floor.get("T", dims["T"])
        dims = dict(dims, T=_floored(t + 1, t + 1, t))
        layout = predict_next_layout(self._layout, dims)
        flags = {k: v for k, v in (self.last_solve_flags or {}).items()
                 if k in SOLVE_FLAG_NAMES}

        def grow():
            watcher.install()
            watcher.register_background()
            try:
                warm_delta_solve(layout, dims, self.chunk, flags)
            except Exception:  # noqa: BLE001 — the growth then compiles
                log.warning("arena growth warm failed for %s", dims,
                            exc_info=True)
                return
            with self._floor_lock:
                if dims["T"] > self.dims_floor.get("T", 0):
                    self.dims_floor["T"] = dims["T"]

        # not a daemon: an interpreter that exits mid-compile aborts
        self.grow_thread = threading.Thread(target=grow, name="arena-grow")
        self.grow_thread.start()
        return True

    def dispatch(self, staged):
        """Dispatch the staged solve asynchronously: the fused delta
        scatter + solve, one dispatch donating the resident buffers. A
        throwing donated dispatch may have consumed the buffers, so it
        invalidates the arena (the host mirror and the pinned params stay
        for the next session's re-ship)."""
        from .solver import solve_allocate_delta

        payload, layout, params, flags = staged
        try:
            res, new_f, new_i = solve_allocate_delta(
                *payload, layout, params, **flags)
        except Exception:
            self.invalidate()
            raise
        self.commit(new_f, new_i)
        return res

    def record(self, timing: dict) -> None:
        """The last plan's shipping, as the turn record's arena keys."""
        timing["delta_chunks"] = float(self.last_shipped_chunks)
        timing["arena_mode"] = self.MODE
        timing["arena_bytes_shipped"] = float(self.last_shipped_bytes)
        timing["arena_full_ship"] = float(self.last_full_ship)

    # ------------------------------------------------------------------
    # device-resident score params: the per-session params dict is a few
    # small arrays ([N] node_static dominates, ~8 KB at 2k nodes) that
    # almost never change between cycles — re-uploading them every
    # dispatch puts a transfer on the critical path. Cache the
    # device copies and re-put only when the content bytes change, when a
    # suspect flag (collect failure) finds a device copy actually dead,
    # or after a hard reset.
    # ------------------------------------------------------------------

    @staticmethod
    def _params_alive(dev_params: Optional[dict]) -> bool:
        """Whether every pinned device array still holds live buffers.
        Donation never touches these, so after a collect failure they are
        normally intact; an actual device restart deletes them."""
        if not dev_params:
            return False
        try:
            for v in dev_params.values():
                is_deleted = getattr(v, "is_deleted", None)
                if is_deleted is not None and is_deleted():
                    return False
        except Exception:  # noqa: BLE001 — treat any doubt as dead
            return False
        return True

    def _put_params(self, params: dict) -> dict:
        """Device placement for the pinned score params; the sharded
        arena subclass overrides this to shard node_static along the
        mesh and replicate the scalars."""
        import jax

        return {k: jax.device_put(np.asarray(v)) for k, v in params.items()}

    def params_device(self, params: dict) -> dict:
        def _ent(k, v):
            # delimited key + dtype + shape + content: without these two
            # distinct params dicts whose concatenated bytes happen to
            # line up (or whose arrays share bytes but not shape/dtype)
            # could collide and serve stale device params
            a = np.asarray(v)
            return b"\0".join((k.encode(), str(a.dtype).encode(),
                               repr(a.shape).encode(), a.tobytes())) + b"\1"

        blob = b"".join(_ent(k, v) for k, v in sorted(params.items()))
        if blob == self._params_blob:
            if not self._params_suspect:
                return self._params_dev
            # re-validate the pinned copies after a collect failure:
            # content unchanged AND buffers alive -> keep them resident
            if self._params_alive(self._params_dev):
                self._params_suspect = False
                return self._params_dev
        self._params_dev = self._put_params(params)
        self._params_blob = blob
        self._params_suspect = False
        self.params_repins += 1
        return self._params_dev


# ---------------------------------------------------------------------------
# node-axis-sharded arena: the D>1 steady-state analog of the cache above
# ---------------------------------------------------------------------------

#: packed keys whose LEADING axis is the node axis — sharded along the
#: mesh 'n' axis by the sharded arena (parallel.sharded_solver in_specs
#: use P("n", ...) for exactly these)
NODE_AXIS_KEYS = frozenset({
    "node_idle", "node_extra_future", "node_used", "node_alloc",
    "node_npods", "node_max_pods", "node_valid",
})

#: node axis SECOND: [S, N] predicate-signature masks are stored per
#: shard as [S, N/D] and transposed back on device (P(None, "n"))
NODE_COL_KEYS = frozenset({"sig_masks"})


def split_packed_layout(layout, n_shards: int):
    """Split a ``SnapshotArrays.packed()`` layout into the replicated part
    (task/job/queue/misc arrays, placed once per device) and the per-shard
    node part (node-axis arrays, one slice of N/n_shards rows per mesh
    device). Offsets are re-accumulated per part, so each part is its own
    dense flat buffer; per-shard shapes replace the node axis with
    N/n_shards. Returns ``(rep_layout, node_layout)`` — both in the same
    sorted-key order as the input, so byte layouts are deterministic.

    Pure layout arithmetic (no arrays touched): the bucket prewarmer uses
    it to predict the sharded arena's next-bucket jit signatures exactly
    like predict_next_layout does for the packed path.
    """
    rep, node = [], []
    rf = ri = nf = ni = 0
    for key, kind, _off, _size, shape in layout:
        if key in NODE_AXIS_KEYS:
            n = shape[0]
            if n % n_shards:
                raise ValueError(
                    f"node axis {n} does not divide {n_shards} shards")
            pshape = (n // n_shards,) + tuple(shape[1:])
        elif key in NODE_COL_KEYS:
            n = shape[1]
            if n % n_shards:
                raise ValueError(
                    f"node axis {n} does not divide {n_shards} shards")
            pshape = (shape[0], n // n_shards)
        else:
            size = 1
            for s in shape:
                size *= s
            if kind == "f":
                rep.append((key, kind, rf, size, shape))
                rf += size
            else:
                rep.append((key, kind, ri, size, shape))
                ri += size
            continue
        size = 1
        for s in pshape:
            size *= s
        if kind == "f":
            node.append((key, kind, nf, size, pshape))
            nf += size
        else:
            node.append((key, kind, ni, size, pshape))
            ni += size
    return tuple(rep), tuple(node)


def _part_sizes(part_layout) -> Tuple[int, int]:
    """(flat f32 length, flat i32 length) of one split-layout part."""
    nf = max((off + size for _k, kind, off, size, _s in part_layout
              if kind == "f"), default=0)
    ni = max((off + size for _k, kind, off, size, _s in part_layout
              if kind != "f"), default=0)
    return nf, ni


class ShardedDeviceCache(PackedDeviceCache):
    """The device-resident arena for D>1 sharded solves.

    Same contract as PackedDeviceCache — host mirror diffs, dirty-chunk
    deltas, pinned score params, soft ``invalidate()`` — but the resident
    state is laid out for the node-axis ``shard_map`` solver
    (``parallel.solve_allocate_sharded_arena``):

    - **node-axis arrays** live as one chunked buffer pair PER MESH
      DEVICE (committed single-device arrays assembled zero-copy into a
      global ``[D, C, chunk]`` array with ``NamedSharding(mesh, P("n"))``
      at dispatch time). A dirty node row ships only to the shard that
      owns it — the per-device scatter executes on that device alone;
    - **task/job/queue arrays** live as one replicated chunked buffer
      pair (``NamedSharding(mesh, P())``), delta-updated in place: the
      host ships each dirty chunk once and the runtime fans it out;
    - **score params** are pinned with the solver's shardings
      (node_static split along 'n', scalars replicated), re-validated in
      place after a collect failure exactly like the packed arena;
    - **the delta scatter** is compiled for every chunk-count bucket of
      every resident buffer on a background thread after each full ship,
      so no delta session compiles one.

    ``update(fbuf, ibuf, layout)`` -> ``(f_rep, i_rep, f_node, i_node,
    rep_layout, node_layout)``: the six dispatch inputs of
    ``solve_allocate_sharded_arena``. Accounting adds ``last_shard_bytes``
    (wire bytes per shard for the last session) on top of the inherited
    ``volcano_arena_*`` counters; a zero-dirty session returns the
    resident arrays and ships 0 bytes to every shard.
    """

    def __init__(self, mesh, chunk: int = 512):
        super().__init__(chunk)
        self.mesh = mesh
        self.D = int(mesh.devices.size)
        self._rep_layout = None
        self._node_layout = None
        # host mirrors: rep flat [Crf*c]/[Cri*c]; node [D, Cnf*c]/[D, Cni*c]
        self._host_rep_f = self._host_rep_i = None
        self._host_node_f = self._host_node_i = None
        # device state: rep = global replicated arrays; node = per-device
        # committed [1, Cn, chunk] arrays (assembled on demand)
        self._dev_rep_f = self._dev_rep_i = None
        self._dev_node_f = self._dev_node_i = None
        #: wire bytes shipped to each shard by the last session (node
        #: slices + this shard's copy of the replicated delta)
        self.last_shard_bytes = [0] * self.D
        self._warm = None  # the scatter warm thread of the last full ship

    # -- placement helpers ---------------------------------------------

    def _sharding(self, along_n: bool):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("n") if along_n else P()), jax

    def _put_params(self, params: dict) -> dict:
        ns_n, jax = self._sharding(True)
        ns_rep, _ = self._sharding(False)
        return {k: jax.device_put(
                    np.asarray(v), ns_n if k == "node_static" else ns_rep)
                for k, v in params.items()}

    # -- lifecycle ------------------------------------------------------

    def reset(self) -> None:
        super().reset()
        self._rep_layout = self._node_layout = None
        self._host_rep_f = self._host_rep_i = None
        self._host_node_f = self._host_node_i = None
        self._dev_rep_f = self._dev_rep_i = None
        self._dev_node_f = self._dev_node_i = None

    def invalidate(self) -> None:
        """Soft reset after a failed sharded session: the sharded solve
        never donates, but a mesh-path failure leaves the device-side
        state untrusted (a shard's scatter may have landed while another
        shard's was lost) — drop the resident buffers, full-ship next
        session, and re-validate the pinned params in place."""
        super().invalidate()
        self._rep_layout = self._node_layout = None
        self._dev_rep_f = self._dev_rep_i = None
        self._dev_node_f = self._dev_node_i = None

    def full_upload_bytes(self) -> int:
        if self._host_rep_f is None or self._host_node_f is None:
            return 0
        return int(self._host_rep_f.nbytes + self._host_rep_i.nbytes
                   + self._host_node_f.nbytes + self._host_node_i.nbytes)

    # -- host-side packing ---------------------------------------------

    def _pack_split(self, fbuf, ibuf, layout, rep_layout, node_layout,
                    out_rep_f, out_rep_i, out_node_f, out_node_i) -> None:
        """Scatter the global packed buffers into the split mirrors:
        replicated keys copy through; node keys slice one row-block (or
        sig_masks column-block) per shard."""
        goff = {k: (off, size, shape) for k, off, size, shape in
                ((k, off, size, shape)
                 for k, _kind, off, size, shape in layout)}
        D = self.D
        for key, kind, off, size, shape in rep_layout:
            g_off, g_size, _ = goff[key]
            src = fbuf if kind == "f" else ibuf
            dst = out_rep_f if kind == "f" else out_rep_i
            dst[off:off + size] = src[g_off:g_off + g_size]
        for key, kind, off, size, pshape in node_layout:
            g_off, g_size, g_shape = goff[key]
            src = fbuf if kind == "f" else ibuf
            dst = out_node_f if kind == "f" else out_node_i
            g = src[g_off:g_off + g_size].reshape(g_shape)
            if key in NODE_COL_KEYS:
                nl = pshape[1]
                for d in range(D):
                    dst[d, off:off + size] = \
                        g[:, d * nl:(d + 1) * nl].ravel()
            else:
                nl = pshape[0]
                for d in range(D):
                    dst[d, off:off + size] = \
                        g[d * nl:(d + 1) * nl].ravel()

    # -- the session entry ---------------------------------------------

    def update(self, fbuf: np.ndarray, ibuf: np.ndarray, layout):
        import jax

        c, D = self.chunk, self.D
        self._join_warm()
        if self._layout != layout or self._rep_layout is None:
            rep_layout, node_layout = split_packed_layout(layout, D)
        else:
            rep_layout, node_layout = self._rep_layout, self._node_layout
        rf, ri = _part_sizes(rep_layout)
        nf, ni = _part_sizes(node_layout)
        crf = -(-max(rf, 1) // c)
        cri = -(-max(ri, 1) // c)
        cnf = -(-max(nf, 1) // c)
        cni = -(-max(ni, 1) // c)

        if (self._layout != layout or self._host_rep_f is None
                or self._host_rep_f.size != crf * c
                or self._host_node_f.shape != (D, cnf * c)):
            # full ship: (re)build mirrors and place every shard
            hrf = np.zeros(crf * c, np.float32)
            hri = np.zeros(cri * c, np.int32)
            hnf = np.zeros((D, cnf * c), np.float32)
            hni = np.zeros((D, cni * c), np.int32)
            self._pack_split(fbuf, ibuf, layout, rep_layout, node_layout,
                             hrf, hri, hnf, hni)
            self._host_rep_f, self._host_rep_i = hrf, hri
            self._host_node_f, self._host_node_i = hnf, hni
            ns_rep, _ = self._sharding(False)
            self._dev_rep_f = jax.device_put(hrf.reshape(crf, c), ns_rep)
            self._dev_rep_i = jax.device_put(hri.reshape(cri, c), ns_rep)
            devs = list(self.mesh.devices.flat)
            self._dev_node_f = [
                jax.device_put(hnf[d].reshape(1, cnf, c), devs[d])
                for d in range(D)]
            self._dev_node_i = [
                jax.device_put(hni[d].reshape(1, cni, c), devs[d])
                for d in range(D)]
            self._layout = layout
            self._rep_layout, self._node_layout = rep_layout, node_layout
            rep_bytes = hrf.nbytes + hri.nbytes
            self.last_shard_bytes = [
                int(hnf[d].nbytes + hni[d].nbytes + rep_bytes)
                for d in range(D)]
            self._account(crf + cri + D * (cnf + cni),
                          rep_bytes + hnf.nbytes + hni.nbytes, full=True)
            self._start_scatter_warm()
            return self._assembled(rep_layout, node_layout)

        # delta path: diff the split mirrors chunk-wise
        srf = np.zeros(crf * c, np.float32)
        sri = np.zeros(cri * c, np.int32)
        snf = np.zeros((D, cnf * c), np.float32)
        sni = np.zeros((D, cni * c), np.int32)
        self._pack_split(fbuf, ibuf, layout, rep_layout, node_layout,
                         srf, sri, snf, sni)
        drf = np.nonzero((srf.reshape(crf, c)
                          != self._host_rep_f.reshape(crf, c))
                         .any(axis=1))[0]
        dri = np.nonzero((sri.reshape(cri, c)
                          != self._host_rep_i.reshape(cri, c))
                         .any(axis=1))[0]
        chunks = drf.size + dri.size
        rep_bytes = self._scatter_wire_bytes(drf, dri)
        if drf.size:
            self._dev_rep_f = self._apply_keep(
                self._dev_rep_f, drf, srf.reshape(crf, c))
        if dri.size:
            self._dev_rep_i = self._apply_keep(
                self._dev_rep_i, dri, sri.reshape(cri, c))
        shard_bytes = [0] * D
        for d in range(D):
            dnf = np.nonzero((snf[d].reshape(cnf, c)
                              != self._host_node_f[d].reshape(cnf, c))
                             .any(axis=1))[0]
            dni = np.nonzero((sni[d].reshape(cni, c)
                              != self._host_node_i[d].reshape(cni, c))
                             .any(axis=1))[0]
            if dnf.size:
                self._dev_node_f[d] = self._apply_keep(
                    self._dev_node_f[d], dnf, snf[d].reshape(cnf, c))
            if dni.size:
                self._dev_node_i[d] = self._apply_keep(
                    self._dev_node_i[d], dni, sni[d].reshape(cni, c))
            chunks += dnf.size + dni.size
            shard_bytes[d] = self._scatter_wire_bytes(dnf, dni)
        if chunks:
            self._host_rep_f, self._host_rep_i = srf, sri
            self._host_node_f, self._host_node_i = snf, sni
        self.last_shard_bytes = [
            int(b + (rep_bytes if chunks else 0)) for b in shard_bytes]
        self._account(chunks, rep_bytes + sum(shard_bytes), full=False)
        return self._assembled(rep_layout, node_layout)

    # -- the allocate solve over this arena: plan, then dispatch ---------

    MODE = "sharded"

    def plan(self, fbuf: np.ndarray, ibuf: np.ndarray, layout,
             params: dict, flags: dict):
        bufs = self.update(fbuf, ibuf, layout)
        params = self.last_params = self.params_device(params)
        self.last_solve_flags = dict(layout=layout, **flags)
        self._raise_floor(layout)
        return bufs, params, flags

    def dispatch(self, staged):
        """Dispatch the node-axis ``shard_map`` solve over the resident
        shards, re-sent once on a transport-marked error
        (resilience.transient; a device runtime error is never one, so it
        reaches the breaker at once)."""
        from ..parallel import solve_allocate_sharded_arena
        from ..resilience.transient import retry_transient

        bufs, params, flags = staged
        return retry_transient(
            lambda: solve_allocate_sharded_arena(*bufs, params, self.mesh,
                                                 **flags),
            what="sharded solver dispatch")

    def record(self, timing: dict) -> None:
        from ..metrics.spans import count

        super().record(timing)
        count("mesh_devices", self.D)
        count("shard_bytes_max", max(self.last_shard_bytes))
        count("shard_bytes_total", sum(self.last_shard_bytes))

    def resident_node_arrays(self):
        """The resident node-axis state as the two global ``[D, C, chunk]``
        arrays the sharded solve reads (f32, i32): one slab per mesh
        device, visible through their ``addressable_shards``."""
        return self._assembled(self._rep_layout, self._node_layout)[2:4]

    @staticmethod
    def _apply_keep(dev, idx, host2d):
        """Non-donating dirty-chunk scatter (see _scatter_keep); executes
        on the committed device of ``dev``, so a clean shard receives
        nothing."""
        k = _pow2_bucket(idx.size)
        pad = np.full(k, idx[0], np.int32)
        pad[:idx.size] = idx.astype(np.int32)
        return _scatter_keep(dev, pad, host2d[pad])

    def _start_scatter_warm(self) -> None:
        """Compile the dirty-chunk scatter for every power-of-two chunk
        count of every resident buffer, on a background thread, right
        after a full ship: the scatter compiles once per (buffer shape,
        device, bucket), and a delta session that compiled its own would
        stall on it. The warm scatters write chunk 0's own bytes into a
        copy, so the resident buffers are untouched; the next ``update``
        joins the thread before it scatters."""
        import threading

        _keep_fn()  # one jit object for both threads: its cache is shared
        targets = [(self._dev_rep_f, self._host_rep_f),
                   (self._dev_rep_i, self._host_rep_i)]
        targets += [(dev, host) for devs, hosts in (
                        (self._dev_node_f, self._host_node_f),
                        (self._dev_node_i, self._host_node_i))
                    for dev, host in zip(devs, hosts)]
        # not a daemon: the interpreter's exit waits for it rather than
        # tearing the runtime down under a compile
        self._warm = threading.Thread(
            target=self._warm_scatters, args=(targets,),
            name="arena-scatter-warm", daemon=False)
        self._warm.start()

    def _warm_scatters(self, targets) -> None:
        from .precompile import watcher

        watcher.install()
        watcher.register_background()
        c = self.chunk
        try:
            for dev, host in targets:
                n = host.size // c
                k = 1
                while True:
                    _scatter_keep(dev, np.zeros(k, np.int32),
                                  np.broadcast_to(host[:c], (k, c)))
                    if k >= n:
                        break
                    k <<= 1
        except Exception:  # noqa: BLE001 — a delta compiles what is left
            log.warning("arena scatter warm failed", exc_info=True)

    def _join_warm(self) -> None:
        if self._warm is not None:
            self._warm.join()
            self._warm = None

    def _assembled(self, rep_layout, node_layout):
        """Zero-copy global views over the resident shards: the node
        slabs become one [D, C, chunk] array sharded along 'n'."""
        import jax

        c, D = self.chunk, self.D
        ns_n, _ = self._sharding(True)
        cnf = self._dev_node_f[0].shape[1]
        cni = self._dev_node_i[0].shape[1]
        f_node = jax.make_array_from_single_device_arrays(
            (D, cnf, c), ns_n, self._dev_node_f)
        i_node = jax.make_array_from_single_device_arrays(
            (D, cni, c), ns_n, self._dev_node_i)
        return (self._dev_rep_f, self._dev_rep_i, f_node, i_node,
                rep_layout, node_layout)
