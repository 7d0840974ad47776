"""Compile-and-dispatch pipeline layer: kill XLA compile stalls.

Three cooperating pieces keep every XLA compile off the scheduling
session thread:

- **Persistent compilation cache** (``configure_compilation_cache``):
  wires JAX's on-disk executable cache so a repeated bucket shape — or a
  process restart — deserializes a compiled executable (~100 ms) instead
  of re-paying the full XLA compile (~tens of seconds on TPU for the
  full-solve kernel).

- **CompileWatcher**: a ``jax.monitoring`` tap recording per-thread
  backend-compile counts/seconds, the seconds a first dispatch spends
  tracing, lowering and loading from the persistent cache, and
  persistent-cache hits, feeding ``volcano_tpu.metrics``. The scheduler
  surfaces the deltas in ``last_cycle_timing`` so "a compile happened on
  the session thread" is an observable regression, not a mystery 10 s
  spike.

- **BucketPrewarmer**: the flatten pads to compile buckets
  (``ops.arrays.bucket`` quarter-steps), so the set of future jit
  signatures is *predictable*: when live task/node/job occupancy crosses
  a threshold of the current bucket, the next bucket's packed layout is
  synthesized host-side (``predict_next_layout`` — byte-exact layout
  arithmetic, no flatten needed) and the solver variants for it are
  traced + compiled on a daemon thread. jit caches are per-function and
  process-global, so the session thread's first post-crossing dispatch
  hits the already-populated cache.

The allocate action's dispatch/collect split (actions/allocate.py) rides
on the same module: JAX dispatch is async, so between dispatching the
solve and blocking on the compact readback the host runs replay
preparation, the prewarm occupancy check, and a young-generation GC —
work that previously serialized after the device finished.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

#: the variable JAX itself reads its cache directory from; where it is
#: set, this module sets no directory in code
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the entry points (``python -m volcano_tpu.standalone``,
#: ``chip_smoke.py``, ``bench.py``) keep the cache when neither a flag
#: nor the variable names one: fixed and inside the checkout, because the
#: directory is part of the cache key's lookup path (a moving directory
#: never hits). Listed in .gitignore.
ENTRY_POINT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_configured_dir: Optional[str] = None


def configure_compilation_cache(cache_dir: Optional[str] = None,
                                default_dir: Optional[str] = None,
                                min_compile_secs: float = 0.0
                                ) -> Optional[str]:
    """Turn on JAX's persistent on-disk compilation cache.

    Precedence: an explicit ``cache_dir`` (``--compile-cache-dir``) wins;
    else, where $JAX_COMPILATION_CACHE_DIR is set, JAX already reads it
    and no directory is set here; else ``default_dir`` (the entry points
    pass ``ENTRY_POINT_CACHE_DIR``; the library passes nothing and the
    cache stays off). Returns the directory in effect (None = off).
    Idempotent — repeated calls with the same dir are no-ops; a
    different dir re-points the cache.
    """
    global _configured_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    env_dir = os.environ.get(CACHE_DIR_ENV) or None
    target = cache_dir or env_dir or default_dir
    if not target or _configured_dir == target:
        return _configured_dir
    if cache_dir or not env_dir:
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    # the default thresholds skip exactly the small recompiles a restart
    # re-pays; pinning them to 0/-1 caches every solver variant
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache backend latches on first use: a process that compiled
    # anything before this call (warmup, another scheduler) must drop the
    # initialized-with-no-dir instance or the new dir never receives
    # entries
    cc.reset_cache()
    _configured_dir = target
    return _configured_dir


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------

#: the ``jax.monitoring`` durations of a first dispatch besides the
#: backend compile, by phase: tracing to a jaxpr, lowering it to an MLIR
#: module, and reading the executable from the persistent cache (a cache
#: load is timed inside the backend compile's duration as well)
COMPILE_PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class CompileWatcher:
    """Per-thread XLA compile accounting via ``jax.monitoring``.

    ``install()`` registers two listeners (idempotent): backend-compile
    and ``COMPILE_PHASE_EVENTS`` durations keyed by
    ``threading.get_ident()``, and persistent-cache hit events. Threads
    registered through ``register_background`` (the prewarmer's workers)
    are labeled ``background`` in the exported metrics; everything else
    counts as ``session`` — exactly the split the <50 ms budget cares
    about.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._by_thread: Dict[int, list] = {}   # ident -> [count, seconds]
        self._phases: Dict[int, Dict[str, float]] = {}  # ident -> s/phase
        self._background: set = set()
        self.cache_hits = 0
        self._installed = False

    # -- listener plumbing ------------------------------------------------

    def install(self) -> bool:
        with self._lock:
            if self._installed:
                return True
            try:
                import jax.monitoring as jm

                jm.register_event_duration_secs_listener(self._on_duration)
                jm.register_event_listener(self._on_event)
                self._installed = True
            except Exception:  # noqa: BLE001 — monitoring API drifted
                log.exception("jax.monitoring unavailable; compile "
                              "accounting falls back to jit cache sizes")
                return False
        return True

    def _on_duration(self, key: str, secs: float, **kw) -> None:
        try:
            phase = COMPILE_PHASE_EVENTS.get(key)
            if phase is None and "backend_compile" not in key:
                return
            ident = threading.get_ident()
            with self._lock:
                if phase is None:
                    ent = self._by_thread.setdefault(ident, [0, 0.0])
                    ent[0] += 1
                    ent[1] += secs
                else:
                    ph = self._phases.setdefault(ident, {})
                    ph[phase] = ph.get(phase, 0.0) + secs
                label = ("background" if ident in self._background
                         else "session")
            from ..metrics import metrics

            if phase is not None:
                metrics.solver_compile_phase_seconds_total.inc(
                    secs, labels={"phase": phase, "thread": label})
                return
            metrics.solver_compile_total.inc(labels={"thread": label})
            metrics.solver_compile_seconds_total.inc(
                secs, labels={"thread": label})
        except Exception:  # noqa: BLE001 — never break jax's dispatch
            pass

    def _on_event(self, key: str, **kw) -> None:
        try:
            if not key.endswith("/cache_hits"):
                return
            with self._lock:
                self.cache_hits += 1
            from ..metrics import metrics

            metrics.compile_cache_hits_total.inc()
        except Exception:  # noqa: BLE001
            pass

    # -- accounting views -------------------------------------------------

    @property
    def installed(self) -> bool:
        return self._installed

    def register_background(self, ident: Optional[int] = None) -> None:
        with self._lock:
            self._background.add(
                threading.get_ident() if ident is None else ident)

    def counts(self, ident: Optional[int] = None) -> Tuple[int, float]:
        """(compiles, seconds) observed on one thread (default: caller's)."""
        ident = threading.get_ident() if ident is None else ident
        with self._lock:
            ent = self._by_thread.get(ident, (0, 0.0))
            return int(ent[0]), float(ent[1])

    def session_totals(self) -> Tuple[int, float]:
        """(compiles, seconds) summed over all non-background threads."""
        with self._lock:
            c, s = 0, 0.0
            for ident, (n, secs) in self._by_thread.items():
                if ident not in self._background:
                    c += n
                    s += secs
            return c, s

    def session_phase_totals(self) -> Dict[str, float]:
        """Seconds per ``COMPILE_PHASE_EVENTS`` phase, summed over all
        non-background threads."""
        out: Dict[str, float] = {}
        with self._lock:
            for ident, phases in self._phases.items():
                if ident in self._background:
                    continue
                for phase, secs in phases.items():
                    out[phase] = out.get(phase, 0.0) + secs
        return out


#: process-wide watcher; ``install()`` is called by the scheduler wiring,
#: the prewarmer, and the bench — whoever gets there first
watcher = CompileWatcher()


def solver_cache_size() -> int:
    """Total compiled-variant count across the solver jit entry points —
    the fallback compile detector when jax.monitoring is unavailable, and
    the exact "new full-solve variant" counter for the bench (monitoring
    counts every jit, including trivial ops)."""
    from . import solver as _s

    fns = [_s.solve_allocate, _s.solve_allocate_sequential,
           _s.solve_allocate_packed2d,
           _s.solve_allocate_delta]
    try:
        # the sharded entry counts too: sharded-mode sessions dispatch it
        # and its compiles are exactly as much a session-thread stall
        from ..parallel import sharded_solver as _ss
        fns.append(_ss.solve_allocate_sharded_packed2d)
        fns.append(_ss.solve_allocate_sharded_arena)
    except Exception:  # noqa: BLE001 — parallel stack unavailable
        pass
    n = 0
    for fn in fns:
        try:
            n += fn._cache_size()
        except Exception:  # noqa: BLE001 — private API drifted
            return -1
    return n


# ---------------------------------------------------------------------------
# packed-layout prediction
# ---------------------------------------------------------------------------

#: semantic dims of every key in SnapshotArrays._base_device_dict — the
#: packed layout for ANY bucket combination follows from these plus the
#: sorted-key offset accumulation in SnapshotArrays.packed(). hdrf keys
#: are deliberately absent: their tree dims (H, D) don't scale with the
#: buckets, so hdrf sessions skip prewarm (predict returns None).
_PACKED_DIMS: Dict[str, Tuple[str, ...]] = {
    "task_init_req": ("T", "R"), "task_req": ("T", "R"),
    "task_job": ("T",), "task_rank": ("T",), "task_sig": ("T",),
    "task_counts_ready": ("T",), "task_valid": ("T",),
    "job_min": ("J",), "job_ready_base": ("J",), "job_queue": ("J",),
    "job_valid": ("J",), "job_drf_allocated": ("J", "R"),
    "drf_total": ("R",), "job_drf_prerank": ("J",),
    "node_idle": ("N", "R"), "node_extra_future": ("N", "R"),
    "node_used": ("N", "R"), "node_alloc": ("N", "R"),
    "node_npods": ("N",), "node_max_pods": ("N",), "node_valid": ("N",),
    "sig_masks": ("S", "N"),
    "queue_weight": ("Q",), "queue_capability": ("Q", "R"),
    "queue_allocated": ("Q", "R"), "queue_request": ("Q", "R"),
    "thresholds": ("R",), "scalar_dim_mask": ("R",),
}


def layout_dims(layout) -> Optional[Dict[str, int]]:
    """Recover the padded {T,N,J,Q,S,R} from a packed layout, or None when
    the layout carries keys outside the predictable set (hdrf)."""
    dims: Dict[str, int] = {}
    for key, _kind, _off, _size, shape in layout:
        names = _PACKED_DIMS.get(key)
        if names is None:
            return None
        for name, size in zip(names, shape):
            if dims.setdefault(name, size) != size:
                return None  # inconsistent layout; refuse to predict
    return dims


def predict_next_layout(layout, dims: Dict[str, int]):
    """Rebuild a packed layout for new padded sizes ``dims`` (complete
    {T,N,J,Q,S,R} map): same keys in the same (sorted) order, shapes
    remapped per _PACKED_DIMS, offsets re-accumulated exactly like
    SnapshotArrays.packed(). Byte-exact against a real flatten at those
    sizes (asserted by tests/test_precompile.py). None when the layout
    has unpredictable keys."""
    out = []
    foff = ioff = 0
    for key, kind, _off, _size, _shape in layout:
        names = _PACKED_DIMS.get(key)
        if names is None or any(n not in dims for n in names):
            return None
        shape = tuple(int(dims[n]) for n in names)
        size = 1
        for s in shape:
            size *= s
        if kind == "f":
            out.append((key, kind, foff, size, shape))
            foff += size
        else:
            out.append((key, kind, ioff, size, shape))
            ioff += size
    return tuple(out)


def dummy_packed_buffers(layout, chunk: int):
    """Zeroed chunked device-cache-shaped buffers (f2d, i2d) for a layout:
    the shapes — not the contents — are what the jit signature keys on.
    All-zero content makes the dummy solve converge immediately (no valid
    task, no valid job), so a warm call costs trace+compile plus a
    trivial device execution."""
    nf = max(off + size for _k, kind, off, size, _s in layout
             if kind == "f")
    ni = max(off + size for _k, kind, off, size, _s in layout
             if kind != "f")
    cf = -(-max(nf, 1) // chunk)
    ci = -(-max(ni, 1) // chunk)
    return (np.zeros((cf, chunk), np.float32),
            np.zeros((ci, chunk), np.int32))


def dummy_sharded_buffers(layout, chunk: int, mesh):
    """Zeroed, correctly-sharded dispatch inputs for the sharded arena
    entry (parallel.solve_allocate_sharded_arena) at a layout: replicated
    chunked rep buffers + [D, C, chunk] node buffers split along the mesh
    'n' axis, exactly the shardings ShardedDeviceCache commits — the jit
    cache keys on (aval, sharding), so a mis-sharded warm would compile a
    variant the session never dispatches."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .device_cache import _part_sizes, split_packed_layout

    D = int(mesh.devices.size)
    rep_l, node_l = split_packed_layout(layout, D)
    rf, ri = _part_sizes(rep_l)
    nf, ni = _part_sizes(node_l)
    crf = -(-max(rf, 1) // chunk)
    cri = -(-max(ri, 1) // chunk)
    cnf = -(-max(nf, 1) // chunk)
    cni = -(-max(ni, 1) // chunk)
    ns_rep = NamedSharding(mesh, P())
    ns_n = NamedSharding(mesh, P("n"))
    return (jax.device_put(np.zeros((crf, chunk), np.float32), ns_rep),
            jax.device_put(np.zeros((cri, chunk), np.int32), ns_rep),
            jax.device_put(np.zeros((D, cnf, chunk), np.float32), ns_n),
            jax.device_put(np.zeros((D, cni, chunk), np.int32), ns_n),
            rep_l, node_l)


def dummy_score_params(dims: Dict[str, int]) -> Dict[str, np.ndarray]:
    """Score-params dict with the avals build_score_inputs produces for a
    problem of these padded sizes (values irrelevant; shapes/dtypes key
    the jit signature)."""
    return {
        "binpack_weight": np.float32(0.0),
        "binpack_res_weights": np.ones(dims["R"], np.float32),
        "least_req_weight": np.float32(0.0),
        "most_req_weight": np.float32(0.0),
        "balanced_weight": np.float32(0.0),
        "node_static": np.zeros(dims["N"], np.float32),
    }


def warm_delta_solve(layout, dims: Dict[str, int], chunk: int,
                     flags: dict) -> None:
    """Trace and compile ``solve_allocate_delta``, the one executable the
    packed arena dispatches per layout, for ``layout`` (padded sizes
    ``dims``) against dummy buffers, so that the first session at that
    layout finds it compiled. Blocks; run it on a background thread."""
    import jax

    from .device_cache import PackedDeviceCache
    from .solver import solve_allocate_delta

    # device_put everything exactly like the real dispatch path
    # (PackedDeviceCache._full_ship / params_device): a committed device
    # array and a host np.ndarray key DIFFERENT jit cache entries, so a
    # numpy-fed warm would compile a variant the session never dispatches
    params = {k: jax.device_put(v)
              for k, v in dummy_score_params(dims).items()}
    f2d, i2d = dummy_packed_buffers(layout, chunk)
    k = PackedDeviceCache.FUSED_SLOTS
    zero = np.zeros(k, np.int32)
    res, _nf, _ni = solve_allocate_delta(
        jax.device_put(f2d), jax.device_put(i2d), zero,
        np.zeros((k, chunk), np.float32), zero,
        np.zeros((k, chunk), np.int32), layout, params, **flags)
    res.compact.block_until_ready()


# ---------------------------------------------------------------------------
# background bucket pre-warm
# ---------------------------------------------------------------------------

#: static flag names shared by the packed solver entry points; the
#: sharded entry accepts a subset (parallel.sharded_solver.PACKED2D_FLAGS)
SOLVE_FLAG_NAMES = ("herd_mode", "score_families", "use_queue_cap",
                    "use_drf_order", "use_hdrf_order", "work_conserving")


class BucketPrewarmer:
    """Watch bucket occupancy; compile the next bucket's solver variants
    on a daemon thread before the cluster crosses into them.

    ``observe(arr, dc, flags)`` is called by the allocate action inside
    the dispatch/collect overlap window (zero critical-path cost: it only
    compares integers and maybe spawns a thread). When any of live
    T/N/J reaches ``threshold`` of its current bucket, the next bucket's
    layout is predicted and ``solve_allocate_delta``, the one executable
    the packed arena dispatches per layout (and, with a ``mesh``, the
    sharded entries), is traced+compiled against dummy buffers
    off-thread. Each
    (dims, flags) combination warms at most once per process; the
    persistent compilation cache makes the warm a disk-cache
    deserialization after the first process ever to cross that bucket.
    """

    def __init__(self, threshold: float = 0.8, mesh=None):
        self.threshold = threshold
        self.mesh = mesh
        self._lock = threading.Lock()
        self._started: Dict[tuple, str] = {}   # key -> status
        self._threads: list = []
        self.completions = 0
        self.failures = 0

    # -- occupancy watch --------------------------------------------------

    def observe(self, arr, dc, flags: Optional[dict] = None) -> bool:
        """Check occupancy against the current buckets; spawn a warm for
        the next-bucket variant when warranted. Returns True when a warm
        was scheduled."""
        from .arrays import _floored, bucket

        layout = getattr(dc, "_layout", None)
        if layout is None:
            return False
        if flags is None:
            flags = getattr(dc, "last_solve_flags", None)
            if flags is None:
                return False
        flags = {k: v for k, v in flags.items() if k in SOLVE_FLAG_NAMES}
        live_t = len(arr.tasks_list)
        live_n = len(arr.nodes_list)
        live_j = len(arr.jobs_list)
        dims = layout_dims(layout)
        if dims is None:
            return False  # hdrf / unknown layout: no prediction
        crossed = []
        # J pads to bucket(nJ + 1) in the flatten, so its occupancy
        # compares live+1 against the bucket
        for name, live, pad1 in (("T", live_t, 0), ("N", live_n, 0),
                                 ("J", live_j, 1)):
            cur = dims[name]
            if live + pad1 >= self.threshold * cur and bucket(cur + 1) != cur:
                crossed.append(name)
        if not crossed:
            return False
        # an occupancy trigger says WHICH dims are near their edge, not
        # which will actually cross first (pods grow without nodes all the
        # time): warm every non-empty subset of the crossed dims, largest
        # first, so whichever combination the cluster lands on is covered
        # (≤7 combos, each deduped per process and disk-cached thereafter)
        fkey = tuple(sorted((k, v) for k, v in flags.items()))
        # the flatten grows an axis past the arena's floor to a power of
        # two at least twice the floor (flatten_snapshot's ``floor``),
        # else to the next bucket
        floor = getattr(dc, "dims_floor", {})
        work = []
        subsets = sorted(
            (s for m in range(1, 1 << len(crossed))
             for s in [[d for i, d in enumerate(crossed) if m >> i & 1]]),
            key=len, reverse=True)
        for sub in subsets:
            nxt = dict(dims)
            for name in sub:
                nxt[name] = _floored(bucket(dims[name] + 1), dims[name] + 1,
                                     floor.get(name, 0))
            key = (tuple(sorted(nxt.items())), fkey)
            with self._lock:
                if key in self._started:
                    continue
                self._started[key] = "running"
            layout2 = predict_next_layout(layout, nxt)
            if layout2 is None:
                with self._lock:
                    self._started[key] = "unsupported"
                continue
            work.append((key, layout2, nxt))
        if not work:
            return False
        t = threading.Thread(
            target=self._warm_many, args=(work, dc.chunk, flags),
            name="bucket-prewarm", daemon=True)
        with self._lock:
            self._threads.append(t)
        t.start()
        return True

    def _warm_many(self, work, chunk: int, flags: dict) -> None:
        for key, layout2, dims2 in work:
            self._warm(key, layout2, dims2, chunk, flags)

    # -- the warm itself (background thread) ------------------------------

    def _warm(self, key, layout, dims, chunk: int, flags: dict) -> None:
        watcher.install()
        watcher.register_background()
        try:
            import jax

            warm_delta_solve(layout, dims, chunk, flags)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ..parallel.sharded_solver import (
                    PACKED2D_FLAGS, solve_allocate_sharded_arena,
                    solve_allocate_sharded_packed2d,
                )
                sflags = {k2: v for k2, v in flags.items()
                          if k2 in PACKED2D_FLAGS}
                params = {k2: jax.device_put(v)
                          for k2, v in dummy_score_params(dims).items()}
                rs = solve_allocate_sharded_packed2d(
                    *(jax.device_put(b)
                      for b in dummy_packed_buffers(layout, chunk)),
                    layout, params, self.mesh, **sflags)
                rs.assigned.block_until_ready()
                # the sharded ARENA variant too: a sharded session's
                # bucket crossing dispatches this entry against the
                # ShardedDeviceCache's shardings (node_static split along
                # 'n', scalars replicated), so the warm must match them
                sharded_bufs = dummy_sharded_buffers(
                    layout, chunk, self.mesh)
                ns_n = NamedSharding(self.mesh, P("n"))
                ns_rep = NamedSharding(self.mesh, P())
                sparams = {k2: jax.device_put(
                               np.asarray(v),
                               ns_n if k2 == "node_static" else ns_rep)
                           for k2, v in dummy_score_params(dims).items()}
                ra = solve_allocate_sharded_arena(
                    *sharded_bufs, sparams, self.mesh, **sflags)
                ra.assigned.block_until_ready()
            with self._lock:
                self._started[key] = "done"
                self.completions += 1
            from ..metrics import metrics

            metrics.prewarm_completions_total.inc()
            log.info("pre-warmed solver variants for buckets %s", dims)
        except Exception:  # noqa: BLE001 — a failed warm must not crash
            with self._lock:
                self._started[key] = "failed"
                self.failures += 1
            log.exception("bucket pre-warm failed for %s", dims)

    # -- sync points (bench / tests / shutdown) ---------------------------

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Join outstanding warm threads; True when none remain alive."""
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout)
        with self._lock:
            self._threads = [t for t in self._threads if t.is_alive()]
            return not self._threads

    @property
    def pending(self) -> int:
        with self._lock:
            return sum(1 for t in self._threads if t.is_alive())
