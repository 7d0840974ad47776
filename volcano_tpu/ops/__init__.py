"""JAX/TPU kernels: snapshot flattening, feasibility, scoring, solvers.

Solver imports are lazy (PEP 562) so the pure-Python control plane
(controllers, webhooks, CLI, cache) never pays jax/PJRT initialization —
jax loads on the first actual solve.
"""

from .arrays import (  # noqa: F401
    FlattenCache, ScoreParams, SnapshotArrays, bucket, flatten_snapshot,
)
from .ordering import OrderCache  # noqa: F401

_LAZY = ("SolveResult", "fits_matrix", "score_matrix", "solve_allocate",
         "solve_allocate_sequential", "solve_allocate_packed2d")
_LAZY_EVICT = ("EvictResult", "solve_evict")
_LAZY_DEVCACHE = ("PackedDeviceCache", "ShardedDeviceCache",
                  "split_packed_layout")
# precompile itself only imports jax lazily (inside functions/threads), but
# routing it through the lazy hook keeps the import-cost contract uniform
_LAZY_PRECOMPILE = ("BucketPrewarmer", "CompileWatcher",
                    "configure_compilation_cache", "watcher")
_LAZY_PIPELINE = ("SessionPipeline", "SessionTicket", "start_readback")

__all__ = ["FlattenCache", "OrderCache", "ScoreParams", "SnapshotArrays",
           "bucket", "flatten_snapshot", *_LAZY, *_LAZY_EVICT,
           *_LAZY_DEVCACHE, *_LAZY_PRECOMPILE, *_LAZY_PIPELINE]


def __getattr__(name):
    if name in _LAZY:
        from . import solver
        return getattr(solver, name)
    if name in _LAZY_EVICT:
        from . import evict
        return getattr(evict, name)
    if name in _LAZY_DEVCACHE:
        from . import device_cache
        return getattr(device_cache, name)
    if name in _LAZY_PRECOMPILE:
        from . import precompile
        return getattr(precompile, name)
    if name in _LAZY_PIPELINE:
        from . import pipeline
        return getattr(pipeline, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
