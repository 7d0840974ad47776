"""Device-mesh sharding of the solver (the multi-chip scale axis)."""

from .sharded_evict import solve_evict_uniform_sharded  # noqa: F401
from .sharded_solver import (  # noqa: F401
    arena_mesh, make_mesh, solve_allocate_sharded,
    solve_allocate_sharded_arena, solve_allocate_sharded_packed2d,
)
