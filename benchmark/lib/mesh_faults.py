"""Faults planted under the node-sharded allocate path, to show that
``correct`` comes out false when it is broken. ``lib/faults.py`` patches
``decode_compact``, which the sharded path never calls; these patch the
sharded solve's result instead, where the allocate action receives it from
``volcano_tpu.parallel.solve_allocate_sharded_arena``:

- ``solve_nothing``: the sharded solve places no task;
- ``solve_to_node0``: every placement of the sharded solve is altered to
  node index 0.

Each is a context manager around one ``run_cell`` and restores what it
patched; it yields no ``plant`` callback. The benchmark's own runs never
plant one.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(fault: str):
    if fault not in FAULTS:
        raise ValueError(f"unknown mesh fault {fault!r}")
    import jax.numpy as jnp

    import volcano_tpu.parallel as parallel

    orig = parallel.solve_allocate_sharded_arena
    to = -1 if fault == "solve_nothing" else 0

    def solve(*args, **kwargs):
        res = orig(*args, **kwargs)
        return res._replace(
            assigned=jnp.where(res.assigned >= 0, to, res.assigned))

    parallel.solve_allocate_sharded_arena = solve
    try:
        yield None
    finally:
        parallel.solve_allocate_sharded_arena = orig


FAULTS = ("solve_nothing", "solve_to_node0")
