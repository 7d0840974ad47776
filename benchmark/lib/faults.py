"""Faults planted under the timed path, to show that ``correct`` comes out
false when the path is broken (``tests/test_faults.py`` on the CPU,
``control.py`` on the chip). The benchmark's own runs never plant one.

- ``bind_one_node`` (the control): the bind effector writes every pod to
  node ``n0``, breaking the capacity guarantee where the answer reaches
  the store;
- ``solve_nothing``: the allocate solve's readback places no task, the
  scheduler's step returning its state unchanged;
- ``solve_to_node0``: every placement of the allocate solve is altered to
  node index 0 where the readback produces it;
- ``evict_nothing``: the evict solve's readback evicts no victim;
- ``evict_extra``: each preemptor job's evictions take one victim more
  than the solve chose, the next one on the same node: the room a smarter
  or a broken choice of victims would move.

Each is a context manager around one ``run_cell``; it takes the cluster
through ``plant`` where it needs it and restores what it patched.
"""

from __future__ import annotations

import contextlib

import numpy as np


class _OneNodeBinder:
    def __init__(self, inner):
        self.inner = inner

    def bind(self, pod, hostname):
        self.inner.bind(pod, "n0")

    def __getattr__(self, name):
        return getattr(self.inner, name)


@contextlib.contextmanager
def _patch(module, name, wrap):
    orig = getattr(module, name)
    setattr(module, name, wrap(orig))
    try:
        yield None
    finally:
        setattr(module, name, orig)


@contextlib.contextmanager
def planted(fault: str):
    """Yield the ``plant`` callback for ``run_cell`` (or None)."""
    if fault == "bind_one_node":
        def plant(cluster):
            cluster.sa.cache.binder = _OneNodeBinder(cluster.sa.cache.binder)
        yield plant
        return
    if fault in ("solve_nothing", "solve_to_node0"):
        from volcano_tpu.ops import solver

        def wrap(orig):
            def decode(compact):
                assigned, kind = orig(compact)
                assigned = np.array(assigned, copy=True)
                placed = assigned >= 0
                assigned[placed] = -1 if fault == "solve_nothing" else 0
                return assigned, kind
            return decode
        with _patch(solver, "decode_compact", wrap):
            yield None
        return
    if fault == "evict_nothing":
        from volcano_tpu.ops import evict

        def wrap(orig):
            def decode(compact, n):
                assigned, evicted_by = orig(compact, n)
                return assigned, np.full_like(np.asarray(evicted_by), -1)
            return decode
        with _patch(evict, "decode_evict_compact", wrap):
            yield None
        return
    if fault == "evict_extra":
        from volcano_tpu.actions import evict_solver

        seen = {}

        def wrap_collect(orig):
            def collect(ssn, nodes_list):
                seen["victims"] = orig(ssn, nodes_list)
                return seen["victims"]
            return collect

        def wrap_by_job(orig):
            def by_job(evicted_by):
                out = orig(evicted_by)
                vs = seen.get("victims", [])
                taken = {vi for evs in out.values() for vi in evs}
                for evs in out.values():
                    nxt = max(evs) + 1
                    if nxt < len(vs) and nxt not in taken and \
                            vs[nxt].node_name == vs[evs[-1]].node_name:
                        evs.append(nxt)
                        taken.add(nxt)
                return out
            return by_job
        with _patch(evict_solver, "collect_victims", wrap_collect), \
                _patch(evict_solver, "_evictions_by_job", wrap_by_job):
            yield None
        return
    raise ValueError(f"unknown fault {fault!r}")


FAULTS = ("bind_one_node", "solve_nothing", "solve_to_node0",
          "evict_nothing", "evict_extra")
