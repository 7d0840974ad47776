"""Evict solve, device round trip (ms per 1,000 traffic pods bound in the
window): last_cycle_timing preempt_solve_ms (dispatch + readback of the
evict kernel)."""

from lib.layers import per_kpod, timing


def read(run):
    return per_kpod(run, timing("preempt_solve_ms"))
