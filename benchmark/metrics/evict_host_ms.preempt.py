"""Evict solve, host side (ms per 1,000 traffic pods bound in the window): the
preempt action's time outside the device solve's dispatch and readback
(``preempt_ms - preempt_solve_ms``)."""

from lib.layers import per_kpod


def read(run):
    return per_kpod(run, lambda t: float(t.timing.get("preempt_ms", 0.0))
                    - float(t.timing.get("preempt_solve_ms", 0.0)))
