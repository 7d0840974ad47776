"""Allocate solve, host side (ms per 1,000 traffic pods bound in the window):
dispatch_ms + readback_ms."""

from lib.layers import per_kpod, solve_wait


def read(run):
    return per_kpod(run, solve_wait)
