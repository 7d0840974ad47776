"""Evict solve on the device (ms per 1,000 traffic pods bound in the window):
device time of the solve_evict* executables in the trace."""

from lib.layers import module_ms


def read(run):
    ms = module_ms(run, "solve_evict")
    return None if ms is None or not run.binds else ms / (run.binds / 1000.0)
