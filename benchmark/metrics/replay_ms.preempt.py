"""Replay + bind (ms per 1,000 traffic pods bound in the window):
last_cycle_timing replay_ms."""

from lib.layers import per_kpod, timing


def read(run):
    return per_kpod(run, timing("replay_ms"))
