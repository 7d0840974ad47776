"""Control plane (ms per 1,000 traffic pods bound in the window): the turn's
wall time outside Scheduler.run_once."""

from lib.layers import control, per_kpod


def read(run):
    return per_kpod(run, control)
