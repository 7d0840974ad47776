"""Replay + bind (ms per 1,000 traffic pods bound in the window): the
``volcano.allocate.replay`` span."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.allocate.replay")
