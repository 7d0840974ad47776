"""Scalar overlay (ms per 1,000 traffic pods bound in the window): the
flatten's pass that writes scalar requests into the task rows
(``volcano.allocate.flatten.scalar``)."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.allocate.flatten.scalar")
