"""Controllers (ms per 1,000 traffic pods bound in the window): the
program's ``volcano.controllers`` span, both drains of a turn."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.controllers")
