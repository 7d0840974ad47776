"""Session open (ms per 1,000 traffic pods bound in the window): the
``volcano.session.open`` span."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.session.open")
