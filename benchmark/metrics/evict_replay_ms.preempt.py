"""Evict solve replay (ms per 1,000 traffic pods bound in the window):
the preempt action's post-solve validation, statements and evictions,
and its intra-job phase (``volcano.preempt.replay``, ``.intra_job``)."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.preempt.replay",
                         "volcano.preempt.intra_job")
