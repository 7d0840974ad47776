"""Evict solve preparation (ms per 1,000 traffic pods bound in the
window): the preempt action's claimer collection, flatten, and victims
with their arrays and score inputs (``volcano.preempt.collect``,
``.flatten``, ``.victims``)."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.preempt.collect",
                         "volcano.preempt.flatten",
                         "volcano.preempt.victims")
