"""Session close's status writes (ms per 1,000 traffic pods bound in the
window), in the burst cells: the job updater's PodGroup status and
Unschedulable condition writes at the end of each session
(``volcano.session.close.update``)."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.session.close.update")
