"""Scheduling wait at the mesh's scale (ms, mean per bound pod): from each
pod's creation to the open of the session that bound it
(``pod_wait_ms_sum / pod_wait_n``, counted by the program at the bind
write)."""

from lib.program import ratio


def read(run):
    return ratio(run, "pod_wait_ms_sum", "pod_wait_n")
