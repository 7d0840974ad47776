"""Bind writes at the mesh's scale (ms per 1,000 traffic pods bound in the
window): the program's ``volcano.bind.write`` span, the bind effector's
store writes and the watch fan-out they trigger."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.bind.write")
