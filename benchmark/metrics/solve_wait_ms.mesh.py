"""Node-sharded allocate solve, host side (ms per 1,000 traffic pods bound
in the window): the ``volcano.allocate.dispatch`` and ``.readback`` spans."""

from lib.program import span_per_kpod


def read(run):
    return span_per_kpod(run, "volcano.allocate.dispatch",
                         "volcano.allocate.readback")
