"""Round budget hits (per 1,000 traffic pods bound in the window): the
allocate solve's admission phases that stopped at their round budget while
their last round still admitted (``round_cap_hits``). A solve that runs
every phase to its fixpoint reads 0."""

from lib.program import count_per_kpod


def read(run):
    return count_per_kpod(run, "round_cap_hits")
