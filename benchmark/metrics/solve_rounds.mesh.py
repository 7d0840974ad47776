"""Node-sharded allocate solve rounds (per 1,000 traffic pods bound in the
window): the rounds the sharded solve ran (``solve_rounds``, read back with
its assignment)."""

from lib.program import count_per_kpod


def read(run):
    return count_per_kpod(run, "solve_rounds")
