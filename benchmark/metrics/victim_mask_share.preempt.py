"""Victim mask share (%): the claimer rows of the evict solve's
eligibility matrix that the plugins' column forms decided alone, with no
per-claimer plugin call (``100 * victim_rows_masked / victim_rows``)."""

from lib.program import ratio


def read(run):
    return ratio(run, "victim_rows_masked", "victim_rows", 100.0)
