"""Evict scan step use (%): the claimers the evict scan solved over the
steps it ran, padding included (``100 * evict_claimers /
evict_scan_steps``)."""

from lib.program import ratio


def read(run):
    return ratio(run, "evict_claimers", "evict_scan_steps", 100.0)
