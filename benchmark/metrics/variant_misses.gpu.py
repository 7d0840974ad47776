"""Variant misses (dispatches): the window's allocate dispatches whose
executable was not compiled or warmed before the dispatch, so that the
dispatch compiled it or loaded it from the persistent cache
(``variant_misses``). A warm steady turn reads 0."""

from lib.program import total


def read(run):
    if not any("variant_misses" in t.timing for t in run.turns):
        return None
    return total(run, "variant_misses")
