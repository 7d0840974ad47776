"""Shared arithmetic of the readers of the program's own spans and
counters: the ``volcano.*`` span keys and the counters that the program
adds to each turn's record (``run.turns[i].timing``).

A run of a program that records none of the keys a reader reads gives
None, and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from .layers import per_kpod


def _recorded(run, keys) -> bool:
    return any(k in t.timing for t in run.turns for k in keys)


def total(run, key: str) -> float:
    return sum(float(t.timing.get(key, 0.0)) for t in run.turns)


def span_per_kpod(run, *names: str) -> Optional[float]:
    """ms per 1,000 traffic pods bound in the window, summed over the
    spans ``names``."""
    if not _recorded(run, names):
        return None
    return per_kpod(run, lambda t: sum(float(t.timing.get(n, 0.0))
                                       for n in names))


def count_per_kpod(run, key: str) -> Optional[float]:
    if not _recorded(run, (key,)):
        return None
    return per_kpod(run, lambda t: float(t.timing.get(key, 0.0)))


def ratio(run, num: str, den: str, scale: float = 1.0) -> Optional[float]:
    """scale x (counter ``num`` / counter ``den``) over the window."""
    d = total(run, den)
    if not _recorded(run, (num, den)) or not d:
        return None
    return scale * total(run, num) / d
