"""Shared arithmetic of the metric readers (``metrics/<name>.py``).

A cell reports a layer's time in ms per 1,000 of the traffic's pods bound
in the window. A reader that finds nothing to read returns None, and the
harness leaves the metric out.
"""

from __future__ import annotations

from typing import Callable, Optional


def total_ms(run, fn: Callable) -> float:
    return sum(fn(t) for t in run.turns)


def per_kpod(run, fn: Callable) -> Optional[float]:
    if not run.turns or not run.binds:
        return None
    return total_ms(run, fn) / (run.binds / 1000.0)


def timing(key: str) -> Callable:
    return lambda t: float(t.timing.get(key, 0.0))


def control(t) -> float:
    """The turn's wall time outside ``Scheduler.run_once``: controllers,
    admission, the bind and evict effectors' writes, the store's watch."""
    return t.wall_ms - float(t.timing.get("total_ms", 0.0))


def prep(t) -> float:
    return float(t.timing.get("flatten_ms", 0.0)) \
        + float(t.timing.get("order_ms", 0.0))


def solve_wait(t) -> float:
    return float(t.timing.get("dispatch_ms", 0.0)) \
        + float(t.timing.get("readback_ms", 0.0))


def module_ms(run, part: str) -> Optional[float]:
    """Device time, in ms, of the executables whose jit name holds
    ``part``, in the traced window; None where the trace shows none."""
    tr = run.trace
    if tr is None:
        return None
    hit = [v for k, v in tr["module_s"].items() if part in k]
    if not hit:
        return None
    return sum(hit) * 1e3


def idle_pct(run) -> Optional[float]:
    if run.trace is None:
        return None
    return run.trace["idle_share"] * 100.0
