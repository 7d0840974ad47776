"""Transient-failure classification + one-shot retry.

A call that crosses a wire (the remote-store client, a multi-device
dispatch) fails in two distinct ways: *transient* transport hiccups — a
half-closed socket, a deadline — that succeed when simply re-sent, and
*real* faults that must count against the circuit breaker and degrade to
the host oracle. A device runtime error on the locally attached chip (an
OOM, a Mosaic failure) is always the second kind: re-sending it only
doubles the cost of the fault.

``retry_transient`` gives call sites one cheap re-send for the first kind
only; anything else (and a second transient failure) raises to the
caller's breaker/fallback handling. The marker list is shared with
``bench.py``'s per-config isolation so both layers agree on what
"transient" means.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, TypeVar

log = logging.getLogger(__name__)

T = TypeVar("T")

#: substrings identifying a retriable transport failure (exception type
#: name or message); deliberately conservative — an unknown error must
#: reach the breaker, not loop here
TRANSIENT_MARKERS = (
    "connection", "Connection", "socket",
    "UNAVAILABLE", "DEADLINE", "timed out", "timeout", "closed",
)


def is_transient(exc: BaseException) -> bool:
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in TRANSIENT_MARKERS)


def retry_transient(fn: Callable[[], T], retries: int = 1,
                    delay_s: float = 0.2, what: str = "dispatch") -> T:
    """Run ``fn``; re-run it up to ``retries`` times when it fails with a
    transient transport error. Non-transient errors (and the final
    transient one) propagate unchanged so breaker accounting still sees
    them."""
    attempt = 0
    while True:
        try:
            return fn()
        except KeyboardInterrupt:
            raise
        except Exception as e:  # noqa: BLE001 — classified below
            if attempt >= retries or not is_transient(e):
                raise
            attempt += 1
            log.warning("%s failed with a transient transport error "
                        "(attempt %d/%d, retrying in %.1fs): %s",
                        what, attempt, retries + 1, delay_s,
                        str(e).splitlines()[0][:200])
            time.sleep(delay_s)
