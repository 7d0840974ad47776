"""Spans and counters: where a turn's time and work go, measured where the
work happens.

``span("volcano.<layer>.<step>")`` times a block three ways at once:

- **turn record**: the block's wall time, in ms, is added to the open turn
  record under the span's name, and under ``key`` where one is given (the
  names ``Scheduler.last_cycle_timing`` has always carried: ``open_ms``,
  ``total_ms``, ...). Its self time, the wall time less that of the
  spans that closed inside it, is added under ``record["self_ms"][name]``;
- **profiler**: while a profiler runs, the block is a
  ``jax.profiler.TraceAnnotation`` of the same name, on the profiler's
  host plane and so on the device trace's clock. JAX is looked up only
  once something else has imported it: a process that never imports JAX
  (a store-only shard worker) does not import it here;
- **exporter**: the registry histogram ``volcano_span_milliseconds{span}``.

``count(name, n)`` adds ``n`` to the open turn record and to the registry
counter ``volcano_turn_counter_total{counter}``.

A turn record is a plain dict. The outermost span opened with
``root=True`` starts one; every span and count inside it, on the same
thread (or in a context copied from it), adds to it. With no record open,
spans and counts still reach the profiler and the registry. A span left
by an exception, or ``discard()``-ed because its work turned out not to
happen, adds nothing to the record or the histogram: a key's presence
keeps meaning that its step ran. With no profiler running, a span costs a
``perf_counter`` pair and one profiler-activity check.
"""

from __future__ import annotations

import sys
from contextvars import ContextVar
from time import perf_counter
from typing import Optional

from .metrics import Counter, Histogram, registry

#: spans' wall times, 10 us .. ~84 s
span_ms = registry.register(Histogram(
    "volcano_span_milliseconds",
    "Wall time of each named span of the scheduler's turn, in ms",
    ["span"], buckets=tuple(0.01 * 2 ** i for i in range(24))))
turn_counter_total = registry.register(Counter(
    "volcano_turn_counter_total",
    "Work counted where it happens (pods created, binds and evictions "
    "written, solver rounds, evict scan steps, pod wait), by counter",
    ["counter"]))

_open: ContextVar[Optional["span"]] = ContextVar("volcano_open_span",
                                                 default=None)
_annotation = None


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is imported, else None."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        _annotation = profiler.TraceAnnotation
    return _annotation


class span:
    """A named, timed block: see the module docstring. ``meta`` goes onto
    the profiler's event only (``volcano.turn`` carries the turn number).
    After the block, ``ms`` holds its wall time and ``self_ms`` its self
    time; ``record`` is the turn record it added to, or None."""

    __slots__ = ("name", "key", "record", "ms", "self_ms", "_root", "_meta",
                 "_parent", "_token", "_ann", "_t0", "_child", "_discarded")

    def __init__(self, name: str, key: Optional[str] = None,
                 root: bool = False, **meta):
        self.name = name
        self.key = key
        self.record: Optional[dict] = None
        self.ms = self.self_ms = 0.0
        self._root = root
        self._meta = meta
        self._discarded = False

    def __enter__(self) -> "span":
        parent = _open.get()
        self._parent = parent
        if parent is not None and parent.record is not None:
            self.record = parent.record
        elif self._root:
            self.record = {}
        ann = _profiler_annotation()
        self._ann = None
        if ann is not None and ann.is_enabled():
            self._ann = ann(self.name, **self._meta)
            self._ann.__enter__()
        self._token = _open.set(self)
        self._child = 0.0
        self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.ms = (perf_counter() - self._t0) * 1e3
        _open.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None or self._discarded:
            return False
        self.self_ms = self.ms - self._child
        if self._parent is not None:
            self._parent._child += self.ms
        rec = self.record
        if rec is not None:
            rec[self.name] = rec.get(self.name, 0.0) + self.ms
            if self.key is not None:
                rec[self.key] = rec.get(self.key, 0.0) + self.ms
            selfs = rec.get("self_ms")
            if selfs is None:
                selfs = rec["self_ms"] = {}
            selfs[self.name] = selfs.get(self.name, 0.0) + self.self_ms
        span_ms.observe(self.ms, labels={"span": self.name})
        return False

    def discard(self) -> None:
        """The step this span times did not happen after all (nothing to
        place): record nothing for it."""
        self._discarded = True


def count(name: str, n: float = 1.0) -> None:
    """Add ``n`` to counter ``name`` in the open turn record and the
    registry."""
    sp = _open.get()
    if sp is not None and sp.record is not None:
        sp.record[name] = sp.record.get(name, 0.0) + n
    turn_counter_total.inc(n, labels={"counter": name})
