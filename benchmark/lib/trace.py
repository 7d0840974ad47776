"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

- busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  traced window (the benchmark's ``bench.window`` host span), averaged
  over the devices;
- module time: the device time of each executable (``XLA Modules`` line),
  keyed by its jit name with the ``jit_`` prefix and ``(id)`` suffix
  stripped: ``solve_allocate_delta``; the ten largest are the
  breakdown's ``device_ops``;
- idle gaps: the window's idle time on device 0, summed by the innermost
  benchmark host span (``bench.*``) that covers each idle stretch.

Checked against a trace recorded on the chip (``data/``,
``tests/test_trace.py``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_JIT = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def module_name(raw: str) -> str:
    return _JIT.match(raw).group(1)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def read_planes(pd) -> Tuple[Dict[str, Dict[str, list]], List[tuple]]:
    """(device planes: name -> {line name -> events}, host spans)."""
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[tuple] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {line.name: list(_events(line))
                                   for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name.startswith("bench."):
                        spans.append((name, s, s + d))
    return devices, spans


def reduce(pd, turns: int = 0) -> dict:
    """The device numbers of one traced window; ``turns`` is how many
    turns the window held (module time is also given per turn)."""
    devices, spans = read_planes(pd)
    win = [s for s in spans if s[0] == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0][1], win[0][2]
    if not devices:
        raise ValueError("trace holds no /device:TPU: plane")
    busy_each = []
    modules: Dict[str, float] = {}
    first_busy: Optional[List[Interval]] = None
    for name in sorted(devices):
        lines = devices[name]
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = union(clip([(s, s + d) for _, s, d in op_line], lo, hi))
        busy_each.append(sum(b - a for a, b in busy))
        if first_busy is None:
            first_busy = busy
        for n, s, d in lines.get("XLA Modules", []):
            if lo <= s < hi:
                key = module_name(n)
                modules[key] = modules.get(key, 0.0) + d
    window_ns = hi - lo
    busy_ns = sum(busy_each) / len(busy_each)
    gaps = idle_gaps(first_busy or [], lo, hi, spans)
    top = sorted(modules.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "module_s": {k: v / 1e9 for k, v in modules.items()},
        "turns": turns,
        "breakdown": {
            "device_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": [[n, v / 1e9] for n, v in gaps[:10]],
        },
    }


def idle_gaps(busy: Sequence[Interval], lo: float, hi: float,
              spans: Sequence[tuple]) -> List[Tuple[str, float]]:
    """The window's idle time by what the host was doing: the window is
    cut at every span edge, each piece is labelled by the innermost
    ``bench.*`` span (other than the window) that covers it, and the
    device's idle time in the pieces is summed by label, most first."""
    inner = [s for s in spans if s[0] != "bench.window"]
    edges = sorted({lo, hi} | {x for s in inner for x in (s[1], s[2])
                               if lo < x < hi})
    starts = [a for a, _ in busy]
    acc = [0.0]
    for a, b in busy:
        acc.append(acc[-1] + (b - a))

    def busy_upto(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        a, b = busy[i - 1]
        return acc[i - 1] + (min(t, b) - a)

    out: Dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        cover = [s for s in inner if s[1] <= mid <= s[2]]
        label = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "bench.none"
        idle = (b - a) - (busy_upto(b) - busy_upto(a))
        if idle > 0:
            out[label] = out.get(label, 0.0) + idle
    return sorted(out.items(), key=lambda kv: -kv[1])


def xplane_in(dirname: str) -> str:
    found = glob.glob(os.path.join(dirname, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise ValueError(f"no .xplane.pb under {dirname}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_dir(dirname: str, turns: int = 0) -> dict:
    return reduce(load(xplane_in(dirname)), turns)
