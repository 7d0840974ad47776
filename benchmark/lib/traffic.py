"""The one traffic generator: reads a traffic file's parameters and draws
every job from ``--seed``.

Each seed gets the same multiset of job sizes and pod shapes, in another
order (stratified draws, then a seeded shuffle), so two seeds do the same
work and differ only in its order. The arithmetic follows
``volcano_tpu/sim/workload.py``'s seeded generator (gang/queue/priority
mixes), copied here so that a later change to ``sim/`` cannot move the
yardstick.

A mix names its pods' shapes by the configuration's ``pods`` templates
(the source's pod specs), so the requests come from the deployment and the
grouping and arrival from the traffic. Imports nothing of the program.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import Dict, List, Sequence


@dataclass
class JobDraw:
    """One job as the load generator submits it."""

    name: str
    size: int            # replicas of its single task
    min_available: int
    cpu: str             # quantity per pod, as the pod template states it
    memory: str
    queue: str
    priority_class: str


def _counts(weights: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` items over ``weights``: the exact
    multiset every seed gets."""
    total = float(sum(weights))
    raw = [w / total * n for w in weights]
    counts = [int(math.floor(r)) for r in raw]
    rest = sorted(range(len(raw)), key=lambda i: (counts[i] - raw[i], i))
    for i in rest[:n - sum(counts)]:
        counts[i] += 1
    return counts


def stratified(rng: random.Random, choices: Sequence[Sequence], n: int
               ) -> list:
    """``n`` values drawn from ``[[value, weight], ...]`` in their exact
    proportions, shuffled by ``rng``."""
    out: list = []
    for c, k in zip(choices, _counts([c[1] for c in choices], n)):
        out.extend([c[0]] * k)
    rng.shuffle(out)
    return out


def draw_jobs(rng: random.Random, mix: dict, n: int, prefix: str,
              queues: Sequence[str], pods: Dict[str, dict]) -> List[JobDraw]:
    """``n`` jobs of a mix: (size, pod template) drawn jointly in the
    proportions of their product, so every seed gets the same multiset of
    job shapes; queues round-robin, as ``sim/workload.py`` assigns them."""
    joint = [[(s, p), ws * wp] for s, ws in mix["sizes"]
             for p, wp in mix["pods"]]
    shapes = stratified(rng, joint, n)
    mn = mix.get("min", "replicas")
    jobs = []
    for k, (size, pod) in enumerate(shapes):
        tpl = pods[pod]
        jobs.append(JobDraw(
            name=f"{prefix}{k}", size=int(size),
            min_available=int(size) if mn == "replicas"
            else min(int(mn), int(size)),
            cpu=str(tpl["cpu"]), memory=str(tpl["memory"]),
            queue=queues[k % len(queues)] if queues else "default",
            priority_class=tpl.get("priority_class", "")))
    return jobs


def wave(traffic: dict, seed: int, index: int, pods: Dict[str, dict]
         ) -> List[JobDraw]:
    """Wave ``index`` of a closed-loop cell: ``wave_jobs`` jobs, drawn from
    a stream of their own so that waves do not depend on how many came
    before."""
    rng = random.Random(f"{seed}/wave/{index}")
    return draw_jobs(rng, traffic["jobs"], int(traffic["wave_jobs"]),
                     f"w{index}-", list(traffic.get("queues", [])), pods)


def prefill_jobs(spec: dict, seed: int, prefix: str, pods: Dict[str, dict]
                 ) -> List[JobDraw]:
    """The ``count`` long-running jobs the cluster holds before the run
    starts."""
    rng = random.Random(f"{seed}/prefill/{prefix}")
    return draw_jobs(rng, spec["jobs"], int(spec["count"]), prefix,
                     list(spec.get("queues", [])), pods)
