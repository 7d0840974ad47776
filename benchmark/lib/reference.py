"""The plain reference: an independent model of the cluster, replayed from
the store's own event stream, that holds a run to the guarantees the
configuration states.

It imports nothing of the program and takes nothing the program made: the
nodes' sizes and the jobs' requests, gang minimums and priorities come from
the configuration and traffic files, and the events are what a watcher of
the store saw (binds, deletion marks, deletions), in order, with the
harness's own turn and job-deletion markers between them.

Each count it returns is compared with its limit (0: the comparison is
exact):

- ``over_capacity``: binds after which a node's pods request more cpu,
  memory or pod slots than the node has, or that name no known node,
  plus the nodes over their allocatable in the store at the end;
- ``gang_partial``: (turn end, job) pairs at which a live job had some but
  fewer than ``minAvailable`` pods bound, evicted or not;
- ``double_bind``: binds of a pod that was already bound elsewhere;
- ``evict_priority``: evictions of a pod whose priority is not below that
  of some pod then waiting (no pending pod could have needed the room);
- ``over_evicted``: binds of a pod onto a node that victims of lower
  priority were evicted from, where the node would still have held it
  with one of those victims kept: more was evicted than the preemptor
  needed;
- ``never_started``: measured jobs that never had ``minAvailable`` pods
  bound by the end of the drain.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

# event kinds in the log (kept small: the watcher appends in the window)
ADD, BIND, MARK, DELETE, TURN, JOBDEL = range(6)


def parse_quantity(q) -> float:
    """Kubernetes quantities: "2", "250m", "4Gi", "1G", 3."""
    if isinstance(q, (int, float)):
        return float(q)
    s = str(q).strip()
    bin_suffix = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40}
    dec_suffix = {"k": 1e3, "M": 1e6, "G": 1e9, "T": 1e12, "m": 1e-3}
    for suf, mul in bin_suffix.items():
        if s.endswith(suf):
            return float(s[:-2]) * mul
    if s and s[-1] in dec_suffix:
        return float(s[:-1]) * dec_suffix[s[-1]]
    return float(s)


class JobFacts:
    """What the reference knows of a job, from the traffic file."""

    __slots__ = ("min_available", "req", "priority", "measured")

    def __init__(self, min_available: int, cpu: float, mem: float,
                 priority: int, measured: bool):
        self.min_available = min_available
        self.req = (cpu, mem, 1.0)
        self.priority = priority
        self.measured = measured


def job_of(pod_name: str) -> str:
    """Pods are named ``<job>-<task>-<index>`` by the job controller."""
    return pod_name.rsplit("-", 2)[0]


def check(nodes: Dict[str, Tuple[float, float, float]],
          jobs: Dict[str, JobFacts],
          log: Sequence[tuple]) -> Dict[str, int]:
    """Replay ``log`` against ``nodes`` (name -> cpu, memory, pods) and
    ``jobs``; return the count of each broken guarantee."""
    used = {n: [0.0, 0.0, 0.0] for n in nodes}
    pods: Dict[str, list] = {}          # name -> [node or "", marked]
    bound: Dict[str, int] = {}          # job -> pods bound, not marked
    started = set()
    deleted_jobs = set()
    pending_by_prio: Dict[int, int] = {}
    touched = set()
    # node -> victims evicted from it not yet followed by a bind of higher
    # priority there: (priority, request); and the requests of pods marked
    # for deletion but not yet gone, still in ``used``
    victims: Dict[str, List[tuple]] = {}
    leaving = {n: [0.0, 0.0, 0.0] for n in nodes}
    out = {"over_capacity": 0, "gang_partial": 0, "double_bind": 0,
           "evict_priority": 0, "over_evicted": 0, "never_started": 0}

    def pend(prio: int, d: int) -> None:
        pending_by_prio[prio] = pending_by_prio.get(prio, 0) + d

    def release(node: str, req) -> None:
        u = used.get(node)
        if u is not None:
            for i in range(3):
                u[i] -= req[i]

    for ev in log:
        kind = ev[0]
        if kind == TURN:
            for job in touched:
                if job in deleted_jobs:
                    continue
                c = bound.get(job, 0)
                if 0 < c < jobs[job].min_available:
                    out["gang_partial"] += 1
            touched.clear()
            continue
        if kind == JOBDEL:
            deleted_jobs.add(ev[1])
            continue
        name = ev[1]
        job = job_of(name)
        facts = jobs.get(job)
        if facts is None:
            continue  # not a pod of a generated job
        st = pods.get(name)
        if kind == ADD:
            if st is None:
                pods[name] = st = ["", False]
                pend(facts.priority, 1)
            if ev[2]:  # created already bound: count it as a bind
                kind = BIND
            else:
                continue
        if st is None:
            continue
        if kind == BIND:
            node = ev[2]
            if st[0] == node:
                continue
            if st[0]:
                out["double_bind"] += 1
                release(st[0], facts.req)
            elif not st[1]:
                pend(facts.priority, -1)
            st[0] = node
            u = used.get(node)
            if u is None:
                out["over_capacity"] += 1
            else:
                cap = nodes[node]
                for i in range(3):
                    u[i] += facts.req[i]
                if any(u[i] > cap[i] * (1 + 1e-9) for i in range(3)):
                    out["over_capacity"] += 1
                freed = [v for v in victims.get(node, ())
                         if v[0] < facts.priority]
                if freed:
                    victims[node] = [v for v in victims[node]
                                     if v[0] >= facts.priority]
                    live = [u[i] - leaving[node][i] for i in range(3)]
                    if any(all(live[i] + v[1][i] <= cap[i] * (1 + 1e-9)
                               for i in range(3)) for v in freed):
                        out["over_evicted"] += 1
            if not st[1]:
                c = bound[job] = bound.get(job, 0) + 1
                touched.add(job)
                if c >= facts.min_available:
                    started.add(job)
        elif kind == MARK:
            if st[1]:
                continue
            st[1] = True
            if st[0]:
                bound[job] -= 1
                touched.add(job)
                lv = leaving.get(st[0])
                if lv is not None:
                    for i in range(3):
                        lv[i] += facts.req[i]
                if job not in deleted_jobs:
                    victims.setdefault(st[0], []).append(
                        (facts.priority, facts.req))
                    waiting = [p for p, k in pending_by_prio.items() if k > 0]
                    if not waiting or facts.priority >= max(waiting):
                        out["evict_priority"] += 1
            else:
                pend(facts.priority, -1)
        elif kind == DELETE:
            if st[0]:
                release(st[0], facts.req)
                lv = leaving.get(st[0])
                if st[1] and lv is not None:
                    for i in range(3):
                        lv[i] -= facts.req[i]
                if not st[1]:
                    bound[job] -= 1
                    touched.add(job)
            elif not st[1]:
                pend(facts.priority, -1)
            del pods[name]
    out["never_started"] = sum(1 for j, f in jobs.items()
                               if f.measured and j not in started)
    return out


def over_capacity_now(nodes: Dict[str, Tuple[float, float, float]],
                      jobs: Dict[str, JobFacts],
                      bound: Iterable[Tuple[str, str]]) -> int:
    """Nodes over their allocatable in the store as it stands at the end
    (``bound``: pod name, node name), or bound pods naming no known node:
    a second reading that does not depend on the watch stream."""
    used: Dict[str, list] = {}
    out = 0
    for pod, node in bound:
        facts = jobs.get(job_of(pod))
        if facts is None:
            continue
        if node not in nodes:
            out += 1
            continue
        u = used.setdefault(node, [0.0, 0.0, 0.0])
        for i in range(3):
            u[i] += facts.req[i]
    for node, u in used.items():
        cap = nodes[node]
        if any(u[i] > cap[i] * (1 + 1e-9) for i in range(3)):
            out += 1
    return out


def limits() -> Dict[str, int]:
    """Every count is held at 0: each is a broken guarantee, and sound runs
    read 0 on every seed (PERF.md §2)."""
    return {"over_capacity": 0, "gang_partial": 0, "double_bind": 0,
            "evict_priority": 0, "over_evicted": 0, "never_started": 0,
            "off_device": 0,
            "errors_logged": 0}


def verdict(counts: Dict[str, int]) -> Tuple[bool, List[dict]]:
    lim = limits()
    rows = [{"name": k, "value": counts[k], "limit": lim[k]} for k in lim]
    return all(r["value"] <= r["limit"] for r in rows), rows
