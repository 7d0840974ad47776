"""Run one cell once: build the configuration's cluster behind the whole
control plane, drive the traffic through it, and hand every number to the
metric readers and the reference.

Nothing here knows a cell by name. A cell is an entry of ``BENCHMARK.json``;
its configuration is ``configs/<config>.json``, its traffic
``traffic/<traffic>.json`` and each metric ``metrics/<name>.py``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from . import reference as ref
from . import traffic as gen

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

clock = time.monotonic  # the same clock run.py reads at process start


class CellError(Exception):
    """The cell cannot be run as specified (missing file, unknown name)."""


# ---------------------------------------------------------------------------
# the cell, as BENCHMARK.json and its files name it
# ---------------------------------------------------------------------------

def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise CellError(f"missing {os.path.relpath(path, ROOT)}") from e


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return Cell(workload, int(w["chips"]), config, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def load_reader(name: str) -> Callable:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfmetric_" + name.replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise CellError(f"no reader metrics/{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclass
class Turn:
    t0: float
    t1: float
    timing: dict

    @property
    def wall_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


@dataclass
class Run:
    """Everything the metric readers see of one run."""

    seconds: float                     # the window's length as measured
    setup_s: float
    turns: List[Turn]                  # the window's turns
    binds: int                         # traffic pods bound in the window
    attempted: int                     # jobs submitted in the window
    failed: int                        # of those, refused or never started
    compiles: int                      # compiles inside the window
    trace: Optional[dict] = None       # lib.trace.reduce() of a traced run
    extra: dict = field(default_factory=dict)


class Watcher:
    """The store watch every bind is timed by. It appends one tuple per
    pod event that matters to the reference (``lib.reference`` kinds) and
    counts, per job of the traffic (``min_of``), the pods bound, so a
    closed loop sees a wave finish without scanning the store. ``binds``
    counts the traffic's pods only: a prefill job's pod bound again after
    an eviction is not work the traffic asked for."""

    def __init__(self, min_of: Dict[str, int]):
        self.log: List[tuple] = []
        self.min_of = min_of
        self._last: Dict[str, Tuple[str, bool]] = {}
        self._bound: Dict[str, int] = {}
        self.started: Dict[str, float] = {}
        self.binds = 0

    def on_pod(self, event, pod, _old) -> None:
        t = clock()
        name = pod.name
        if event == "delete":
            self._last.pop(name, None)
            self.log.append((ref.DELETE, name, "", t))
            return
        node = pod.node_name or ""
        marked = pod.deletion_timestamp is not None
        last = self._last.get(name)
        self._last[name] = (node, marked)
        if event == "add" or last is None:
            self.log.append((ref.ADD, name, node, t))
            if node:
                self._count(name, t)
            return
        if node and node != last[0]:
            self.log.append((ref.BIND, name, node, t))
            if not marked:
                self._count(name, t)
        if marked and not last[1]:
            self.log.append((ref.MARK, name, node, t))

    def _count(self, name: str, t: float) -> None:
        job = ref.job_of(name)
        if job not in self.min_of:
            return
        self.binds += 1
        c = self._bound[job] = self._bound.get(job, 0) + 1
        if c == self.min_of.get(job, -1) and job not in self.started:
            self.started[job] = t


class ErrorCount(logging.Handler):
    """Counts ERROR records the program logs: a failed bind, a replay the
    host refused, a device solve that raised."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.n = 0
        self.first: List[str] = []

    def emit(self, record) -> None:
        self.n += 1
        if len(self.first) < 5:
            self.first.append(f"{record.name}: {record.getMessage()[:300]}")


_TURN_KEYS = ("total_ms", "open_ms", "enqueue_ms", "allocate_ms",
              "preempt_ms", "backfill_ms", "flatten_ms", "order_ms",
              "dispatch_ms", "readback_ms", "preempt_solve_ms", "replay_ms",
              "session_compiles", "session_compile_s")


def turn_line(i: int, t: Turn) -> str:
    """One turn, for the run's stderr: where a slow run spent its turns."""
    fields = " ".join(f"{k}={t.timing[k]:.1f}" for k in _TURN_KEYS
                      if k in t.timing)
    return f"turn {i} at={t.t0:.3f} wall_ms={t.wall_ms:.1f} {fields}"


def off_device(timing: dict) -> bool:
    """A cycle that fell back to the host, tripped the breaker, or failed
    or timed out in an action (the same keys ``chip_smoke.py`` refuses)."""
    return any(k == "host_fallback" or k == "breaker_open"
               or k.endswith("_error") or k.endswith("_timeout")
               for k in timing) or timing.get("breaker_state", 0.0) != 0.0


# ---------------------------------------------------------------------------
# the cluster
# ---------------------------------------------------------------------------

def _job_object(d: gen.JobDraw):
    from volcano_tpu.models import Job, JobSpec, TaskSpec

    task = TaskSpec(name="task", replicas=d.size, template={"spec": {
        "containers": [{"name": "c", "requests": {
            "cpu": d.cpu, "memory": d.memory}}]}})
    return Job(name=d.name, namespace="default", spec=JobSpec(
        min_available=d.min_available, tasks=[task], queue=d.queue,
        priority_class_name=d.priority_class))


class Cluster:
    """The configuration's cluster behind one ``Standalone`` control plane
    (in-process store, admission, controllers, scheduler, effectors)."""

    def __init__(self, config: dict, watcher: Watcher):
        from volcano_tpu.controllers import KubeletStandin
        from volcano_tpu.models import Node, PriorityClass, Queue, QueueSpec
        from volcano_tpu.standalone import Standalone

        self.config = config
        self.sa = Standalone(scheduler_conf=config["scheduler_conf"],
                             metrics_port=0, async_effectors=False,
                             period=float(config["schedule_period_s"]))
        self.store = self.sa.store
        for c in self.sa.controllers.controllers:
            if isinstance(c, KubeletStandin):
                c.grace_seconds = float(config["kubelet_grace_s"])
        for name, value in config.get("priority_classes", {}).items():
            self.store.create("priorityclasses",
                              PriorityClass(name=name, value=int(value)))
        for name, weight in config.get("queues", []):
            self.store.create("queues", Queue(name=name,
                                              spec=QueueSpec(weight=weight)))
        n = config["nodes"]
        self.nodes: Dict[str, Tuple[float, float, float]] = {}
        for i in range(int(n["count"])):
            rl = {"cpu": str(n["cpu"]), "memory": n["memory"],
                  "pods": str(n["pods"])}
            self.store.create("nodes", Node(name=f"n{i}", allocatable=rl,
                                            capacity=dict(rl)))
            self.nodes[f"n{i}"] = (ref.parse_quantity(n["cpu"]),
                                   ref.parse_quantity(n["memory"]),
                                   float(n["pods"]))
        self.store.watch("pods", watcher.on_pod, replay=False)
        self.turns: List[Turn] = []
        self._free = {n: list(c) for n, c in self.nodes.items()}
        self._k = 0

    def submit(self, d: gen.JobDraw) -> bool:
        from volcano_tpu.client.store import AdmissionError

        try:
            self.store.create("jobs", _job_object(d))
        except AdmissionError:
            return False
        return True

    def finish(self, d: gen.JobDraw, log: List[tuple]) -> None:
        """The job ran to its end: delete it, and its pods and podgroup, as
        the garbage collector cascades a Job's deletion."""
        from volcano_tpu.client.store import NotFoundError

        log.append((ref.JOBDEL, d.name))
        for kind, name in ([("jobs", d.name)]
                           + [("pods", f"{d.name}-task-{i}")
                              for i in range(d.size)]
                           + [("podgroups", d.name)]):
            try:
                self.store.delete(kind, name, "default")
            except NotFoundError:
                pass

    def turn(self, log: List[tuple]) -> Turn:
        t0 = clock()
        self.sa.run_once()
        t1 = clock()
        log.append((ref.TURN, t1))
        tr = Turn(t0, t1, dict(self.sa.scheduler.last_cycle_timing))
        self.turns.append(tr)
        return tr

    def bind_directly(self, jobs: List[gen.JobDraw], stripe: bool,
                      fill: bool) -> None:
        """Prefill: bind the prefill jobs' pods straight in the store, as a
        scheduler that ran before this one left them. A job is bound whole
        or not at all. ``stripe``: pod k on node k mod N, else first fit;
        ``fill``: a job that no longer fits stays pending, else it is an
        error."""
        names = list(self.nodes)
        free = self._free
        for d in jobs:
            req = (ref.parse_quantity(d.cpu), ref.parse_quantity(d.memory),
                   1.0)
            placed = []
            for i in range(d.size):
                for step in range(len(names)):
                    node = names[(self._k + step) % len(names)]
                    f = free[node]
                    if all(f[r] >= req[r] for r in range(3)):
                        break
                else:
                    break
                for r in range(3):
                    f[r] -= req[r]
                placed.append(node)
                self._k = (self._k + step + (1 if stripe else 0)) \
                    % len(names)
            if len(placed) < d.size:
                for node in placed:
                    for r in range(3):
                        free[node][r] += req[r]
                if not fill:
                    raise CellError(f"prefill does not fit: {d.name}")
                continue
            for i, node in enumerate(placed):
                pod = self.store.get("pods", f"{d.name}-task-{i}", "default")
                pod.node_name = node
                pod.phase = "Running"
                self.store.update("pods", pod)

    def device_peak(self) -> int:
        import jax

        peak = 0
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def breaker_failures(self) -> int:
        br = self.sa.cache.breaker
        return (int(br.failures_total) + int(br.fallback_cycles)
                + (0 if br.state == "closed" else 1))

    def stop(self) -> None:
        self.sa.stop()


# ---------------------------------------------------------------------------
# tracing: the benchmark's own spans, the profiler around the window
# ---------------------------------------------------------------------------

def _span(name: str, fn: Callable) -> Callable:
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def install_spans(cluster: Cluster) -> None:
    """Host spans at each layer boundary of a turn, written into the
    profiler's trace (traced runs only)."""
    sa = cluster.sa
    sa.controllers.process_all = _span("bench.controllers",
                                       sa.controllers.process_all)
    sa.scheduler.run_once = _span("bench.scheduler", sa.scheduler.run_once)
    sa.cache.wait_for_effects = _span("bench.effects",
                                      sa.cache.wait_for_effects)


class Profiler:
    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
            if enabled else None
        self._ann = None

    def span(self, name: str):
        """A host span around the load generator's own work (traced runs
        only), so idle time is not left unlabelled."""
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self) -> None:
        if not self.enabled or self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def reduce(self, turns: int, keep: Optional[str] = None
               ) -> Optional[dict]:
        """The trace's numbers; ``keep`` saves the trace gzipped there
        first (how ``tests/record_trace.py`` made ``data/``)."""
        if not self.enabled:
            return None
        from . import trace

        try:
            path = trace.xplane_in(self.dir)
            if keep:
                import gzip

                with open(path, "rb") as f, gzip.open(keep, "wb") as g:
                    shutil.copyfileobj(f, g)
            return trace.reduce(trace.load(path), turns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

def _facts(cluster: Cluster, jobs: List[gen.JobDraw], measured: set
           ) -> Dict[str, ref.JobFacts]:
    prio = cluster.config.get("priority_classes", {})
    return {d.name: ref.JobFacts(d.min_available, ref.parse_quantity(d.cpu),
                                 ref.parse_quantity(d.memory),
                                 int(prio.get(d.priority_class, 0)),
                                 d.name in measured)
            for d in jobs}


def _prefill(cluster: Cluster, seed: int, log) -> List[gen.JobDraw]:
    """The cluster as the run finds it: the configuration's prefill jobs,
    each bound in the store (``bind_directly``)."""
    spec = cluster.config.get("prefill")
    if not spec:
        return []
    jobs = gen.prefill_jobs(spec, seed, "f", cluster.config["pods"])
    for d in jobs:
        if not cluster.submit(d):
            raise CellError(f"prefill job {d.name} refused")
    # one turn: the podgroups go Inqueue and the job controller makes the
    # pods; then they are bound in the store, and one more turn lets the
    # scheduler see them running
    cluster.turn(log)
    cluster.bind_directly(jobs, bool(spec.get("stripe")),
                          bool(spec.get("fill")))
    cluster.turn(log)
    return jobs


def run_closed(cluster: Cluster, traffic: dict, seed: int, seconds: float,
               watcher: Watcher, prof: Profiler, t_proc0: float
               ) -> Tuple[Run, Dict[str, ref.JobFacts]]:
    """Waves: the next is submitted once the watcher has seen every job of
    the last one start; the last one's jobs then finish (with
    ``finish_first``, before the next lands, and one turn passes). The
    window opens with the first measured wave and closes when the wave in
    flight at ``seconds`` has started."""
    log = watcher.log
    pods = cluster.config["pods"]
    pre = _prefill(cluster, seed, log)
    drain = float(traffic["drain_s"])
    all_jobs: List[gen.JobDraw] = []
    measured = set()
    refused = set()
    lead_waves = int(traffic["lead_in_waves"])
    index = 0
    prev: List[gen.JobDraw] = []
    w0 = w1 = None
    binds0 = c0 = 0
    window_turns: List[Turn] = []
    while True:
        d_wave = gen.wave(traffic, seed, index, pods)
        for d in d_wave:
            watcher.min_of[d.name] = d.min_available
        all_jobs += d_wave
        if index == lead_waves:
            w0 = clock()
            binds0 = watcher.binds
            c0 = _compiles()
            prof.start()
        if w0 is not None:
            measured.update(d.name for d in d_wave)
        if traffic.get("finish_first") and prev:
            # the last wave ends before this one lands, and one turn lets
            # the pending backlog take the room it freed: the new wave
            # has to preempt its way in
            with prof.span("bench.finish"):
                for d in prev:
                    cluster.finish(d, log)
            tr = cluster.turn(log)
            if w0 is not None:
                window_turns.append(tr)
            prev = []
        with prof.span("bench.submit"):
            for d in d_wave:
                if not cluster.submit(d):
                    refused.add(d.name)
        with prof.span("bench.finish"):
            for d in prev:
                cluster.finish(d, log)
        t_wave = clock()
        while not all(d.name in watcher.started or d.name in refused
                      for d in d_wave):
            tr = cluster.turn(log)
            if w0 is not None:
                window_turns.append(tr)
            if clock() - t_wave > drain:
                break
        prev = d_wave
        index += 1
        if w0 is not None and clock() - w0 >= seconds:
            break
    w1 = clock()
    binds = watcher.binds - binds0
    compiles = _compiles() - c0
    prof.stop()
    failed = sum(1 for name in measured
                 if name not in watcher.started or name in refused)
    run = Run(seconds=w1 - w0, setup_s=w0 - t_proc0, turns=window_turns,
              binds=binds, attempted=len(measured), failed=failed,
              compiles=compiles)
    run.extra["waves"] = index - lead_waves
    run.extra["refused"] = len(refused)
    facts = _facts(cluster, pre + all_jobs, measured)
    return run, facts


def _compiles() -> int:
    from volcano_tpu.ops.precompile import watcher

    return watcher.session_totals()[0]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int, require_chip: bool) -> dict:
    """The device as JAX reports it; refuses a run with no accelerator or
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or len(devs) < chips):
        raise CellError(f"no TPU with {chips} chip(s): {info}")
    return info


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_proc0: float, require_chip: bool = True,
             cell: Optional[Cell] = None, plant: Optional[Callable] = None,
             keep_trace: Optional[str] = None,
             cache_dir: Optional[str] = None) -> dict:
    """One run of ``workload``. ``cell``, ``plant`` and ``keep_trace`` are
    for the tests and the control: a cell built in code at a small size,
    a fault planted into the control plane before the traffic starts,
    where to save the raw trace, and a compile cache other than the
    checkout's ``.jax_cache``."""
    cell = cell or load_cell(workload)
    dev = device_info(cell.chips, require_chip)
    from volcano_tpu.ops import precompile

    precompile.configure_compilation_cache(
        cache_dir=cache_dir, default_dir=os.path.join(ROOT, ".jax_cache"))
    precompile.watcher.install()
    errors = ErrorCount()
    logging.getLogger("volcano_tpu").addHandler(errors)
    watcher = Watcher({})
    t_build = clock()
    cluster = Cluster(cell.config, watcher)
    t_built = clock()
    prof = Profiler(trace)
    try:
        if trace:
            install_spans(cluster)
        if plant is not None:
            plant(cluster)
        run, facts = run_closed(cluster, cell.traffic, seed, seconds,
                                watcher, prof, t_proc0)
        final = [(p.name, p.node_name) for p in cluster.store.list("pods")
                 if p.node_name and p.deletion_timestamp is None]
        peak = cluster.device_peak()
        off = sum(1 for t in cluster.turns if off_device(t.timing)) \
            + cluster.breaker_failures()
        solved = sum(1 for t in cluster.turns
                     if "dispatch_ms" in t.timing
                     or "preempt_solve_ms" in t.timing)
    finally:
        prof.stop()
        cluster.stop()
        logging.getLogger("volcano_tpu").removeHandler(errors)
    run.trace = prof.reduce(len(run.turns), keep_trace)
    counts = ref.check(cluster.nodes, facts, watcher.log)
    counts["over_capacity"] += ref.over_capacity_now(cluster.nodes, facts,
                                                     final)
    counts["off_device"] = off + (0 if solved else 1)
    counts["errors_logged"] = errors.n
    correct, rows = ref.verdict(counts)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev["memory_peak_bytes"] = peak
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    for i, t in enumerate(cluster.turns):
        print(turn_line(i, t), file=sys.stderr)
    out["notes"] = {"setup_split_s": {
                        "imports": t_build - t_proc0,
                        "cluster": t_built - t_build,
                        "traffic_and_warmup":
                            run.setup_s - (t_built - t_proc0)},
                    "turns": len(run.turns), "binds": run.binds,
                    "window_compiles": run.compiles,
                    "solved_cycles": solved, "errors": errors.first,
                    **run.extra}
    out["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                     for r in rows}
    return out
