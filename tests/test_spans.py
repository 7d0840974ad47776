"""metrics.spans: nesting, self time and the turn record; spans that do not
import JAX; spans on the profiler's host plane; and a small preemption run
through Standalone whose turn records carry every span and counter."""

import glob
import os
import subprocess
import sys
import threading

import pytest

import volcano_tpu.actions.evict_solver  # noqa: F401 — imported before timing
from volcano_tpu.metrics import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_nesting_self_time_and_the_turn_record():
    with spans.span("t.outer", "outer_ms", root=True) as outer:
        with spans.span("t.a") as a:
            with spans.span("t.a.b") as b:
                sum(range(20000))
        with spans.span("t.a") as a2:
            pass
        spans.count("things", 3)
        spans.count("things")
    rec = outer.record
    assert a.record is rec and b.record is rec
    assert rec["t.outer"] == rec["outer_ms"] == outer.ms
    assert rec["t.a"] == pytest.approx(a.ms + a2.ms)
    assert rec["t.a.b"] == b.ms
    assert rec["things"] == 4.0
    assert a.self_ms == pytest.approx(a.ms - b.ms)
    assert outer.self_ms == pytest.approx(outer.ms - a.ms - a2.ms)
    assert rec["self_ms"]["t.a"] == pytest.approx(a.self_ms + a2.self_ms)
    assert rec["self_ms"]["t.outer"] == outer.self_ms
    assert 0 < b.ms <= a.ms <= outer.ms


def test_root_joins_an_open_record_and_starts_one_otherwise():
    with spans.span("t.turn", root=True) as turn:
        with spans.span("t.cycle", root=True) as cycle:
            pass
    assert cycle.record is turn.record and "t.cycle" in turn.record
    with spans.span("t.free") as free:
        spans.count("t.uncounted")
    assert free.record is None
    with spans.span("t.cycle", root=True) as alone:
        pass
    assert set(alone.record) == {"t.cycle", "self_ms"}


def test_exception_and_discard_record_nothing():
    with spans.span("t.outer", root=True) as outer:
        with pytest.raises(ValueError):
            with spans.span("t.failed", "failed_ms"):
                raise ValueError("x")
        with spans.span("t.skipped", "skipped_ms") as sk:
            sk.discard()
    assert not {"t.failed", "failed_ms", "t.skipped",
                "skipped_ms"} & set(outer.record)
    # the time of the failed and the discarded step stays the outer's own
    assert outer.self_ms == outer.ms


def test_registry_histogram_and_counter():
    n0 = spans.span_ms.get_count({"span": "t.exported"})
    c0 = spans.turn_counter_total.get({"counter": "t.exported"})
    with spans.span("t.exported"):
        spans.count("t.exported", 2)
    assert spans.span_ms.get_count({"span": "t.exported"}) == n0 + 1
    assert spans.turn_counter_total.get({"counter": "t.exported"}) == c0 + 2


def test_a_thread_of_its_own_starts_outside_the_record():
    seen = {}

    def other():
        with spans.span("t.thread") as sp:
            seen["record"] = sp.record

    with spans.span("t.outer", root=True) as outer:
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    assert seen["record"] is None and "t.thread" not in outer.record


def test_spans_without_a_profiler_do_not_import_jax():
    code = (
        "import sys\n"
        "from volcano_tpu.metrics import spans\n"
        "import volcano_tpu.client.shardproc, volcano_tpu.controllers\n"
        "import volcano_tpu.cache.cache\n"
        "with spans.span('volcano.turn', root=True, turn=1) as t:\n"
        "    with spans.span('volcano.controllers'):\n"
        "        spans.count('pods_created', 2)\n"
        "assert t.record['pods_created'] == 2.0, t.record\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("volcano.turn", root=True, turn=7):
            with spans.span("volcano.scheduler", "total_ms"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path
    events = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(dict(e.stats))
    assert {"turn": 7} in events["volcano.turn"]
    assert "volcano.scheduler" in events


# ---------------------------------------------------------------------------
# a small preemption through the whole control plane
# ---------------------------------------------------------------------------

PREEMPT_CONF = """
actions: "enqueue, allocate, preempt, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: predicates
  - name: proportion
  - name: nodeorder
configurations:
- name: enqueue
  arguments:
    overcommit-factor: 2.0
"""

#: every span a preemption run's turns carry (volcano.allocate.pack and
#: .delta_plan run on the device-resident arena's path, which this is)
SPANS = (
    ["volcano.turn", "volcano.controllers", "volcano.effects",
     "volcano.scheduler", "volcano.session.open", "volcano.session.close",
     "volcano.bind.write"]
    + [f"volcano.controllers.{c}"
       for c in ("job", "podgroup", "queue", "gc", "kubelet")]
    + [f"volcano.action.{a}"
       for a in ("enqueue", "allocate", "preempt", "backfill")]
    + [f"volcano.allocate.{s}"
       for s in ("order", "flatten", "flatten.snapshot", "solve", "pack",
                 "delta_plan", "dispatch", "overlap", "readback", "replay")]
    + [f"volcano.preempt.{s}"
       for s in ("collect", "flatten", "victims", "solve", "replay",
                 "intra_job")])
COUNTERS = ("pods_created", "binds_written", "evictions_written",
            "solve_rounds", "evict_scan_steps", "evict_claimers",
            "victim_rows", "victim_rows_masked", "pod_wait_ms_sum",
            "pod_wait_n", "pod_events", "pod_task_builds",
            "round_cap_hits", "task_rows", "scalar_task_rows")
LEGACY = ("open_ms", "order_ms", "flatten_ms", "dispatch_ms", "readback_ms",
          "replay_ms", "solve_ms", "preempt_ms", "preempt_solve_ms",
          "total_ms")


def _job(name, cpu, priority_class):
    from volcano_tpu.models import Job, JobSpec, TaskSpec

    task = TaskSpec(name="task", replicas=1, template={"spec": {
        "containers": [{"name": "c", "requests": {
            "cpu": cpu, "memory": "500Mi"}}]}})
    return Job(name=name, namespace="default", spec=JobSpec(
        min_available=1, tasks=[task], priority_class_name=priority_class))


@pytest.fixture(scope="module")
def preemption():
    """Three 4-cpu nodes full of 900m low pods, then three 3000m high
    pods that each evict three of a node's four: every turn's record."""
    from volcano_tpu.controllers import KubeletStandin
    from volcano_tpu.models import Node, PriorityClass
    from volcano_tpu.standalone import Standalone

    sa = Standalone(scheduler_conf=PREEMPT_CONF, metrics_port=0,
                    async_effectors=False, period=0.0)
    try:
        for c in sa.controllers.controllers:
            if isinstance(c, KubeletStandin):
                c.grace_seconds = 0.0
        for name, value in (("high", 10), ("low", 0)):
            sa.store.create("priorityclasses",
                            PriorityClass(name=name, value=value))
        for i in range(3):
            rl = {"cpu": "4", "memory": "32Gi", "pods": "110"}
            sa.store.create("nodes", Node(name=f"n{i}", allocatable=rl,
                                          capacity=dict(rl)))
        turns = []

        def bound(prefix):
            return sum(1 for p in sa.store.list("pods")
                       if p.name.startswith(prefix) and p.node_name
                       and p.deletion_timestamp is None)

        def drive(prefix, want):
            for _ in range(8):
                sa.run_once()
                rec = sa.scheduler.last_cycle_timing
                turns.append(rec)
                if bound(prefix) >= want:
                    return
            raise AssertionError(f"{bound(prefix)}/{want} {prefix} bound")

        for i in range(12):
            sa.store.create("jobs", _job(f"low{i}", "900m", "low"))
        drive("low", 12)
        for i in range(3):
            sa.store.create("jobs", _job(f"high{i}", "3000m", "high"))
        drive("high", 3)
        return turns
    finally:
        sa.stop()


def test_turn_records_carry_every_span_and_counter(preemption):
    keys = set().union(*preemption)
    assert not set(SPANS) - keys
    assert not set(COUNTERS) - keys
    assert not set(LEGACY) - keys
    for rec in preemption:
        assert {"volcano.turn", "volcano.scheduler",
                "volcano.controllers"} <= set(rec)
        top = rec["volcano.controllers"] + rec["volcano.scheduler"] \
            + rec.get("volcano.effects", 0.0)
        assert top <= rec["volcano.turn"]
        assert rec["total_ms"] == rec["volcano.scheduler"]
        assert set(rec["self_ms"]) <= set(rec)


def test_counters_count_the_work(preemption):
    def total(key):
        return sum(rec.get(key, 0.0) for rec in preemption)

    # 12 low and 3 high pods, and one replacement per evicted low pod
    assert total("evictions_written") == 9
    assert total("pods_created") == 15 + 9
    assert total("binds_written") >= 15
    assert total("pod_wait_n") == total("binds_written")
    assert total("pod_wait_ms_sum") > 0
    assert total("solve_rounds") >= 1
    assert 0 < total("evict_claimers") <= total("evict_scan_steps")


def test_every_victim_row_is_masked_under_the_preempt_conf(preemption):
    rows = sum(rec.get("victim_rows", 0.0) for rec in preemption)
    masked = sum(rec.get("victim_rows_masked", 0.0) for rec in preemption)
    assert rows > 0 and masked == rows


def test_no_victim_row_is_masked_with_drf_deciding():
    """drf has no mask form: with it in the deciding tier each claimer's
    row takes a per-claimer call."""
    from helpers import build_node, build_pod, build_pod_group
    from volcano_tpu.actions.preempt import PreemptAction
    from volcano_tpu.cache import FakeBinder, FakeEvictor, SchedulerCache
    from volcano_tpu.client import ClusterStore
    from volcano_tpu.conf import PluginOption, Tier
    from volcano_tpu.framework import close_session, open_session
    from volcano_tpu.models import PriorityClass

    store = ClusterStore()
    cache = SchedulerCache(store)
    cache.binder, cache.evictor = FakeBinder(), FakeEvictor()
    cache.run()
    store.create("priorityclasses", PriorityClass("high", 10))
    store.create("nodes", build_node("n1", {"cpu": "2", "memory": "4Gi"}))
    high = build_pod_group("high", "c1")
    high.spec.priority_class_name = "high"
    for pg in (build_pod_group("low", "c1"), high):
        store.create("podgroups", pg)
    for name, node, phase in (("low-0", "n1", "Running"),
                              ("low-1", "n1", "Running"),
                              ("high-0", "", "Pending")):
        store.create("pods", build_pod("c1", name, node, phase, {
            "cpu": "1", "memory": "1Gi"}, name.split("-")[0]))
    ssn = open_session(cache, [Tier(plugins=[PluginOption(name=n) for n in (
        "drf", "priority", "gang", "conformance")])])
    try:
        with spans.span("t.preempt", root=True) as sp:
            PreemptAction().execute(ssn)
    finally:
        close_session(ssn)
    assert sp.record["victim_rows"] == 1.0
    assert sp.record["victim_rows_masked"] == 0.0


def test_preempt_children_cover_the_action(preemption):
    ran = [rec for rec in preemption if "volcano.preempt.solve" in rec]
    assert ran
    for rec in ran:
        children = sum(rec.get(f"volcano.preempt.{s}", 0.0) for s in (
            "collect", "flatten", "victims", "solve", "replay",
            "intra_job"))
        assert abs(rec["preempt_ms"] - children) <= max(
            0.05 * rec["preempt_ms"], 2.0), rec


def test_actions_under_a_deadline_still_add_to_the_turn_record():
    """With --action-deadline each action runs on a worker thread; its
    spans still reach the turn record."""
    from volcano_tpu.models import Node
    from volcano_tpu.standalone import Standalone

    sa = Standalone(scheduler_conf=PREEMPT_CONF, metrics_port=0,
                    async_effectors=False, period=0.0,
                    action_deadline_s=120.0)
    try:
        rl = {"cpu": "4", "memory": "32Gi", "pods": "110"}
        sa.store.create("nodes", Node(name="n0", allocatable=rl,
                                      capacity=dict(rl)))
        sa.store.create("jobs", _job("solo", "1", ""))
        turns = []
        for _ in range(4):
            sa.run_once()
            turns.append(sa.scheduler.last_cycle_timing)
    finally:
        sa.stop()
    solved = [rec for rec in turns if "volcano.allocate.replay" in rec]
    assert solved
    assert {"dispatch_ms", "readback_ms", "replay_ms",
            "volcano.bind.write"} <= set(solved[0])
