"""The reference and the traffic generator, on hand-made inputs (no JAX).

Run: ``python -m pytest benchmark/tests -q``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from lib import reference as ref  # noqa: E402
from lib import traffic as gen  # noqa: E402

NODES = {"n0": (4.0, 8.0 * 2 ** 30, 110.0), "n1": (4.0, 8.0 * 2 ** 30, 110.0)}


def facts(min_available=2, cpu=1.0, prio=0, measured=True):
    return ref.JobFacts(min_available, cpu, 2.0 ** 30, prio, measured)


def bind(name, node, t=0.0):
    return (ref.BIND, name, node, t)


def add(name, t=0.0):
    return (ref.ADD, name, "", t)


def turn(t=0.0):
    return (ref.TURN, t)


def test_sound_log_reads_zero_everywhere():
    jobs = {"a": facts(2)}
    log = [add("a-task-0"), add("a-task-1"), bind("a-task-0", "n0"),
           bind("a-task-1", "n1"), turn()]
    out = ref.check(NODES, jobs, log)
    assert out == {"over_capacity": 0, "gang_partial": 0, "double_bind": 0,
                   "evict_priority": 0, "over_evicted": 0,
                   "never_started": 0}


def test_over_capacity_counts_each_overflowing_bind():
    jobs = {"a": facts(1, cpu=3.0)}
    log = [add(f"a-task-{i}") for i in range(3)] + [
        bind(f"a-task-{i}", "n0") for i in range(3)] + [turn()]
    assert ref.check(NODES, jobs, log)["over_capacity"] == 2


def test_unknown_node_is_over_capacity():
    log = [add("a-task-0"), bind("a-task-0", "nx"), turn()]
    assert ref.check(NODES, {"a": facts(1)}, log)["over_capacity"] == 1


def test_deletion_frees_room():
    jobs = {"a": facts(1, cpu=4.0), "b": facts(1, cpu=4.0)}
    log = [add("a-task-0"), bind("a-task-0", "n0"),
           (ref.DELETE, "a-task-0", "", 0.0),
           add("b-task-0"), bind("b-task-0", "n0"), turn()]
    assert ref.check(NODES, jobs, log)["over_capacity"] == 0


def test_gang_partial_at_turn_end_only():
    jobs = {"a": facts(2)}
    mid = [add("a-task-0"), add("a-task-1"), bind("a-task-0", "n0"),
           turn(), bind("a-task-1", "n0"), turn()]
    assert ref.check(NODES, jobs, mid)["gang_partial"] == 1
    whole = [add("a-task-0"), add("a-task-1"), bind("a-task-0", "n0"),
             bind("a-task-1", "n0"), turn()]
    assert ref.check(NODES, jobs, whole)["gang_partial"] == 0


def test_deleted_job_is_not_a_partial_gang():
    jobs = {"a": facts(2, measured=False)}
    log = [add("a-task-0"), add("a-task-1"), bind("a-task-0", "n0"),
           bind("a-task-1", "n0"), turn(), (ref.JOBDEL, "a"),
           (ref.DELETE, "a-task-0", "", 0.0), turn()]
    assert ref.check(NODES, jobs, log)["gang_partial"] == 0


def test_double_bind():
    log = [add("a-task-0"), bind("a-task-0", "n0"), bind("a-task-0", "n1"),
           turn()]
    assert ref.check(NODES, {"a": facts(1)}, log)["double_bind"] == 1


def test_eviction_needs_a_higher_priority_waiter():
    jobs = {"lo": facts(1, prio=1, measured=False),
            "hi": facts(1, prio=100)}
    evict = [add("lo-task-0"), bind("lo-task-0", "n0"), add("hi-task-0"),
             (ref.MARK, "lo-task-0", "n0", 0.0),
             (ref.DELETE, "lo-task-0", "", 0.0),
             bind("hi-task-0", "n0"), turn()]
    assert ref.check(NODES, jobs, evict)["evict_priority"] == 0
    # the same eviction with nobody of higher priority waiting
    no_waiter = [add("lo-task-0"), bind("lo-task-0", "n0"),
                 (ref.MARK, "lo-task-0", "n0", 0.0), turn()]
    assert ref.check(NODES, jobs, no_waiter)["evict_priority"] == 1


def test_never_started_counts_measured_jobs_only():
    jobs = {"a": facts(2), "b": facts(2, measured=False)}
    log = [add("a-task-0"), add("a-task-1"), bind("a-task-0", "n0"), turn()]
    out = ref.check(NODES, jobs, log)
    assert out["never_started"] == 1


def _preempt_log(victims):
    """n0 (4 cpu) holds four low pods of 0.9 cpu; ``victims`` of them are
    evicted and deleted, and a high pod of 3 cpu binds there."""
    log = []
    for i in range(4):
        log += [add(f"lo{i}-task-0"), bind(f"lo{i}-task-0", "n0")]
    log += [turn(), add("hi-task-0")]
    for i in range(victims):
        log += [(ref.MARK, f"lo{i}-task-0", "n0", 0.0),
                (ref.DELETE, f"lo{i}-task-0", "", 0.0)]
    return log + [turn(), bind("hi-task-0", "n0"), turn()]


def _preempt_jobs():
    jobs = {f"lo{i}": ref.JobFacts(1, 0.9, 2.0 ** 29, 0, False)
            for i in range(4)}
    jobs["hi"] = ref.JobFacts(1, 3.0, 2.0 ** 29, 10, True)
    return jobs


@pytest.mark.parametrize("victims,over", [(3, 0), (4, 1)])
def test_over_eviction_needs_a_victim_to_spare(victims, over):
    out = ref.check(NODES, _preempt_jobs(), _preempt_log(victims))
    assert out["over_evicted"] == over
    assert out["over_capacity"] == 0 and out["evict_priority"] == 0


def test_backlog_rebind_is_not_a_preemptor():
    # the last victims' room taken back by an evicted low pod's re-created
    # twin, of the victims' own priority: nothing was over-evicted for it
    jobs = _preempt_jobs()
    log = [add(f"lo{i}-task-0") for i in range(4)] + [
        bind(f"lo{i}-task-0", "n0") for i in range(4)] + [
        turn(), add("hi-task-0"), (ref.MARK, "lo0-task-0", "n0", 0.0),
        (ref.DELETE, "lo0-task-0", "", 0.0), add("lo0-task-0"),
        bind("lo0-task-0", "n0"), turn()]
    assert ref.check(NODES, jobs, log)["over_evicted"] == 0


def test_quantities():
    assert ref.parse_quantity("250m") == 0.25
    assert ref.parse_quantity("4Gi") == 4 * 2 ** 30
    assert ref.parse_quantity("2") == 2.0


def test_verdict_fails_on_any_count():
    ok, rows = ref.verdict({k: 0 for k in ref.limits()})
    assert ok and all(r["limit"] == 0 for r in rows)
    bad = dict({k: 0 for k in ref.limits()}, over_capacity=1)
    assert not ref.verdict(bad)[0]


def test_preempted_job_below_its_minimum_is_partial():
    jobs = {"lo": facts(2, prio=1, measured=False), "hi": facts(1, prio=100)}
    log = [add("lo-task-0"), add("lo-task-1"), bind("lo-task-0", "n0"),
           bind("lo-task-1", "n1"), turn(), add("hi-task-0"),
           (ref.MARK, "lo-task-0", "n0", 0.0), turn()]
    assert ref.check(NODES, jobs, log)["gang_partial"] == 1


# -- the generator ----------------------------------------------------------

PODS = {"a": {"cpu": "100m", "memory": "500Mi"},
        "b": {"cpu": "3", "memory": "1Gi", "priority_class": "high"}}
MIX = {"wave_jobs": 6, "queues": ["q0"],
       "jobs": {"sizes": [[10, 1]], "pods": [["a", 1], ["b", 1]]}}


def test_waves_are_independent_of_history():
    w = gen.wave(MIX, 3, 4, PODS)
    assert [j.name for j in w][:2] == ["w4-0", "w4-1"]
    assert sorted(j.cpu for j in w) == ["100m"] * 3 + ["3"] * 3
    assert [j.cpu for j in w] == [j.cpu for j in gen.wave(MIX, 3, 4, PODS)]
    assert {j.priority_class for j in w if j.cpu == "3"} == {"high"}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7, 9_999_999_999])
def test_every_seed_gets_the_same_work(seed):
    def key(js):
        return sorted((j.size, j.cpu, j.memory, j.min_available) for j in js)

    base, other = gen.wave(MIX, 0, 0, PODS), gen.wave(MIX, seed, 0, PODS)
    assert key(base) == key(other)


def test_prefill_count_and_min():
    spec = {"count": 5, "jobs": {"sizes": [[4, 1]], "pods": [["a", 1]],
                                 "min": 2}}
    jobs = gen.prefill_jobs(spec, 1, "f", PODS)
    assert len(jobs) == 5 and {j.min_available for j in jobs} == {2}
