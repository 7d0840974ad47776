"""TaskInfo and JobInfo: the scheduler's view of pods and podgroups.

Reimplements reference pkg/scheduler/api/job_info.go:36-377 semantics on top
of the TPU build's Pod/PodGroup model objects (volcano_tpu.models). The
status-indexed task bookkeeping is kept because gang readiness
(Ready/Pipelined) and the snapshot flattening both read it.
"""

from __future__ import annotations

from typing import Dict, Optional

from .resource import Resource
from .types import (
    ALLOCATED_STATUSES,
    POD_GROUP_ANNOTATION,
    TaskStatus,
    allocated_status,
    next_flat_version,
)
from .unschedule_info import FitErrors


def job_key_of_pod(pod) -> str:
    """JobID for a pod: '<ns>/<group-name annotation>' (job_info.go getJobID)."""
    group = (pod.annotations or {}).get(POD_GROUP_ANNOTATION, "")
    if group:
        return f"{pod.namespace}/{group}"
    return ""


def pod_key(pod) -> str:
    return f"{pod.namespace}/{pod.name}"


def container_requests(c) -> dict:
    """A container's resource requests, accepting both the k8s pod-spec
    shape ({"resources": {"requests": ...}} — what job templates and any
    YAML-born pod carry) and the flat {"requests": ...} shorthand the
    in-process builders use. Without the nested form, template-defined
    jobs silently became best-effort."""
    r = c.get("requests")
    if r is None:
        r = (c.get("resources") or {}).get("requests")
    return r or {}


def get_pod_resource_without_init_containers(pod) -> Resource:
    r = Resource()
    for c in pod.containers:
        r.add(Resource.from_resource_list(container_requests(c)))
    return r


def get_pod_resource_request(pod) -> Resource:
    """Max(sum(containers), max(initContainers)) (k8s launch request)."""
    return _max_init_containers(
        get_pod_resource_without_init_containers(pod), pod)


def _max_init_containers(r: Resource, pod) -> Resource:
    """``r`` (the containers' sum), maxed in place with each init
    container's requests."""
    for c in pod.init_containers:
        r.set_max_resource(
            Resource.from_resource_list(container_requests(c)))
    return r


def status_of_pod(pod) -> TaskStatus:
    """Map pod phase -> TaskStatus (job_info.go getTaskStatus)."""
    phase = pod.phase
    if phase == "Running":
        return TaskStatus.RELEASING if pod.deletion_timestamp else TaskStatus.RUNNING
    if phase == "Pending":
        if pod.deletion_timestamp:
            return TaskStatus.RELEASING
        return TaskStatus.BOUND if pod.node_name else TaskStatus.PENDING
    if phase == "Unknown":
        return TaskStatus.UNKNOWN
    if phase == "Succeeded":
        return TaskStatus.SUCCEEDED
    if phase == "Failed":
        return TaskStatus.FAILED
    return TaskStatus.UNKNOWN


class TaskInfo:
    """Per-pod scheduling record (job_info.go:36-114)."""

    __slots__ = ("uid", "job", "name", "namespace", "resreq", "init_resreq",
                 "node_name", "status", "priority", "volume_ready", "pod",
                 "sig_cache", "key")

    def __init__(self, pod):
        self.uid = pod.uid
        self.job = job_key_of_pod(pod)
        self.name = pod.name
        self.namespace = pod.namespace
        self.node_name = pod.node_name or ""
        self.status = status_of_pod(pod)
        self.priority = pod.priority if pod.priority is not None else 1
        self.volume_ready = False
        self.pod = pod
        self.resreq = get_pod_resource_without_init_containers(pod)
        # the same sum, not a second pass over the containers
        self.init_resreq = _max_init_containers(self.resreq.clone(), pod)
        self.sig_cache = None  # memoized predicate signature (ops.arrays)
        # plain attribute, not a property: pod identity is immutable and
        # the replay/bind waves read key several times per task — the
        # f-string + descriptor cost was measurable at a 10k-task burst
        self.key = f"{self.namespace}/{self.name}"

    def clone(self) -> "TaskInfo":
        t = TaskInfo.__new__(TaskInfo)
        t.uid = self.uid
        t.job = self.job
        t.name = self.name
        t.namespace = self.namespace
        t.node_name = self.node_name
        t.status = self.status
        t.priority = self.priority
        t.volume_ready = self.volume_ready
        t.pod = self.pod
        # resreq/init_resreq are read-only after construction (every
        # consumer passes them as the rr side of Resource add/sub or calls
        # pure predicates), so clones share them — the snapshot clone
        # fan-out at 10k tasks is the scheduler's per-cycle host floor
        t.resreq = self.resreq
        t.init_resreq = self.init_resreq
        t.sig_cache = self.sig_cache
        t.key = self.key
        return t

    def __repr__(self) -> str:
        return (f"Task({self.namespace}/{self.name} job={self.job} "
                f"status={self.status} node={self.node_name!r})")


class JobInfo:
    """Job = PodGroup + its tasks (job_info.go:125-377)."""

    def __init__(self, uid: str, pod_group=None):
        self.uid = uid
        self.name = ""
        self.namespace = ""
        self.queue = ""
        self.priority = 0
        self.min_available = 0
        self.pod_group = None
        self.priority_class_name = ""
        self.creation_timestamp = None
        self.schedule_start_timestamp = None  # set by enqueue

        self.tasks: Dict[str, TaskInfo] = {}
        self.task_status_index: Dict[TaskStatus, Dict[str, TaskInfo]] = {}
        # bumped on any task-set/status/spec mutation; the snapshot
        # flattener's per-job block cache keys on it (ops.arrays)
        self.flat_version = 0
        self.allocated = Resource()
        self.total_request = Resource()
        # maintained sum of PENDING tasks' resreq: lets per-cycle plugin
        # opens (proportion's request attr) be O(jobs) instead of O(tasks)
        self.pending_request = Resource()
        self.nodes_fit_errors: Dict[str, FitErrors] = {}
        # Plugin-readiness bookkeeping (job controller plugins)
        self.job = None  # batch Job CR when known

        if pod_group is not None:
            self.set_pod_group(pod_group)

    # -- podgroup binding ---------------------------------------------------

    def set_pod_group(self, pg) -> None:
        self.flat_version = next_flat_version()
        self.name = pg.name
        self.namespace = pg.namespace
        self.queue = pg.spec.queue
        self.priority_class_name = pg.spec.priority_class_name or ""
        self.min_available = pg.spec.min_member
        self.creation_timestamp = pg.creation_timestamp
        self.pod_group = pg

    # -- task bookkeeping ---------------------------------------------------

    def _add_to_index(self, ti: TaskInfo) -> None:
        self.task_status_index.setdefault(ti.status, {})[ti.key] = ti

    def _remove_from_index(self, ti: TaskInfo) -> None:
        bucket = self.task_status_index.get(ti.status)
        if bucket is not None:
            bucket.pop(ti.key, None)
            if not bucket:
                del self.task_status_index[ti.status]

    def add_task_info(self, ti: TaskInfo) -> None:
        self.flat_version = next_flat_version()
        self.tasks[ti.key] = ti
        self._add_to_index(ti)
        if allocated_status(ti.status):
            self.allocated.add(ti.resreq)
        elif ti.status == TaskStatus.PENDING:
            self.pending_request.add(ti.resreq)
        self.total_request.add(ti.resreq)

    def delete_task_info(self, ti: TaskInfo) -> None:
        task = self.tasks.get(ti.key)
        if task is None:
            raise KeyError(f"failed to find task <{ti.key}> in job <{self.uid}>")
        if allocated_status(task.status):
            self.allocated.sub(task.resreq)
        elif task.status == TaskStatus.PENDING:
            self.pending_request.sub(task.resreq)
        self.total_request.sub(task.resreq)
        del self.tasks[task.key]
        self._remove_from_index(task)
        self.flat_version = next_flat_version()

    def update_task_status(self, ti: TaskInfo, status: TaskStatus) -> None:
        """Delete + reinsert keeping index/aggregates consistent
        (job_info.go:207-224). When ti IS the stored object (the hot
        replay/bind path) the reinsert collapses to an index move plus the
        allocated-aggregate delta — total_request is invariant under a
        status change, so the sub/add pair is skipped."""
        stored = self.tasks.get(ti.key)
        if stored is ti:
            old = ti.status
            was = allocated_status(old)
            self._remove_from_index(ti)
            ti.status = status
            self._add_to_index(ti)
            now = allocated_status(status)
            if was and not now:
                self.allocated.sub(ti.resreq)
            elif now and not was:
                self.allocated.add(ti.resreq)
            if old == TaskStatus.PENDING and status != TaskStatus.PENDING:
                self.pending_request.sub(ti.resreq)
            elif status == TaskStatus.PENDING and old != TaskStatus.PENDING:
                self.pending_request.add(ti.resreq)
            self.flat_version = next_flat_version()
            return
        if stored is not None:
            self.delete_task_info(ti)
        ti.status = status
        self.add_task_info(ti)

    def bulk_update_status(self, tasks, status: TaskStatus) -> None:
        """update_task_status over a whole wave in one pass: index entries
        move via bulk dict ops and the allocated/pending aggregates take one
        summed delta per distinct old status instead of a Resource op per
        task. Observable state is identical to the per-task loop; tasks that
        are not the stored objects fall back to update_task_status (after
        the stored-object part). Used by the solver replay and the batched
        bind (a 10k-pod burst pays ~68us of per-task Python through the
        scalar path, VERDICT r3).

        Atomic on failure for the stored-object part: every aggregate
        subtraction is pre-checked with the same tolerant less_equal sub()
        asserts, so a ValueError raises BEFORE any index or aggregate
        mutation — callers demote the wave to the per-task path, which has
        partial-application semantics the Statement can undo."""
        by_old: Dict[TaskStatus, list] = {}
        foreign: list = []
        for ti in tasks:
            if self.tasks.get(ti.key) is ti:
                if ti.status != status:
                    by_old.setdefault(ti.status, []).append(ti)
            else:
                foreign.append(ti)
        if by_old:
            now = allocated_status(status)
            deltas = []
            alloc_sub = []
            pending_sub = []
            for old, group in by_old.items():
                was = allocated_status(old)
                total = None
                if was != now or (old == TaskStatus.PENDING) != (
                        status == TaskStatus.PENDING):
                    total = Resource.sum_of(t.resreq for t in group)
                    if was and not now:
                        alloc_sub.append(total)
                    if old == TaskStatus.PENDING \
                            and status != TaskStatus.PENDING:
                        pending_sub.append(total)
                deltas.append((old, group, total, was))
            # pre-check the COMBINED subtraction per aggregate (groups may
            # share one) so no sub() can assert after mutation started
            if alloc_sub and not Resource.sum_of(
                    alloc_sub).less_equal(self.allocated):
                raise ValueError(
                    f"bulk status change to {status} exceeds job "
                    f"<{self.uid}> allocated aggregate")
            if pending_sub and not Resource.sum_of(
                    pending_sub).less_equal(self.pending_request):
                raise ValueError(
                    f"bulk status change to {status} exceeds job "
                    f"<{self.uid}> pending aggregate")
            new_bucket = self.task_status_index.setdefault(status, {})
            for old, group, total, was in deltas:
                bucket = self.task_status_index.get(old)
                if bucket is not None:
                    for ti in group:
                        bucket.pop(ti.key, None)
                    if not bucket:
                        del self.task_status_index[old]
                for ti in group:
                    ti.status = status
                    new_bucket[ti.key] = ti
                if total is not None:
                    if was and not now:
                        self.allocated.sub(total)
                    elif now and not was:
                        self.allocated.add(total)
                    if old == TaskStatus.PENDING \
                            and status != TaskStatus.PENDING:
                        self.pending_request.sub(total)
                    elif status == TaskStatus.PENDING \
                            and old != TaskStatus.PENDING:
                        self.pending_request.add(total)
            self.flat_version = next_flat_version()
        for ti in foreign:
            self.update_task_status(ti, status)

    # -- gang readiness -----------------------------------------------------

    def ready_task_num(self) -> int:
        """Allocated-status + succeeded + best-effort pending
        (job_info.go:317-335)."""
        occupied = 0
        for status, tasks in self.task_status_index.items():
            if allocated_status(status) or status == TaskStatus.SUCCEEDED:
                occupied += len(tasks)
            elif status == TaskStatus.PENDING:
                occupied += sum(1 for t in tasks.values()
                                if t.init_resreq.is_empty())
        return occupied

    def waiting_task_num(self) -> int:
        return len(self.task_status_index.get(TaskStatus.PIPELINED, {}))

    def valid_task_num(self) -> int:
        occupied = 0
        for status, tasks in self.task_status_index.items():
            if (allocated_status(status)
                    or status in (TaskStatus.SUCCEEDED, TaskStatus.PIPELINED,
                                  TaskStatus.PENDING)):
                occupied += len(tasks)
        return occupied

    def ready(self) -> bool:
        return self.ready_task_num() >= self.min_available

    def pipelined(self) -> bool:
        return self.waiting_task_num() + self.ready_task_num() >= self.min_available

    # -- misc ---------------------------------------------------------------

    def clone(self) -> "JobInfo":
        j = JobInfo(self.uid)
        j.name, j.namespace, j.queue = self.name, self.namespace, self.queue
        j.priority = self.priority
        j.min_available = self.min_available
        j.pod_group = self.pod_group
        j.priority_class_name = self.priority_class_name
        j.creation_timestamp = self.creation_timestamp
        j.schedule_start_timestamp = self.schedule_start_timestamp
        j.job = self.job
        # bulk form of add_task_info: the indexes are rebuilt wholesale and
        # the aggregates copied instead of re-summed per task — the snapshot
        # clone fan-out is the scheduler's per-cycle floor, so this path is
        # deliberately allocation-lean (cache.go:693-742 clones in a
        # 16-goroutine pool for the same reason)
        tasks = {k: ti.clone() for k, ti in self.tasks.items()}
        j.tasks = tasks
        index: Dict[TaskStatus, Dict[str, TaskInfo]] = {}
        for k, ti in tasks.items():
            bucket = index.get(ti.status)
            if bucket is None:
                index[ti.status] = bucket = {}
            bucket[k] = ti
        j.task_status_index = index
        j.allocated = self.allocated.clone()
        j.total_request = self.total_request.clone()
        j.pending_request = self.pending_request.clone()
        # a clone is the same logical state: carry the version so the
        # per-session snapshot clone keeps the flatten cache warm
        j.flat_version = self.flat_version
        return j

    def fit_message(self) -> str:
        reasons = {str(s): len(t) for s, t in self.task_status_index.items()}
        reasons["minAvailable"] = self.min_available
        parts = sorted(f"{v} {k}" for k, v in reasons.items())
        return f"pod group is not ready, {', '.join(parts)}."

    def __repr__(self) -> str:
        return (f"Job({self.namespace}/{self.name} queue={self.queue} "
                f"minAvailable={self.min_available} tasks={len(self.tasks)})")
