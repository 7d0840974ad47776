"""Task build share (%): the TaskInfos that the scheduler cache's pod
handlers built, per pod event they handled in the window's turns
(``100 * pod_task_builds / pod_events``). A pod's first add builds one;
a handler that re-places the stored task builds none. Deletes made
outside a turn are not counted."""

from lib.program import ratio


def read(run):
    return ratio(run, "pod_task_builds", "pod_events", 100.0)
