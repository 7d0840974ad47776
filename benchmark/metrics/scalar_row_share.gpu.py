"""Scalar row share (%): of the task rows the flatten built in the
window's turns (``task_rows``), those whose requests name a scalar
resource such as ``nvidia.com/gpu`` and so took the scalar overlay
(``scalar_task_rows``)."""

from lib.program import ratio


def read(run):
    return ratio(run, "scalar_task_rows", "task_rows", 100.0)
