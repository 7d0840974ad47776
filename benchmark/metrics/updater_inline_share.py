"""Updater inline share (%): the jobs whose status session close wrote in
the scheduler's own thread, of all it wrote
(``100 * updater_inline / updater_jobs``). The rest went through the job
updater's pool, which it keeps for stores reached over a wire."""

from lib.program import ratio


def read(run):
    return ratio(run, "updater_inline", "updater_jobs", 100.0)
