"""Pods of the traffic's jobs whose bind the watcher saw in the window, over
the window's seconds."""


def read(run):
    return run.binds / run.seconds if run.binds else None
