"""Pods of the traffic's (higher-priority) jobs whose bind the watcher saw in
the window, over the window's seconds. Prefill pods bound again after an
eviction are not counted: evicting more would not raise it."""


def read(run):
    return run.binds / run.seconds if run.binds else None
