"""Process start to window start: imports, cluster build, prefill, warm-up
(lead-in)."""


def read(run):
    return run.setup_s
