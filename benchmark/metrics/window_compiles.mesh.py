"""XLA compiles (persistent-cache loads included) inside the window; should
read 0."""


def read(run):
    return float(run.compiles)
