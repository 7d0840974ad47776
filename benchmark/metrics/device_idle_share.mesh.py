"""Device idle share (%) of the traced window: 1 - union of device-op
intervals / window, averaged over the mesh's devices."""

from lib.layers import idle_pct


def read(run):
    return idle_pct(run)
