"""Flatten + order (ms per 1,000 traffic pods bound in the window):
flatten_ms + order_ms, as ``prep_ms.burst`` reads them."""

from lib.layers import per_kpod, prep


def read(run):
    return per_kpod(run, prep)
