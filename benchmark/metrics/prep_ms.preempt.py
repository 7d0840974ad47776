"""Flatten + order (ms per 1,000 traffic pods bound in the window): flatten_ms
+ order_ms."""

from lib.layers import prep, per_kpod


def read(run):
    return per_kpod(run, prep)
