"""Bytes shipped to the fullest shard of the sharded arena (KB per 1,000
traffic pods bound in the window): the ``shard_bytes_max`` counter, the
largest of one session's per-device deltas, summed over the window."""

from lib.program import count_per_kpod


def read(run):
    kb = count_per_kpod(run, "shard_bytes_max")
    return None if kb is None else kb / 1000.0
