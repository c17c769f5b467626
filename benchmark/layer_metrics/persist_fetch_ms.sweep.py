"""Persist, the device's side of a dump: mean ``persist.fetch`` a trial
(its leaves sliced out of the pack, the bfloat16 cast, device to host).
Moves trials_per_hour."""
from _spans import span_seconds


def read(m):
    total, n = span_seconds(m, "persist.fetch")
    if n == 0:
        return None
    return 1000.0 * total / n
