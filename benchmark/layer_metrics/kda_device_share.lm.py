"""Device programs: of the device time of the epoch program's operations in
the traced span, the share that ran under the KDA mixers (``kda``: projections, convolutions, gates, the chunked delta rule),
forward and backward: the trace's operations inside the epoch program's
module joined with the compiled program's scopes (drivers/lm_sweep.py).
Moves trials_per_hour."""

from _scopes import scope_share


def read(m):
    return scope_share(m, "kda")
