"""Persist, the host's side of a dump: ``persist.write`` (pickling, the
params store's save: two spans a trial) plus ``persist.mark`` (the trial's
row, its checkpoints) seconds over the trials marked. Moves
trials_per_hour."""
from _spans import span_seconds


def read(m):
    write, _n = span_seconds(m, "persist.write")
    mark, trials = span_seconds(m, "persist.mark")
    if trials == 0:
        return None
    return 1000.0 * (write + mark) / trials
