"""Persist (rafiki_tpu/store/params.py, utils/serial.py): ``trial.persist``
span seconds over the window (host clock; the saver thread overlaps the
next round, so this is a share of the wall and not of the critical path).
Moves trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial.persist")
