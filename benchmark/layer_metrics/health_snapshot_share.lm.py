"""Trial loop: the health plane's copy of the whole train state to the host before every epoch (``train.health_snapshot`` spans, 7.2 GB a trial here) over the window, in percent. Moves
trials_per_hour."""

from _spans import share


def read(m):
    return share(m, "train.health_snapshot")
