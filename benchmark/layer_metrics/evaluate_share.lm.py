"""Device programs: the serial lane's blocked evaluation of a trial (``trial.evaluate`` spans) over the window, in percent. Moves
trials_per_hour."""

from _spans import share


def read(m):
    return share(m, "trial.evaluate")
