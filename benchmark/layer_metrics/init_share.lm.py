"""Trial loop: the window's ``train.init`` spans (a serial trial's
initialisation as the host spends it: the init program traced, found and
enqueued, the state placed) over the window, in percent. Moves
trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "train.init")
