"""Trial loop: the set-up's seconds inside ``data.load`` (a data set generated
or decoded on a miss of the process's cache) and ``data.upload`` (its first
copy to a device), net of the compile stages inside them (_setup.py). Moves
setup_s."""
from _setup import seconds


def read(m):
    return seconds(m, "data")
