"""Trial loop: the set-up's seconds inside ``train.init`` (a serial trial's
initialisation, the host's side) and ``trial_pack.init`` (a pack's), net of the
``compile.*`` and ``data.*`` records inside them (_setup.py). Moves setup_s."""
from _setup import seconds


def read(m):
    return seconds(m, "init")
