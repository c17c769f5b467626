"""Trial loop: of setup_s, the share that lies inside the program's enclosing
spans (``trial.total``, ``trial_pack.total`` and what they hold) under no leaf
span, no childless span and no ``compile.*`` or ``data.*`` record: the twin of
handover_unattributed_share.sweep for set-up (_setup.py). Moves setup_s."""
from _setup import share_of_setup


def read(m):
    return share_of_setup(m, "unattributed")
