"""Compile: the set-up's seconds inside ``compile.backend`` records: XLA's
compile on a miss of the persistent cache; the read, deserialise and load of
the executable on a hit (the records' ``retrieval_s`` is logged beside it;
_setup.py). Moves setup_s."""
from _setup import seconds


def read(m):
    return seconds(m, "backend")
