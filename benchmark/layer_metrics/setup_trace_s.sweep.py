"""Compile: the set-up's seconds inside ``compile.trace`` and ``compile.lower``
records (jax tracing a function in Python and lowering it to MLIR, which no
cache saves), each second once (nested traces by their union; _setup.py).
Moves setup_s."""
from _setup import seconds


def read(m):
    return seconds(m, "trace")
