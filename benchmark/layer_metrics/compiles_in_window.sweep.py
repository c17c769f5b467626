"""Compile (ops/train.py program cache + jax's persistent cache): programs
built, backend compilations and persistent-cache misses inside the window.
Expected 0: every shape is warmed up in set-up. Moves trials_per_hour."""


def read(m):
    c = m["compiles"]
    return float(m["program_cache_misses"] + c["backend_compiles"]
                 + c["cache_misses"])
