"""Trial loop: share of the window inside the worker's own bookkeeping
between rounds: ``trial.log`` (a trial's log rows), ``trial.claim`` (its
store row), ``trial_pack.bucket`` (throw-away models for packing keys) and
``trial_pack.init`` (the pack's initialisation up to its first epoch).
Moves trials_per_hour."""
from _spans import span_seconds

PHASES = ("trial.log", "trial.claim", "trial_pack.bucket", "trial_pack.init")


def read(m):
    found = [span_seconds(m, name) for name in PHASES]
    if not any(n for _s, n in found) or m["window_s"] <= 0:
        return None
    return 100.0 * sum(s for s, _n in found) / m["window_s"]
