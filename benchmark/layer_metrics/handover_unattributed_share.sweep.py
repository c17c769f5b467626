"""Trial loop: the share of the hand-overs' seconds (handover_s.sweep) that
no childless span of the worker's thread covers: what the program's phases
leave unnamed. Moves trials_per_hour."""
from _handover import handovers, uncovered_seconds


def read(m):
    hs = handovers(m)
    total = sum(b - a for _t, a, b in hs)
    if total <= 0:
        return None
    return 100.0 * sum(uncovered_seconds(m, t, a, b) for t, a, b in hs) / total
