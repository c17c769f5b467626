"""Trial loop (rafiki_tpu/worker/train.py): the hand-over between two pack
rounds, mean seconds from the end of a round's ``trial_pack.evaluate`` to
the start of the next ``train.packed_epoch`` on the worker's thread (the
spans' monotonic stamps). The device has nothing to train in it. Moves
trials_per_hour."""
from _handover import handovers


def read(m):
    hs = handovers(m)
    if not hs:
        return None
    return sum(b - a for _t, a, b in hs) / len(hs)
