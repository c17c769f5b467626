"""Device programs (rafiki_tpu/ops/train.py), packed evaluation: share of
the window inside ``trial_pack.evaluate`` spans (host clock around work
that ends in a device fetch). Moves trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial_pack.evaluate")
