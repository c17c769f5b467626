"""Trial loop (rafiki_tpu/worker/train.py): share of the window inside
``trial_pack.build`` spans (host clock). Moves trials_per_hour."""
from _spans import share


def read(m):
    return share(m, "trial_pack.build")
