"""Device programs (rafiki_tpu/ops/train.py), packed train epoch: all the
window's ``trial_pack.train`` span seconds over all the optimizer steps run
in them (one step trains the whole pack; host clock around work that ends
in a device fetch). Moves trials_per_hour."""
from _spans import span_seconds


def read(m):
    total, n = span_seconds(m, "trial_pack.train")
    steps = n * m["steps_per_trial"]
    if steps == 0:
        return None
    return 1000.0 * total / steps
