"""Device programs, the whole step: FLOPs the forward and backward passes
of the window's completed trials need (the configuration's reference counts
one image's forward; x3 for a trained image, x1 for an evaluated one), over
window x chips x the chip's bf16 peak (benchmark/peaks.py). End-to-end
utilisation: it bounds what any kernel's gain can buy. Moves
trials_per_hour."""


def read(m):
    if m["done"] == 0 or m["window_s"] <= 0 or m["peak"] is None:
        return None
    per_trial = m["forward_flops"] * (3 * m["train_images_per_trial"]
                                      + m["eval_images_per_trial"])
    return (100.0 * m["done"] * per_trial
            / (m["window_s"] * m["chips"] * m["peak"]["bf16_flops"]))
