"""Device programs, the whole step, of a language-model sweep: FLOPs the
forward and backward passes of the window's completed trials need (the
reference's count of one token's forward, ``references/kimi_linear.py``: x3
a trained token, x1 an evaluated one; recomputation not counted) over
window x chips x the chip's bf16 peak (benchmark/peaks.py). The whole-step
share that bounds what any kernel's gain can buy. Moves trials_per_hour."""


def read(m):
    if m["done"] == 0 or m["window_s"] <= 0 or m.get("peak") is None:
        return None
    per_trial = m["forward_flops"] * (3 * m["train_tokens_per_trial"]
                                      + m["eval_tokens_per_trial"])
    return (100.0 * m["done"] * per_trial
            / (m["window_s"] * m["chips"] * m["peak"]["bf16_flops"]))
