"""A seconds-scale size of the ``lfm2_moe`` configurations for the CPU, as
``lm_tiny.py`` is of the ``kimi_linear`` ones: hidden 64, 4 query heads on 2
key/value heads of 16, 16 experts of which rank 0's 4 are held, top 4, the
published six layers (conv, conv, attention, conv, conv, conv; two dense), 96
tokens a sequence. Used by ``benchmark/tests`` and by
``tests/test_lfm2_moe_*.py``."""

import json

from lm_tiny import load_lm_cfg, template_knobs  # noqa: F401  (the same helpers)

LFM2 = "lfm2_8b_a1b_ep4"


def tiny_lfm2(cfg: dict, seq_len: int = 96) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, moe_intermediate_size=32, num_experts=4,
               vocab_size=256, seq_len=seq_len, train_n=8, eval_n=4,
               experts_held=[0, 1, 2, 3])
    cfg["published"] = dict(cfg["published"], num_experts=16)
    fixed = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 16}
    for k, v in fixed.items():
        cfg["knobs"][k] = {"fixed": v}
    return cfg


def load_lfm2_cfg() -> dict:
    return load_lm_cfg(LFM2)
