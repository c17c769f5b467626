"""A seconds-scale size of the ``ouro`` configurations for the CPU, as
``lm_tiny.py`` is of the ``kimi_linear`` ones: hidden 64, 4 heads of 16,
feed-forward 128, two held layers looped four times, 96 tokens a sequence.
Used by ``benchmark/tests`` and by ``tests/test_ouro_*.py``."""

import json

from lm_tiny import load_lm_cfg, template_knobs  # noqa: F401  (the same helpers)

OURO = "ouro_2_6b_pp8"


def tiny_ouro(cfg: dict, seq_len: int = 96, layers: int = 2, passes: int = 4) -> dict:
    cfg = json.loads(json.dumps(cfg))
    sizes = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
             "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": layers,
             "total_ut_steps": passes}
    cfg.update(sizes, vocab_size=256, seq_len=seq_len, train_n=8, eval_n=4)
    for k, v in sizes.items():
        cfg["knobs"][k] = {"fixed": v}
    return cfg


def load_ouro_cfg() -> dict:
    return load_lm_cfg(OURO)
