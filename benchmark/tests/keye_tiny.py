"""A seconds-scale size of the ``keye_vl2`` configurations for the CPU, as
``lm_tiny.py`` is of the ``kimi_linear`` ones: hidden 64, 4 query heads on 2
key/value heads of 16, an indexer of 4 heads of 8 that selects 16 keys (so
that the selection bites from the 17th token on), 16 experts of which rank
0's 4 are held, top 4, two layers, 64 tokens a sequence. Used by
``benchmark/tests`` and by ``tests/test_keye_vl2.py``."""

import json

from lm_tiny import load_lm_cfg, template_knobs  # noqa: F401  (the same helpers)

KEYE = "keye_vl2_30b_a3b_ep8"


def tiny_keye(cfg: dict, seq_len: int = 64, topk: int = 16) -> dict:
    cfg = json.loads(json.dumps(cfg))
    sizes = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "moe_intermediate_size": 32, "num_hidden_layers": 2,
             "num_experts_per_tok": 4}
    cfg.update(sizes, num_experts=4, num_local_experts=4, vocab_size=256, seq_len=seq_len,
               train_n=4, eval_n=2, experts_held=[0, 1, 2, 3])
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_num_heads=4, indexer_head_dim=8,
                            topk=topk)
    knobs = dict(sizes, num_experts=16, expert_shards=4, indexer_num_heads=4,
                 indexer_head_dim=8, topk=topk, batch_size=2)
    for k, v in knobs.items():
        cfg["knobs"][k] = {"fixed": v}
    return cfg


def load_keye_cfg() -> dict:
    return load_lm_cfg(KEYE)
