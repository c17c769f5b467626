"""A seconds-scale size of the ``kimi_linear`` configurations for the CPU:
hidden 64, 4 heads of 16, 16 experts of which 4 are held, 5 layers (dense
first, then KDA, KDA, MLA, KDA), 96 tokens a sequence, so that several KDA
chunks and a ragged last one occur. Used by ``benchmark/tests`` and by
``tests/test_kimi_linear.py``."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def tiny_lm(cfg: dict, chunk: int = 16, seq_len: int = 96) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(hidden_size=64, num_attention_heads=4, intermediate_size=128,
               moe_intermediate_size=32, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
               num_experts_per_token=4, vocab_size=256, seq_len=seq_len,
               train_n=8, eval_n=4, experts_held=[0, 1, 2, 3])
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], head_dim=16,
                                     num_heads=4)
    fixed = {"hidden_size": 64, "num_heads": 4, "kda_head_dim": 16,
             "kda_chunk": chunk, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "kv_lora_rank": 32, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts": 16,
             "num_experts_per_token": 4, "expert_shards": 4}
    for k, v in fixed.items():
        cfg["knobs"][k] = {"fixed": v}
    return cfg


def load_lm_cfg(name: str = "kimi_linear_48b_a3b_ep32") -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def template_knobs(cfg: dict, seed: int = 0, **free) -> dict:
    """Knobs of one trial of the configuration's template."""
    knobs = {k: v["fixed"] for k, v in cfg["knobs"].items() if "fixed" in v}
    knobs["seed"] = seed
    knobs.update(learning_rate=1e-3, label_smoothing=0.05)
    knobs.update(free)
    return knobs
