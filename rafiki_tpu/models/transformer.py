"""Small text-classification transformer template.

No reference analog: the reference zoo stops at CNNs and a BiLSTM
tagger. This family exists as the zoo's first *sharded-lane* citizen
(docs/sharding.md): its knob grid reaches dimensions whose train state
outgrows one chip's HBM, and it declares a :class:`ShardPlan` via
``shard_plan`` so the sweep scheduler can route big configurations to
a chip group. Small configurations stay ordinary packable trials —
the lane choice is the plan's solved width, not the family.

TPU notes: embedding + attention + MLP matmuls run in bfloat16 on the
MXU; params stay float32. Sequences are fixed length (one XLA program
per shape bucket) with one label per sequence — `synthetic://text`
data. The embed/MLP dims are multiples of 8 so every FSDP width the
plan can pick divides them cleanly.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import (CategoricalKnob, FixedKnob, FloatKnob,
                                    IntegerKnob)


class _Encoder(nn.Module):
    vocab: int
    embed_dim: int
    num_heads: int
    num_layers: int
    num_classes: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        length = x.shape[-1]
        h = nn.Embed(self.vocab, self.embed_dim, dtype=self.dtype)(x)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (length, self.embed_dim))
        h = h + pos.astype(self.dtype)
        for _ in range(self.num_layers):
            a = nn.LayerNorm()(h).astype(self.dtype)
            a = nn.SelfAttention(num_heads=self.num_heads,
                                 dtype=self.dtype,
                                 deterministic=True)(a)
            h = h + a
            m = nn.LayerNorm()(h).astype(self.dtype)
            m = nn.Dense(4 * self.embed_dim, dtype=self.dtype)(m)
            m = nn.gelu(m)
            m = nn.Dense(self.embed_dim, dtype=self.dtype)(m)
            h = h + m
        h = nn.LayerNorm()(h)
        h = h.mean(axis=1).astype(self.dtype)  # mean pool over tokens
        return nn.Dense(self.num_classes, dtype=self.dtype)(h)


class Transformer(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "embed_dim": CategoricalKnob([32, 64, 128], affects_shape=True),
            "num_heads": CategoricalKnob([2, 4], affects_shape=True),
            "num_layers": IntegerKnob(1, 2, affects_shape=True),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([16, 32, 64], affects_shape=True),
            "epochs": IntegerKnob(1, 5),
            "seed": FixedKnob(0),
        }

    def _input_dtype(self):
        return np.int32

    def build_module(self, num_classes, input_shape):
        vocab = int(self._dataset_meta.get("vocab", 1) or 1)
        return _Encoder(
            vocab=max(vocab, 2),
            embed_dim=int(self.knobs["embed_dim"]),
            num_heads=int(self.knobs["num_heads"]),
            num_layers=int(self.knobs["num_layers"]),
            num_classes=num_classes,
        )

    def shard_plan(self, ds):
        """Solve this configuration's group width from the param tree's
        shapes alone (eval_shape — nothing is materialized). Width 1
        (the usual answer for this small grid) keeps the trial in the
        serial/packed lanes; tests pin wider groups via
        ``RAFIKI_SHARD_WIDTH``."""
        import jax

        from rafiki_tpu.shard import ShardPlan

        num_classes, input_shape = self._dataset_arch(ds)
        fns = self._loop_fns(num_classes, input_shape)
        abs_params = jax.eval_shape(fns["init_fn"], jax.random.PRNGKey(0))
        return ShardPlan.for_params(abs_params, family=type(self).__name__)


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.X`): an explicit
    # CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    test_model_class(
        Transformer, "TEXT_CLASSIFICATION",
        "synthetic://text?vocab=81&classes=5&n=512&len=16&seed=0",
        "synthetic://text?vocab=81&classes=5&n=128&len=16&seed=1",
        queries=[[5, 9, 3] * 5 + [1], [17, 2] * 8],
        knobs=dict(embed_dim=32, num_heads=2, num_layers=1,
                   learning_rate=5e-3, batch_size=32, epochs=3, seed=0),
    )
