"""DenseNet-BC template for CIFAR-10-class images.

Reference analog: examples/models/image_classification/PyDenseNet.py
(unverified — a torch DenseNet on CIFAR-10).

TPU-first notes: dense blocks are concat-heavy; XLA fuses the concats
and the 1x1 bottleneck convs keep channel counts MXU-friendly.
GroupNorm replaces BatchNorm (see vgg.py rationale). Knobs expose the
classic (depth, growth rate) DenseNet-BC axes.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax.numpy as jnp

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob


class _DenseLayer(nn.Module):
    growth: int
    dtype: object

    @nn.compact
    def __call__(self, x):
        h = nn.GroupNorm(num_groups=math.gcd(8, x.shape[-1]), dtype=self.dtype)(x)
        h = nn.relu(h)
        h = nn.Conv(4 * self.growth, (1, 1), dtype=self.dtype, use_bias=False)(h)
        h = nn.GroupNorm(num_groups=math.gcd(8, h.shape[-1]), dtype=self.dtype)(h)
        h = nn.relu(h)
        h = nn.Conv(self.growth, (3, 3), padding="SAME", dtype=self.dtype, use_bias=False)(h)
        return jnp.concatenate([x, h], axis=-1)


class _Transition(nn.Module):
    out_ch: int
    dtype: object

    @nn.compact
    def __call__(self, x):
        x = nn.GroupNorm(num_groups=math.gcd(8, x.shape[-1]), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Conv(self.out_ch, (1, 1), dtype=self.dtype, use_bias=False)(x)
        if min(x.shape[1], x.shape[2]) >= 2:
            x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        return x


class _DenseNet(nn.Module):
    depth: int       # total conv layers; (depth-4) % 3 == 0 for 3 blocks
    growth: int
    num_classes: int
    reduction: float = 0.5
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        n = (self.depth - 4) // 6  # bottleneck layers per block (each = 2 convs)
        ch = 2 * self.growth
        x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype, use_bias=False)(x)
        for block in range(3):
            for _ in range(max(1, n)):
                x = _DenseLayer(self.growth, self.dtype)(x)
            if block < 2:
                out_ch = max(8, int(x.shape[-1] * self.reduction))
                x = _Transition(out_ch, self.dtype)(x)
        x = nn.GroupNorm(num_groups=math.gcd(8, x.shape[-1]), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2))  # global average pool
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)


class DenseNet(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "depth": CategoricalKnob([22, 40, 58], affects_shape=True),
            "growth": CategoricalKnob([12, 24], affects_shape=True),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128], affects_shape=True),
            "epochs": IntegerKnob(1, 10),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _DenseNet(
            depth=int(self.knobs["depth"]),
            growth=int(self.knobs["growth"]),
            num_classes=num_classes,
        )

if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.X`): an explicit
    # CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    test_model_class(
        DenseNet, "IMAGE_CLASSIFICATION",
        "synthetic://images?classes=10&n=1024&w=32&h=32&c=3&seed=0",
        "synthetic://images?classes=10&n=256&w=32&h=32&c=3&seed=1",
        knobs=dict(depth=22, growth=12, learning_rate=3e-3, batch_size=64,
                   epochs=2, seed=0),
    )
