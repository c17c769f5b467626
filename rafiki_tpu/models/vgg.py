"""VGG template for CIFAR-10-class images.

Reference analog: examples/models/image_classification/TfVgg16.py
(unverified — a TF1 VGG16 on CIFAR-10, knobs for lr/batch/epochs).

TPU-first re-design notes:
  * NHWC + 3x3 convs map directly onto the MXU via XLA's conv tiling;
    compute dtype bfloat16, params float32.
  * GroupNorm instead of BatchNorm: no running statistics, so the
    model stays a pure function of (params, batch) — no mutable
    collections threaded through jit — and accuracy on CIFAR-scale
    data is comparable. This is a deliberate architectural departure
    from the reference's BN.
  * ``depth`` knob selects the VGG config (11/13/16); ``width_mult``
    scales channel counts so the advisor can trade FLOPs for accuracy.
  * pooling stops once the spatial dim reaches 1, so the same template
    works on small synthetic images in tests.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax.numpy as jnp

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob

_CFGS = {
    11: [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    13: [64, 64, "M", 128, 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
    16: [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M",
         512, 512, 512, "M"],
}


class _Vgg(nn.Module):
    depth: int
    width_mult: float
    num_classes: int
    dropout: float
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False, dropout_rate=None):
        x = x.astype(self.dtype)
        for v in _CFGS[self.depth]:
            if v == "M":
                if min(x.shape[1], x.shape[2]) >= 2:
                    x = nn.max_pool(x, (2, 2), strides=(2, 2))
                continue
            ch = max(8, int(v * self.width_mult))
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype, use_bias=False)(x)
            x = nn.GroupNorm(num_groups=math.gcd(8, ch), dtype=self.dtype)(x)
            x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(max(64, int(512 * self.width_mult)), dtype=self.dtype)(x)
        x = nn.relu(x)
        # dropout_rate may be a TRACED scalar (rafiki_tpu.ops.dropout),
        # so a dropout sweep shares one compiled program; falls back to
        # the static attribute when called without one.
        if train:
            from rafiki_tpu.ops.train import dropout as _dropout

            rate = self.dropout if dropout_rate is None else dropout_rate
            x = _dropout(x, rate, self.make_rng("dropout"), deterministic=False)
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)


class Vgg(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "depth": CategoricalKnob([11, 13, 16], affects_shape=True),
            "width_mult": CategoricalKnob([0.25, 0.5, 1.0], affects_shape=True),
            "dropout": FloatKnob(0.0, 0.5),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128, 256], affects_shape=True),
            "epochs": IntegerKnob(1, 10),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Vgg(
            depth=int(self.knobs["depth"]),
            width_mult=float(self.knobs["width_mult"]),
            num_classes=num_classes,
            dropout=float(self.knobs["dropout"]),
        )

if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.X`): an explicit
    # CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    test_model_class(
        Vgg, "IMAGE_CLASSIFICATION",
        "synthetic://images?classes=10&n=1024&w=32&h=32&c=3&seed=0",
        "synthetic://images?classes=10&n=256&w=32&h=32&c=3&seed=1",
        knobs=dict(depth=11, width_mult=0.25, dropout=0.1, learning_rate=1e-3,
                   batch_size=64, epochs=4, seed=0),
    )
