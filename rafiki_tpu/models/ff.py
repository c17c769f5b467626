"""FeedForward MLP template (reference analog: examples/models/
image_classification/TfFeedForward.py, unverified — an MLP over
flattened images with knobs for hidden layer count/units, log-scale
learning rate, batch size, epochs).

TPU notes: dense layers map straight onto the MXU; compute in bfloat16,
params float32. ``hidden_units``/``hidden_layers`` affect shapes →
flagged ``affects_shape`` so the scheduler can bucket trials by
compiled-program signature.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob


class _Mlp(nn.Module):
    hidden_layers: int
    hidden_units: int
    num_classes: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for _ in range(self.hidden_layers):
            x = nn.Dense(self.hidden_units, dtype=self.dtype)(x)
            x = nn.relu(x)
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)


class FeedForward(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "hidden_layers": IntegerKnob(1, 3, affects_shape=True),
            "hidden_units": CategoricalKnob([32, 64, 128, 256], affects_shape=True),
            "learning_rate": FloatKnob(1e-4, 1e-1, is_exp=True),
            "batch_size": CategoricalKnob([32, 64, 128], affects_shape=True),
            "epochs": IntegerKnob(1, 5),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Mlp(
            hidden_layers=int(self.knobs["hidden_layers"]),
            hidden_units=int(self.knobs["hidden_units"]),
            num_classes=num_classes,
        )


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.X`): an explicit
    # CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.model.dataset import synthetic_images

    test_model_class(
        FeedForward,
        task="IMAGE_CLASSIFICATION",
        train_dataset_uri="synthetic://images?classes=10&n=2048&seed=0",
        test_dataset_uri="synthetic://images?classes=10&n=512&seed=1",
        queries=[synthetic_images(n=4, seed=2).x[i] for i in range(4)],
    )
