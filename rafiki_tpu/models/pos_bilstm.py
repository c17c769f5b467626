"""BiLSTM POS tagger template.

Reference analog: examples/models/pos_tagging/PyBiLstm.py (unverified)
— a torch embedding + BiLSTM + per-token classifier.

TPU notes: flax ``nn.RNN`` lowers the recurrence to ``lax.scan`` — a
single compiled loop, no per-step Python. Sequences are fixed-length
(L static) with -1-masked labels, so one XLA program serves every
batch. Embedding + projection matmuls run in bfloat16 on the MXU.
"""

from __future__ import annotations

from typing import Any, List

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob, IntegerKnob


class _BiLstmTagger(nn.Module):
    vocab: int
    embed_dim: int
    hidden: int
    num_tags: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Embed(self.vocab, self.embed_dim, dtype=self.dtype)(x)
        h = nn.Bidirectional(
            nn.RNN(nn.LSTMCell(self.hidden)),
            nn.RNN(nn.LSTMCell(self.hidden)),
        )(h)
        return nn.Dense(self.num_tags, dtype=self.dtype)(h.astype(self.dtype))


class PosBiLstm(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "embed_dim": CategoricalKnob([32, 64, 128], affects_shape=True),
            "hidden": CategoricalKnob([32, 64, 128], affects_shape=True),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([16, 32, 64], affects_shape=True),
            "epochs": IntegerKnob(1, 10),
            "seed": FixedKnob(0),
        }

    def _input_dtype(self):
        return np.int32

    def build_module(self, num_classes, input_shape):
        vocab = int(self._dataset_meta.get("vocab", 1) or 1)
        return _BiLstmTagger(
            vocab=max(vocab, 2),
            embed_dim=int(self.knobs["embed_dim"]),
            hidden=int(self.knobs["hidden"]),
            num_tags=num_classes,
        )

    def predict(self, queries: List[Any]) -> List[List[int]]:
        """queries: list of variable-length token-id sequences →
        per-token tag ids (argmax over the tag distribution)."""
        if self._loop is None:
            raise RuntimeError("Model has no parameters: call train() or load_parameters() first")
        _, (length,) = self._arch
        out: List[List[int]] = []
        x = np.zeros((len(queries), length), dtype=np.int32)
        lens = []
        for i, q in enumerate(queries):
            toks = np.asarray(q, dtype=np.int32)[:length]
            x[i, : len(toks)] = toks
            lens.append(len(toks))
        probs = self._loop.predict_proba(x, self.batch_size)  # (N, L, tags)
        for i, n in enumerate(lens):
            out.append(np.argmax(probs[i, :n], axis=-1).astype(int).tolist())
        return out


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.X`): an explicit
    # CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    test_model_class(
        PosBiLstm, "POS_TAGGING",
        "synthetic://corpus?vocab=100&tags=8&n=256&len=16&seed=0",
        "synthetic://corpus?vocab=100&tags=8&n=64&len=16&seed=1",
        queries=[[5, 9, 3], [17, 2]],
        knobs=dict(embed_dim=32, hidden=32, learning_rate=5e-3, batch_size=32,
                   epochs=3, seed=0),
    )
