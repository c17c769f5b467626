"""Stacked ensemble forward: k trials, one XLA program.

Reference contrast: the reference serves k trials as k separate
processes and ensembles on the host (SURVEY.md §3.2). When the top-k
trials share an architecture (same compiled-shape signature), the
TPU-native form stacks their parameter pytrees along a leading "model"
axis and ``vmap``s the forward — one program, one launch, k logits
batches — optionally sharded across chips via a ("model",) mesh axis
so each chip holds 1/k of the ensemble (ICI gathers the outputs).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_params(params_list: Sequence[Any]):
    """Stack k identically-shaped pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def make_ensemble_forward(apply_fn, mesh: Optional[Mesh] = None):
    """Build jit'd fn: (stacked_params, batch) -> (k, B, C) probabilities.

    apply_fn: (params, batch) -> logits for ONE model.
    With a ("model",)-axis mesh, stacked params are sharded across chips
    (each chip computes its sub-ensemble) and the batch is replicated.
    """

    def fwd(stacked, batch):
        logits = jax.vmap(lambda p: apply_fn(p, batch))(stacked)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    if mesh is None:
        return jax.jit(fwd)

    # shard_map, not sharded-vmap: vmap lowers convs to grouped convs
    # whose feature_group dimension the SPMD partitioner cannot split
    # over "model". Under shard_map each chip vmaps over its local k/n
    # sub-ensemble with ordinary convs — embarrassingly parallel, no
    # collectives until the host gathers the output.
    body = jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P("model"), P()),
        out_specs=P("model"),
        check_vma=False,
    )
    return jax.jit(body)


class StackedEnsemble:
    """Serve k same-architecture trials as one vmapped program."""

    def __init__(self, apply_fn, params_list: Sequence[Any],
                 devices: Optional[Sequence] = None):
        self.k = len(params_list)
        mesh = None
        if devices is not None and len(devices) > 1:
            # The model axis must divide the ensemble across chips evenly;
            # use as many chips as divide k.
            n = max(d for d in range(1, min(len(devices), self.k) + 1) if self.k % d == 0)
            if n > 1:
                mesh = Mesh(np.asarray(list(devices)[:n]), ("model",))
        self.mesh = mesh
        self._fwd = make_ensemble_forward(apply_fn, mesh)
        stacked = stack_params(list(params_list))
        if mesh is not None:
            stacked = jax.device_put(stacked, NamedSharding(mesh, P("model")))
        elif devices:
            # One chip given: the stacked copy is committed to it, so
            # the forward runs there and not on jax's default device.
            stacked = jax.device_put(stacked, list(devices)[0])
        self._stacked = stacked

    def predict_proba(self, batch: dict) -> np.ndarray:
        """Returns (k, B, C) per-model probabilities (host array)."""
        return np.asarray(self._fwd(self._stacked, batch))

    def ensemble_proba(self, batch: dict) -> np.ndarray:
        """Mean over the model axis → (B, C), computed with the SAME
        host-side op sequence as the replicated route's ensembler
        (predictor/ensemble.py: f32 stack-mean, shared renormalize) —
        the stacked route must bit-match the host ensemble of k serial
        forwards, which is what the parity test pins."""
        from rafiki_tpu.predictor.ensemble import renormalize_probs

        probs = self.predict_proba(batch).astype(np.float32)
        return renormalize_probs(np.mean(probs, axis=0))
