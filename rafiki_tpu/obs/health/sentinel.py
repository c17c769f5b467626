"""In-graph numerics sentinels: the traced half of the health plane.

Everything here runs INSIDE the jitted, donated epoch programs
(``ops.train.Program.train_epoch`` and the vmapped packed variant):
:func:`bundle` folds a cheap health reduction into every train step's
metric dict, and :func:`reduce_epoch` collapses the per-step series to
one fixed set of epoch-boundary scalars — the only values that ever
cross to the host, and only once per epoch.

Design constraints (docs/health.md):

* **Bit-neutrality.** The bundle only *reads* loss/grads/updates/params;
  it never touches the rng chain or the update math, so params with the
  sentinel enabled are bit-identical to params without it — and a packed
  member stays bit-identical to its serial twin.
* **Always on.** The bundle is unconditionally part of the trace, so a
  program's cache key is unchanged and every cached program carries the
  same metric structure (no health-on/health-off retrace forks).
* **No per-step host sync.** All outputs are device scalars reduced by
  the same ``lax.scan`` that runs the epoch; the host fetches the
  reduced dict at the epoch boundary it already syncs on.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Metric-dict key prefix for sentinel outputs. ``ops.train`` strips
#: these from caller-visible epoch metrics (the JaxModel/logger contract
#: predates the health plane) and routes them to the HealthMonitor.
PREFIX = "health_"


def _sq_sum(tree: Any) -> jax.Array:
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(tree):
        f = leaf.astype(jnp.float32)
        total = total + jnp.sum(f * f)
    return total


def _nonfinite(tree: Any) -> jax.Array:
    total = jnp.zeros((), jnp.int32)
    for leaf in jax.tree.leaves(tree):
        total = total + jnp.sum(~jnp.isfinite(leaf)).astype(jnp.int32)
    return total


def bundle(loss: jax.Array, grads: Any, updates: Any,
           params: Any) -> Dict[str, jax.Array]:
    """Per-step health stats as one fused reduction over the step's
    already-materialized intermediates: global grad/update/param
    L2 norms (f32 accumulation regardless of leaf dtype) and the count
    of non-finite elements across the gradients and the loss."""
    return {
        "health_grad_norm": jnp.sqrt(_sq_sum(grads)),
        "health_update_norm": jnp.sqrt(_sq_sum(updates)),
        "health_param_norm": jnp.sqrt(_sq_sum(params)),
        "health_nonfinite": (_nonfinite(grads)
                             + jnp.sum(~jnp.isfinite(loss)).astype(jnp.int32)),
    }


def split(metrics: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Partition a metric dict into (caller-visible, health) halves."""
    rest = {k: v for k, v in metrics.items() if not k.startswith(PREFIX)}
    health = {k: v for k, v in metrics.items() if k.startswith(PREFIX)}
    return rest, health


def reduce_epoch(series: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Epoch-boundary reduction of the per-step sentinel series.

    Handles both the serial shape ``(n_steps,)`` and the packed shape
    ``(n_steps, k)`` — the dispatch is on static ndim, never a traced
    branch. Outputs, per trial:

    * ``health_nonfinite``   — total non-finite elements this epoch
    * ``health_grad_norm``   — max step grad norm (NaN-propagating)
    * ``health_update_norm`` — max step update norm
    * ``health_param_norm``  — post-update param norm at the last step
    * ``health_bad_step``    — first step with non-finite numerics, -1
      if the epoch was clean
    * ``health_bad_*``       — grad/update norm and non-finite count AT
      the first bad step (step 0 when clean; ignore when bad_step < 0).
      These are the bit-reproduction surface ``obs replay`` verifies.
    """
    nf = series["health_nonfinite"]
    # A series already on the host (the step-by-step serial path fetches
    # its steps' scalars once) is reduced there, by numpy: no device program
    # whose shape is the number of steps.
    xp = np if isinstance(nf, np.ndarray) else jnp
    bad = nf > 0
    any_bad = bad.any(axis=0)
    at = xp.argmax(bad, axis=0).astype(xp.int32)  # 0 when clean
    first_bad = xp.where(any_bad, at, xp.int32(-1))

    def _at_bad(v):
        if v.ndim == 1:
            return v[at]
        return xp.take_along_axis(v, at[None, :], axis=0)[0]

    gn = series["health_grad_norm"]
    un = series["health_update_norm"]
    return {
        "health_nonfinite": nf.sum(axis=0),
        "health_grad_norm": gn.max(axis=0),
        "health_update_norm": un.max(axis=0),
        "health_param_norm": series["health_param_norm"][-1],
        "health_bad_step": first_bad,
        "health_bad_grad_norm": _at_bad(gn),
        "health_bad_update_norm": _at_bad(un),
        "health_bad_nonfinite": _at_bad(nf),
    }
