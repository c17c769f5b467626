"""What the tests of the sparse-attention template share
(tests/test_keye_vl2_layers.py, _kernels.py, _model.py): the benchmark's
reference and tiny configuration, and tests/kimi_linear_common.py's helpers
taken for this template. ``f32`` switches this template's bfloat16 operands
to float32 too, and clears jax's caches on both sides: the selection and the
attention are jitted functions, whose traces would otherwise outlive the
switch."""

import functools

import jax
import jax.numpy as jnp
import pytest

import kimi_linear_common as common
from kimi_linear_common import (  # noqa: F401  (re-exported)
    check, close, flat, FixedKnob, interpreted, K, REPO, telemetry, tokens, value_and_grads)
from keye_tiny import load_keye_cfg, template_knobs, tiny_keye  # noqa: F401
from references import keye_vl2 as R  # noqa: F401

from rafiki_tpu.models import keye_vl2 as M  # noqa: F401

small_class = functools.partial(common.small_class, template=M.KeyeVl2)
program_of = functools.partial(common.program_of, template=M.KeyeVl2, reference=R)


@pytest.fixture
def cfg():
    return tiny_keye(load_keye_cfg())


@pytest.fixture
def f32(monkeypatch):
    """Both templates' matrix products and this one's bfloat16 operands in
    float32 at full precision."""
    def mm(a, b, spec, out=jnp.float32):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision="highest")

    monkeypatch.setattr(K, "_mm", mm)
    monkeypatch.setattr(K, "BF16", jnp.float32)
    monkeypatch.setattr(M, "BF16", jnp.float32)
    jax.clear_caches()
    with jax.default_matmul_precision("highest"):
        yield
    jax.clear_caches()


def dsa_operands(T, B=1, H=4, Hk=2, d=16, HI=2, dI=8, topk=12, seed=0):
    """Heads-major operands of the attention's kernels and a selection of
    ``topk`` keys a query from random indexer inputs: (q, k, v, mask, counts,
    qI, kI, w) as ``_dsa_fused`` takes them, and (do, dkl) cotangents."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, H, T, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hk, T, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hk, T, d)).astype(jnp.bfloat16)
    qI = jax.random.normal(ks[3], (B, T, HI, dI)).astype(jnp.bfloat16)
    kI = jax.random.normal(ks[4], (B, T, dI)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[5], (B, T, HI)) * 0.3
    mask, counts = M.dsa_select(qI, kI, w, topk)
    do = jax.random.normal(ks[6], (B, H, T, d)).astype(jnp.bfloat16)
    dkl = jax.random.normal(ks[7], (B, T))
    return (q, k, v, mask, counts, jnp.swapaxes(qI, 1, 2), kI, w), (do, dkl)
