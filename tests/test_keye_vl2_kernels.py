"""The sparse attention's three Pallas kernels (``dsa_fwd``, ``dsa_dq``,
``dsa_dkv`` in rafiki_tpu/models/keye_vl2.py), run by Pallas' interpreter on
the CPU, against the blocked ``jax.numpy`` path: the output, every head's
and the indexer's log-sum-exp and the indexer's loss forward, and all six
gradients backward, at tilings where a tile holds the whole sequence and
where key tiles above the diagonal and tiles with no selected key are
skipped; with every key selected, the same attention as dense causal
softmax; which path runs is read from the lowering; and a layer whose
``nn.remat`` keeps the forward's output and log-sum-exps has the gradients
of one that runs the forward again. Shared fixtures:
tests/keye_vl2_common.py."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keye_vl2_common import close, dsa_operands, interpreted, K, M  # noqa: F401 (fixtures)

TILINGS = {"one_tile": (64, 64, 64), "four_by_two_tiles": (64, 16, 32),
           "sixteen_by_eight_tiles": (128, 8, 16)}


@pytest.mark.parametrize("tiling", list(TILINGS))
def test_the_kernels_match_the_blocked_path_forward_and_backward(tiling, monkeypatch,
                                                                  interpreted):
    T, tq, tk = TILINGS[tiling]
    monkeypatch.setattr(M, "DSA_QUERY_TILE", tq)
    monkeypatch.setattr(M, "DSA_KEY_TILE", tk)
    monkeypatch.setattr(M, "SELECT_BLOCK", 16)
    args, (do, dkl) = dsa_operands(T)
    q, k, v, mask, counts, qI, kI, w = args
    table = (counts > 0).astype(jnp.int32)
    if tiling == "sixteen_by_eight_tiles":
        assert int((counts == 0).sum()) > int((counts.shape[1] * (counts.shape[2] - 1)) // 2)
    o1, kl1, lse1, lse_i1 = M._dsa_blocked(q, k, v, mask, qI, kI, w)
    o2, kl2, lse2, lse_i2 = M._dsa_kernel_forward(q, k, v, mask, table, qI, kI, w,
                                                  interpret=True)
    assert close(o2, o1, 1e-2) and close(kl2, kl1, 1e-5)
    assert close(lse2[..., 0], lse1, 1e-5) and close(lse_i2[..., 0], lse_i1, 1e-5)
    # lane 0 is what the forward hands on, and widened again it is every lane
    kept = M._dsa_forward(q, k, v, mask, table, qI, kI, w, True)
    np.testing.assert_array_equal(M._lanes(kept[2]), lse2)
    np.testing.assert_array_equal(M._lanes(kept[3]), lse_i2)

    def grads(interpret):
        def f(q, k, v, qI, kI, w):
            o, kl, _ = M._dsa_fused(q, k, v, mask, table, qI, kI, w, interpret)
            return (jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))
                    + jnp.sum(kl * dkl))
        return jax.grad(f, argnums=tuple(range(6)))(q, k, v, qI, kI, w)

    for name, a, b in zip(("dq", "dk", "dv", "dqI", "dkI", "dw"), grads(True), grads(False)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        # bfloat16 operands of the products on both sides, summed in other orders
        assert close(a, b, 2e-2), name


def test_with_every_key_selected_it_is_dense_causal_attention():
    """``dsa_attention`` with ``topk`` at the length selects every key at or
    before a query: the first template's causal attention on repeated heads."""
    T, H, Hk, d = 64, 4, 2, 16
    args, _ct = dsa_operands(T, H=H, Hk=Hk, d=d, topk=T)
    q, k, v, mask, counts, qI, kI, w = args
    rows = lambda x: jnp.swapaxes(x, 1, 2)
    o, kl, fused = M.dsa_attention(rows(q) * np.sqrt(d), rows(k), rows(v), mask, counts,
                                   rows(qI), kI, w)
    want = K._blocked_attention(rows(q) * np.sqrt(d), *(jnp.repeat(rows(x), H // Hk, axis=2)
                                                      for x in (k, v)))
    assert float(fused) == 0.0 and o.shape == (1, T, H, d)
    assert close(o, want, 2e-2)
    assert np.all(np.asarray(kl) > -1e-5)          # a divergence


@pytest.mark.parametrize("T", [512, 448], ids=["a_length_the_key_tile_divides", "one_it_does_not"])
def test_which_path_runs_is_read_from_the_lowering(T):
    """At heads of 128 and 64 and a length the key tile divides, both paths
    are staged and the CPU's lowering takes the blocked code (flag 0); the
    same call lowered for a TPU holds the kernels. Elsewhere only the blocked
    code exists."""
    args, _ct = dsa_operands(T, H=2, Hk=1, d=128, HI=2, dI=64, topk=64)
    q, k, v, mask, counts, qI, kI, w = args
    rows = lambda x: jnp.swapaxes(x, 1, 2)
    call = (rows(q), rows(k), rows(v), mask, counts, rows(qI), kI, w)
    staged = str(jax.make_jaxpr(M.dsa_attention)(*call))
    assert ("platform_index" in staged) == ("pallas_call" in staged) == (T % 512 == 0)
    assert "tpu_custom_call" not in M.dsa_attention.lower(*call).as_text()
    if T % 512 == 0:
        text = M.dsa_attention.trace(*call).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text and "dsa_fwd" in text
    _o, _kl, fused = M.dsa_attention(*call)
    assert float(fused) == 0.0


@pytest.mark.parametrize("path", ["kernels", "blocked"])
def test_keeping_the_forward_leaves_a_layers_gradients_as_they_were(path, monkeypatch,
                                                                    interpreted):
    """A layer at the kernels' widths, rematerialised keeping the attention's
    output and log-sum-exps (``KEEP_FORWARD``), against a plain ``nn.remat``
    that runs the forward again: the same value, indexer loss and gradients
    by the input and every parameter, to float32 rounding, with the kernels
    (Pallas' interpreter) and with the blocked code the CPU's lowering takes;
    and the forward kernel staged once where it was staged twice."""
    monkeypatch.setattr(M, "DSA_QUERY_TILE", 16)
    monkeypatch.setattr(M, "DSA_KEY_TILE", 32)
    monkeypatch.setattr(M, "SELECT_BLOCK", 16)
    if path == "kernels":
        monkeypatch.setattr(M, "_dsa_fused", functools.partial(M._dsa_fused, interpret=True))
    jax.clear_caches()
    cfg = dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
               rope_theta=1e7, indexer_num_heads=2, indexer_head_dim=64, topk=12,
               moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
               num_hidden_layers=1, rms_norm_eps=1e-6, experts_held=(0, 1, 2, 3))
    cfg = tuple(sorted(cfg.items()))
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(ks[0], (1, 64, 64))
    ct, ct_kl = jax.random.normal(ks[1], h.shape), jax.random.normal(ks[2], (1, 64))
    params = M._Layer(cfg).init(jax.random.PRNGKey(0), h)

    def grads(policy):
        layer = nn.remat(M._Layer, policy=policy)(cfg)

        def f(params, h):
            out, _load, kl, fused, _stats = layer.apply(params, h)
            return jnp.sum(out * ct) + jnp.sum(kl * ct_kl), fused

        vg = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
        staged = str(vg.trace(params, h).jaxpr).count("name=dsa_fwd")
        (value, fused), g = vg(params, h)
        return staged, float(fused), value, jax.tree.leaves(g)

    kept, plain = grads(M.KEEP_FORWARD), grads(None)
    assert (kept[0], plain[0]) == (1, 2)
    assert kept[1] == plain[1] == (1.0 if path == "kernels" else 0.0)
    assert close(kept[2], plain[2], 1e-6)
    for a, b in zip(kept[3], plain[3], strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert close(a, b, 1e-6)
