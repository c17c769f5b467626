"""The sparse attention's three Pallas kernels (``dsa_fwd``, ``dsa_dq``,
``dsa_dkv`` in rafiki_tpu/models/keye_vl2.py), run by Pallas' interpreter on
the CPU, against the blocked ``jax.numpy`` path: the output, every head's
and the indexer's log-sum-exp and the indexer's loss forward, and all six
gradients backward, at tilings where a tile holds the whole sequence and
where key tiles above the diagonal and tiles with no selected key are
skipped; with every key selected, the same attention as dense causal
softmax; and which path runs is read from the lowering. Shared fixtures:
tests/keye_vl2_common.py."""

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
