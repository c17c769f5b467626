"""The sparse-attention template's layer (rafiki_tpu/models/keye_vl2.py)
against the plain reference (benchmark/references/keye_vl2.py) at a small
size on seeded weights: the initial parameters, the layer's value, indexer
loss and every gradient, the selection against ``lax.top_k`` with its tie
rule, the softmax router, the shares of the expert layer, and ``route``'s
sigmoid mode as it was. Shared fixtures: tests/keye_vl2_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keye_vl2_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, flat, K, M, program_of, R, tiny_keye, load_keye_cfg, value_and_grads)


def test_reference_starts_from_the_programs_initial_parameters(cfg):
    _m, _fns, params, ref = program_of(cfg)
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]), err_msg=k)
    assert R.parameters(cfg) == sum(v.size for v in got.values())


@pytest.mark.parametrize("topk", [16, 64], ids=["selection_bites", "every_key"])
def test_the_layer_matches_the_reference_forward_and_gradient(topk, f32):
    """The whole residual layer (norm, attention over the selection, norm,
    experts): its value and indexer loss, and the gradient by its input and
    by every parameter under a cotangent on each. The indexer's parameters
    take theirs from the loss alone, the rest from the output alone."""
    cfg = tiny_keye(load_keye_cfg(), topk=topk)
    _m, fns, params, ref = program_of(cfg)
    T = int(cfg["seq_len"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    ct = jax.random.normal(jax.random.PRNGKey(11), h.shape)
    mod = M._Layer(fns["module"].cfg)
    lp = params["layer_1"]
    rp = {k: v for k, v in ref.items() if k.startswith("layer_1/")}

    def program(lp, h):
        out, _load, kl, _fused, stats = mod.apply({"params": lp}, h)
        return out, jnp.sum(kl), stats

    def reference(rp, h):
        return R.layer(rp, 1, h, cfg)

    got, got_kl, stats = program(lp, h)
    want, want_kl = reference(rp, h)
    assert close(got, want, 2e-5)
    assert abs(float(got_kl) - float(want_kl)) <= 1e-4 * abs(float(want_kl))
    # every query selects min(t + 1, topk) keys, in both sequences
    assert float(stats[0]) == 2 * R._selected_keys(T, topk)
    g_lp, g_h = jax.vjp(lambda lp, h: program(lp, h)[:2], lp, h)[1]((ct, jnp.float32(0.7)))
    w_lp, w_h = jax.vjp(reference, rp, h)[1]((ct, jnp.float32(0.7)))
    assert close(g_h, w_h, 1e-4)
    g_lp = flat(g_lp)
    for k, g in w_lp.items():
        name = k.split("/", 1)[1]
        assert close(g_lp[name], g, 2e-4), k
    # the indexer learns from its loss alone: with no cotangent on it, nothing
    idx = jax.vjp(lambda lp, h: program(lp, h)[:2], lp, h)[1]((ct, jnp.float32(0.0)))[0]
    for name, g in flat(idx).items():
        if "index_" in name:
            assert float(jnp.max(jnp.abs(g))) == 0.0, name


def _top_k_mask(scores, topk):
    T = scores.shape[-1]
    valid = jnp.arange(T)[None, :] <= jnp.arange(scores.shape[1])[:, None]
    s = jnp.where(valid[None], scores, -jnp.inf)
    return np.asarray(R.select(s, 0, topk)), np.asarray(valid)


@pytest.mark.parametrize("case", ["distinct", "tied", "every_key"])
def test_the_selection_is_top_k_with_ties_to_the_lower_position(case):
    """``dsa_select`` (a bisection over the bits of the scores) against the
    reference's ``lax.top_k`` on the same scores: scores with no equal pair,
    keys repeated four times over so that rows are full of ties at the
    threshold, and ``topk`` at least the length (the causal mask itself)."""
    B, T, HI, dI = 2, 64, 4, 8
    topk = 64 if case == "every_key" else 12
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    qI = jax.random.normal(ks[0], (B, T, HI, dI)).astype(jnp.bfloat16)
    kI = jax.random.normal(ks[1], (B, T, dI)).astype(jnp.bfloat16)
    if case == "tied":
        kI = jnp.repeat(kI[:, : T // 4], 4, axis=1)
    w = jax.random.normal(ks[2], (B, T, HI)) * 0.3
    mask, counts = M.dsa_select(qI, kI, w, topk)
    want, valid = _top_k_mask(M.index_scores(qI, kI, w), topk)
    np.testing.assert_array_equal(np.asarray(mask, bool), want)
    assert int(counts.sum()) == B * R._selected_keys(T, topk)
    if case == "every_key":
        np.testing.assert_array_equal(np.asarray(mask, bool), np.broadcast_to(valid, mask.shape))
    if case == "tied":
        scores = np.asarray(M.index_scores(qI, kI, w))
        kth = np.sort(np.where(valid, scores, -np.inf), axis=-1)[..., -topk]
        ties = (scores == kth[..., None]) & valid
        assert (ties.sum(-1) > 1).sum() > 20          # ties at the threshold, and ...
        cut = np.asarray(mask, bool) & ties
        # ... of the tied keys the lower positions are taken
        last = np.where(cut.any(-1), T - 1 - np.argmax(cut[..., ::-1], -1), -1)
        left = ties & ~cut & (np.arange(T) < last[..., None])
        assert not left.any()


def test_the_router_is_a_softmax_over_every_output_renormalised_over_the_top_k(cfg):
    _m, _fns, _params, ref = program_of(cfg)
    L = "layer_2"
    x = jax.random.normal(jax.random.PRNGKey(2), (192, 64))
    ids, wt = K.route(x, ref[f"{L}/moe/w_router"], 0.0, 4, 1.0, score_function=jax.nn.softmax)
    want_ids, want_wt = R.router(ref, L, x, cfg)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    assert close(wt, want_wt, 1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 1.0, rtol=1e-6)


def test_the_eight_ranks_routed_parts_add_up_to_the_uncut_layer(cfg, f32):
    """Eight ranks of 2 of the 16 experts (the deployment's eight shares, at
    the tiny size), each through the program's ``expert_layer`` told which it
    holds, sum to the uncut reference's layer with all 16; the softmax and its
    top 4 are what every rank computes alike, counted once."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2 * int(cfg["seq_len"]), 64))
    full = dict(cfg, experts_held=list(range(16)), num_experts=16)
    ref = R.init(key, full)
    L = "layer_2"
    want = R.routed_part(ref, L, x, full, list(range(16)))
    ids, w = K.route(x, ref[f"{L}/moe/w_router"], 0.0, 4, 1.0, score_function=jax.nn.softmax)
    total, rows = jnp.zeros_like(x), 0
    for rank in range(8):
        held = (2 * rank, 2 * rank + 1)
        mine = {n: ref[f"{L}/moe/{n}"][2 * rank: 2 * rank + 2]
                for n in ("w_gate", "w_up", "w_down")}
        part, load = K.expert_layer(x, ids, w, held, *mine.values())
        assert close(part, R.routed_part(ref, L, x, full, held, weights=mine), 2e-5)
        total, rows = total + part, rows + int(load.sum())
    assert rows == x.shape[0] * 4          # every slot landed on one rank
    assert close(total, want, 2e-5)


def _route_as_it_was(x, w_router, bias, top_k, scaling, eps=0.0):
    """``kimi_linear.route`` before it took a scoring function."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
    _vals, ids = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, ids, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    return ids, scaling * picked / (total + eps if eps else total)


@pytest.mark.parametrize("eps", [0.0, 1e-6], ids=["first_template", "second_template"])
def test_the_sigmoid_router_is_as_it_was_bit_for_bit(eps):
    """The two other templates call ``route`` without a scoring function:
    the same program (its jaxpr) and the same bits as before it took one."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (256, 64))
    w = jax.random.normal(ks[1], (64, 32)) * 0.1
    bias = jax.random.uniform(ks[2], (32,), minval=-0.05, maxval=0.05)
    args = (x, w, bias, 4, 2.446, eps)
    assert str(jax.make_jaxpr(K.route, static_argnums=(3, 4, 5))(*args)) == str(
        jax.make_jaxpr(_route_as_it_was, static_argnums=(3, 4, 5))(*args))
    for a, b in zip(K.route(*args), _route_as_it_was(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T,d", [(96, 16), (1000, 64), (32768, 128)],
                         ids=["under_one_split", "across_splits", "cell_length"])
def test_the_rotation_is_the_float64_tables_to_a_few_units_and_holds_no_table(T, d):
    """``keye_vl2.rope`` turns by the angle sum of two small tables: within
    four float32 units of the largest value of ``lfm2_moe.rope``, whose
    [T, d] tables are float64 cosines and sines rounded once, and no
    constant of T rows in the lowered program (at the cell's length such a
    table was 16 MB a rotation)."""
    import re

    from rafiki_tpu.models import lfm2_moe

    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 2, d))
    got, want = np.asarray(M.rope(x, 1e7)), np.asarray(lfm2_moe.rope(x, 1e7))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=4 * np.finfo(np.float32).eps * np.abs(want).max())
    text = jax.jit(lambda x: M.rope(x, 1e7)).lower(x).as_text()
    assert not re.search(rf"stablehlo\.constant dense<[^>]*> : tensor<{T}x", text)
