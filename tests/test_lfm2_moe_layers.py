"""The second language-model template's layers (rafiki_tpu/models/lfm2_moe.py)
against the plain reference (benchmark/references/lfm2_moe.py) at a small
size on seeded weights: each kind of layer forward and gradient, rotary
positions, the router with its bias, the shares of the expert layer, and the
fused attention at 8 key/value heads for 32. Shared fixtures:
tests/lfm2_moe_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lfm2_moe_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, flat, interpreted, K, M, program_of, R, value_and_grads)


def test_reference_starts_from_the_programs_initial_parameters(cfg):
    _m, _fns, params, ref = program_of(cfg)
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]), err_msg=k)
    assert R.parameters(cfg) == sum(v.size for v in got.values())
    bias = np.asarray(ref["layer_3/moe/expert_bias"])
    assert bias.shape == (16,) and 0.0 < np.abs(bias).max() <= R.BIAS_RANGE


@pytest.mark.parametrize("layer", [1, 3, 4], ids=["conv_dense", "attention_experts", "conv_experts"])
def test_each_kind_of_layer_matches_the_reference_forward_and_gradient(cfg, layer, f32):
    """The whole residual layer (norm, operator, norm, feed-forward part):
    its value, the gradient by its input and by every parameter."""
    _m, fns, params, ref = program_of(cfg)
    op, sparse = R.layer_kinds(cfg)[layer - 1]
    h = jax.random.normal(jax.random.PRNGKey(layer), (2, int(cfg["seq_len"]), 64))
    ct = jax.random.normal(jax.random.PRNGKey(10 + layer), h.shape)
    mod = M._Layer(fns["module"].cfg, op, sparse)
    lp = params[f"layer_{layer}"]
    rp = {k: v for k, v in ref.items() if k.startswith(f"layer_{layer}/")}

    got, got_lp, got_h = value_and_grads(
        lambda lp, h: mod.apply({"params": lp}, h)[0], lp, h, ct)
    want, want_lp, want_h = value_and_grads(
        lambda rp, h: R.layer(rp, layer, h, cfg, op, sparse), rp, h, ct)
    assert close(got, want, 2e-5) and close(got_h, want_h, 1e-4)
    got_lp = flat(got_lp)
    scale = max(float(jnp.max(jnp.abs(v))) for v in want_lp.values())
    for k, g in want_lp.items():
        name = k.split("/", 1)[1]
        if name.endswith("expert_bias"):
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(jnp.abs(got_lp[name])))
            continue
        assert close(got_lp[name], g, 2e-4) or \
            float(jnp.max(jnp.abs(got_lp[name] - g))) < 1e-6 * scale, k


@pytest.mark.parametrize("op", ["conv", "attn"])
def test_each_operator_matches_the_reference(cfg, op, f32):
    _m, fns, params, ref = program_of(cfg)
    c = dict(fns["module"].cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, int(cfg["seq_len"]), 64))
    if op == "conv":
        got = M._Conv(c["conv_L_cache"]).apply({"params": params["layer_4"]["conv"]}, x)
        want = R.conv_op(ref, "layer_4", x)
        # three shifted products: tap 2 is the current token's, nothing from the future
        later = x.at[:, 50:].set(0.0)
        assert close(R.conv_op(ref, "layer_4", later)[:, :50], want[:, :50], 1e-6)
    else:
        got, fused = M._Gqa(c["num_attention_heads"], c["num_key_value_heads"],
                            c["rope_theta"], c["norm_eps"]).apply(
            {"params": params["layer_3"]["attn"]}, x)
        want = R.attn_op(ref, "layer_3", x, cfg)
        assert float(fused) == 0.0          # 96 tokens: no block of the kernel divides it
        assert close(R.attn_op(ref, "layer_3", x, cfg, q_block=32), want, 1e-6)
    assert close(got, want, 2e-5)


def test_rotary_positions_are_a_complex_rotation_of_the_half_pairs():
    """Rotate-half: channels i and i + d / 2 are the real and imaginary
    parts of one number, multiplied by exp(j position theta^(-2i/d))."""
    B, T, H, d, theta = 2, 40, 3, 16, 1e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (B, T, H, d)), np.float64)
    z = x[..., : d // 2] + 1j * x[..., d // 2:]
    angle = np.arange(T)[:, None] * theta ** (-np.arange(d // 2) / (d // 2))
    z = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1)
    for fn in (M.rope, R.rope):
        got = np.asarray(fn(jnp.asarray(x, jnp.float32), theta))
        np.testing.assert_allclose(got, want, atol=2e-5)
    # position 0 is left as it is; a rotation keeps a pair's length
    np.testing.assert_allclose(np.asarray(M.rope(jnp.asarray(x, jnp.float32), theta))[:, 0],
                               x[:, 0], atol=1e-6)


def test_the_router_selects_by_score_plus_bias_and_weights_by_the_score_alone(cfg):
    _m, _fns, _params, ref = program_of(cfg)
    L = "layer_3"
    x = jax.random.normal(jax.random.PRNGKey(2), (192, 64))
    w, bias = ref[f"{L}/moe/w_router"], ref[f"{L}/moe/expert_bias"]
    ids, wt = K.route(x, w, bias, 4, 1.0, M.ROUTER_EPS)
    want_ids, want_wt = R.router(ref, L, x, cfg)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    assert close(wt, want_wt, 1e-6)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ w), np.float64)
    unbiased = np.argsort(-s, axis=-1)[:, :4]
    biased = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(biased, -1))
    changed = np.any(np.sort(biased, -1) != np.sort(unbiased, -1), axis=-1)
    assert changed.sum() > 10          # the bias changes the selection ...
    picked = np.take_along_axis(s, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(np.asarray(wt), picked / (picked.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-5)      # ... and no weight
    assert np.all(np.asarray(wt).sum(-1) < 1.0)   # the 1e-6 is there


def test_the_four_ranks_routed_parts_add_up_to_the_uncut_layer(cfg, f32):
    """Every rank's routed part (its 4 of the 16 experts, by the program's
    ``expert_layer`` told which it holds) sums to the uncut reference's layer
    with all 16; there is no shared expert to count once."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2 * int(cfg["seq_len"]), 64))
    full = dict(cfg, experts_held=list(range(16)), num_experts=16)
    ref = R.init(key, full)
    L = "layer_4"
    want = R.routed_part(ref, L, x, full, list(range(16)))
    ids, w = K.route(x, ref[f"{L}/moe/w_router"], ref[f"{L}/moe/expert_bias"], 4, 1.0,
                     M.ROUTER_EPS)
    total, rows = jnp.zeros_like(x), 0
    for rank in range(4):
        held = tuple(range(4 * rank, 4 * rank + 4))
        mine = {n: ref[f"{L}/moe/{n}"][4 * rank: 4 * rank + 4]
                for n in ("w_gate", "w_up", "w_down")}
        part, load = K.expert_layer(x, ids, w, held, *mine.values())
        # the reference given the same share gives the same part
        assert close(part, R.routed_part(ref, L, x, full, held, weights=mine), 2e-5)
        total, rows = total + part, rows + int(load.sum())
    assert rows == x.shape[0] * 4          # every slot landed on one rank
    assert close(total, want, 2e-5)


def gqa_operands(T, dtype=jnp.float32, B=1, H=32, Hkv=8, d=64):
    """The published heads: 32 query heads on 8 key/value heads of 64."""
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q = jax.random.normal(ks[0], (B, T, H, d)).astype(dtype)
    k, v = (jax.random.normal(ks[i], (B, T, Hkv, d)).astype(dtype) for i in (1, 2))
    return q, k, v, jax.random.normal(ks[3], (B, T, H, d))


def repeated(fn):
    """``fn`` on key/value heads repeated for the query heads they serve."""
    return lambda q, k, v: fn(q, *(jnp.repeat(x, q.shape[2] // k.shape[2], axis=2)
                                   for x in (k, v)))


def test_the_fused_kernel_at_8_key_value_heads_for_32_matches_the_reference(f32, monkeypatch,
                                                                             interpreted):
    """The kernel path (Pallas in interpret mode on the CPU) at a length of
    four of its blocks, float32 operands: value and all three gradients
    against the reference's whole-row softmax on repeated heads. A key/value
    head's gradient is the sum over the four query heads it serves."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = gqa_operands(4 * 128)
    got = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    want = value_and_grads(repeated(R.attention), q, k, v, ct)
    for name, a, b in zip(("value", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and close(a, b, 2e-5), name
    assert got[2].shape == (1, 512, 8, 64)


@pytest.mark.parametrize("T", [2 * K.KERNEL_BLOCK, K.KERNEL_BLOCK + 96],
                         ids=["a_length_of_two_blocks", "a_length_no_block_divides"])
def test_which_attention_runs_for_grouped_queries_is_read_from_the_lowering(T):
    """As for the first template: at a length the kernel's block divides both
    paths are staged, the CPU takes the blocked code on repeated heads (flag
    0), and the same call lowered for a TPU holds the kernel."""
    q, k, v, _ct = gqa_operands(T, jnp.bfloat16, H=8, Hkv=2)
    staged = str(jax.make_jaxpr(K.mla_attention)(q, k, v))
    assert ("platform_index" in staged) == ("pallas_call" in staged) == (T % K.KERNEL_BLOCK == 0)
    assert "tpu_custom_call" not in jax.jit(K.mla_attention).lower(q, k, v).as_text()
    if T % K.KERNEL_BLOCK == 0:
        assert "tpu_custom_call" in jax.jit(K.mla_attention).trace(q, k, v).lower(
            lowering_platforms=("tpu",)).as_text()
    got, fused = jax.jit(K.mla_attention)(q, k, v)
    assert float(fused) == 0.0 and got.dtype == jnp.bfloat16 and got.shape == q.shape
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(repeated(K._blocked_attention)(q, k, v), np.float32))
