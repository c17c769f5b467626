"""The third language-model template's parts (rafiki_tpu/models/ouro.py)
against the plain reference (benchmark/references/ouro.py) at a small size on
seeded weights: a layer visit forward and gradient, the attention with as many
key/value heads as query heads, the exit distribution and its entropy term,
the per-token form of the blocked loss at the three templates' shapes, and
the fused attention at 16 heads of 128. Shared fixtures: tests/ouro_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ouro_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, flat, interpreted, K, M, program_of, R, value_and_grads)


def test_reference_starts_from_the_programs_initial_parameters(cfg):
    _m, _fns, params, ref = program_of(cfg)
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]), err_msg=k)
    assert R.parameters(cfg) == sum(v.size for v in got.values())
    # two held layers: one set of weights each, however many passes visit them
    assert sorted(k for k in got if k.endswith("attn/w_q")) == [
        "layer_1/attn/w_q", "layer_2/attn/w_q"]
    assert float(ref["gate_b"]) == 0.0 and 0.0 < float(jnp.abs(ref["gate_w"]).max()) < 0.1


@pytest.mark.parametrize("layer", [1, 2])
def test_a_layer_visit_matches_the_reference_forward_and_gradient(cfg, layer, f32):
    """The whole sandwich-normed layer (norm, attention, norm; norm,
    feed-forward part, norm): its value, the gradient by its input and by
    every parameter. The template's layer returns what it ADDS to the stream.
    Tolerances: both sides are float32 at "highest" here, so 2e-5 of the
    largest element is summation order; a bfloat16 product reads 4e-3."""
    _m, fns, params, ref = program_of(cfg)
    h = jax.random.normal(jax.random.PRNGKey(layer), (2, int(cfg["seq_len"]), 64))
    ct = jax.random.normal(jax.random.PRNGKey(10 + layer), h.shape)
    mod = M._Layer(fns["module"].cfg)
    lp = params[f"layer_{layer}"]
    rp = {k: v for k, v in ref.items() if k.startswith(f"layer_{layer}/")}

    got, got_lp, got_h = value_and_grads(
        lambda lp, h: h + mod.apply({"params": lp}, h)[0], lp, h, ct)
    want, want_lp, want_h = value_and_grads(
        lambda rp, h: R.layer(rp, layer, h, cfg), rp, h, ct)
    assert close(got, want, 2e-5) and close(got_h, want_h, 1e-4)
    got_lp = flat(got_lp)
    assert set(got_lp) == {k.split("/", 1)[1] for k in want_lp}
    for k, g in want_lp.items():
        assert close(got_lp[k.split("/", 1)[1]], g, 2e-4), k


def test_the_attention_matches_the_reference_and_sees_nothing_from_the_future(cfg, f32):
    _m, fns, params, ref = program_of(cfg)
    c = dict(fns["module"].cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, int(cfg["seq_len"]), 64))
    got, fused = M._Attn(c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                         c["rope_theta"]).apply({"params": params["layer_1"]["attn"]}, x)
    want = R.attn_op(ref, "layer_1", x, cfg)
    assert float(fused) == 0.0          # 96 tokens: no block of the kernel divides it
    assert close(got, want, 2e-5)
    assert close(R.attn_op(ref, "layer_1", x, cfg, q_block=32), want, 1e-6)
    later = x.at[:, 50:].set(0.0)
    assert close(R.attn_op(ref, "layer_1", later, cfg)[:, :50], want[:, :50], 1e-6)


def test_the_exit_distribution_sums_to_one_and_is_the_written_out_product():
    """p_t = lambda_t prod_(j<t) (1 - lambda_j), p_R = prod_(j<R) (1 - lambda_j):
    the template's logarithmic form against the reference's product and
    against numpy in float64; the last pass's own gate enters nothing; a gate
    saturated in float32 gives no NaN on either side."""
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 2, 50))
    p, logp = M.exit_distribution(gates)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    want = np.stack([lam[0], lam[1] * (1 - lam[0]), lam[2] * (1 - lam[0]) * (1 - lam[1]),
                     (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    # (1 - sigmoid(g) cancels in float32 where g is large: 1e-7 absolute)
    np.testing.assert_allclose(np.asarray(p), want, rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(np.asarray(R.exit_distribution(gates)), want, rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logp), np.log(want), rtol=1e-5, atol=2e-6)
    other = gates.at[3].set(7.0)
    np.testing.assert_array_equal(np.asarray(M.exit_distribution(other)[0]), np.asarray(p))
    hard = gates.at[0].set(40.0)        # sigmoid(40) is 1.0 in float32
    p_hard, logp_hard = M.exit_distribution(hard)
    assert np.isfinite(np.asarray(p_hard * logp_hard)).all()
    assert np.isfinite(np.asarray(R.exit_entropy(R.exit_distribution(hard)))).all()
    np.testing.assert_allclose(np.asarray(-(p_hard * logp_hard).sum(0)),
                               np.asarray(R.exit_entropy(R.exit_distribution(hard))), atol=1e-6)


def test_the_entropy_terms_gradient_reaches_the_gate():
    """With every pass's cross entropy the same, sum_t p_t CE_t is constant
    (the weights sum to one) and the objective's only gradient by the gates is
    the entropy term's: it is not nought, it is the reference's, it vanishes
    with beta, and it pushes a peaked distribution towards a flatter one."""
    gates = jnp.stack([jnp.full((3,), g) for g in (2.0, 0.5, -0.5, 0.0)])
    ce = jnp.full((4, 3), 5.0)

    def mine(g, beta):
        p, logp = M.exit_distribution(g)
        return jnp.sum(jnp.sum(p * ce, 0) + beta * jnp.sum(p * logp, 0))

    theirs = lambda g, beta: jnp.sum(R.objective(R.exit_distribution(g), ce, beta))
    got, want = jax.grad(mine)(gates, 0.05), jax.grad(theirs)(gates, 0.05)
    assert float(jnp.abs(want[:3]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-7)
    assert float(jnp.abs(jax.grad(mine)(gates, 0.0)).max()) < 1e-5
    assert float(jnp.abs(got[3]).max()) == 0.0          # the last pass has no gate of its own
    # descent lowers the first gate (0.88 of the mass leaves at pass 1): more entropy
    assert float(got[0, 0]) > 0


#: (tokens, hidden, vocabulary, block) in the proportions of the three cells'
#: heads: 20,480 and 16,384 sliced rows, the whole 49,152; hidden 2304, 2048.
LOSS_SHAPES = {"kimi_linear": (96, 72, 640, 32), "lfm2_moe": (96, 64, 512, 32),
               "ouro": (96, 64, 1536, 32), "a_length_no_block_divides": (80, 64, 512, 32)}


@pytest.mark.parametrize("shape", sorted(LOSS_SHAPES))
def test_the_per_token_blocked_loss_is_whole_logits_and_sums_to_the_summed_form(shape, f32):
    """``blocked_logit_stats(per_token=True)`` against whole logits, token by
    token, labels left out (-1) among them; summed it is the summed form the
    other two templates call, to float32's last digits (1e-6: another order
    of one sum); and a cotangent per token comes back as the whole-logits
    gradient (which a summed form cannot give: the weights differ a token)."""
    T, D, V, block = LOSS_SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(T + V), 4)
    h = jax.random.normal(ks[0], (2, T, D))
    head = 0.2 * jax.random.normal(ks[1], (D, V))
    y = jax.random.randint(ks[2], (2, T), 0, V).at[:, ::7].set(-1)
    weights = jax.random.uniform(ks[3], (2, T))

    def whole(h, head):
        logp = jax.nn.log_softmax(jnp.einsum("btd,dv->btv", h, head, precision="highest"), -1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(y, 0)[..., None], -1)[..., 0]
        return jnp.where(y >= 0, 0.9 * nll - 0.1 * logp.mean(-1), 0.0), logp.argmax(-1)

    ce, hit, mask = K.blocked_logit_stats(h, head, y, 0.1, block=block, per_token=True)
    want, top = whole(h, head)
    assert ce.shape == hit.shape == mask.shape == (2, T)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(y >= 0))
    np.testing.assert_array_equal(np.asarray(hit), np.asarray((top == y) & (y >= 0)))
    total, hits, n = K.blocked_logit_stats(h, head, y, 0.1, block=block)
    assert abs(float(ce.sum()) - float(total)) < 1e-6 * float(total)
    assert (int(hit.sum()), int(mask.sum())) == (int(hits), int(n))
    got = jax.grad(lambda h, head: jnp.sum(weights * K.blocked_logit_stats(
        h, head, y, 0.1, block=block, per_token=True)[0]), argnums=(0, 1))(h, head)
    want_g = jax.grad(lambda h, head: jnp.sum(weights * whole(h, head)[0]), argnums=(0, 1))(h, head)
    for a, b in zip(got, want_g):
        assert close(a, b, 2e-5)


def test_the_blocked_loss_in_bfloat16_is_near_whole_logits_and_not_equal():
    """The tolerance above fails a bfloat16-for-float32 swap: with the
    template's own products the per-token values are 1e-3 off, not 1e-5."""
    T, D, V, block = LOSS_SHAPES["ouro"]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    h, head = jax.random.normal(ks[0], (2, T, D)), 0.2 * jax.random.normal(ks[1], (D, V))
    y = jax.random.randint(ks[2], (2, T), 0, V)
    ce, _hit, _mask = K.blocked_logit_stats(h, head, y, 0.0, block=block, per_token=True)
    logp = jax.nn.log_softmax(jnp.einsum("btd,dv->btv", h, head, precision="highest"), -1)
    want = -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
    gap = float(jnp.max(jnp.abs(ce - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < gap < 2e-2


def attention_operands(T, dtype=jnp.float32, B=1, H=16, d=128):
    """The published heads: 16 query and 16 key/value heads of 128."""
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, d)).astype(dtype) for i in range(3))
    return q, k, v, jax.random.normal(ks[3], (B, T, H, d))


def test_the_fused_kernel_at_16_heads_of_128_matches_the_reference(f32, monkeypatch,
                                                                   interpreted):
    """The kernel path (Pallas in interpret mode on the CPU) at a length of
    two of its blocks and the third shape the library kernel is called with
    (H = Hk = 16, q, k and v 128 wide), float32 operands: value and all three
    gradients against the reference's whole-row softmax."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = attention_operands(2 * 128, H=4)
    got = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    want = value_and_grads(R.attention, q, k, v, ct)
    for name, a, b in zip(("value", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and close(a, b, 2e-5), name


def test_which_attention_runs_is_read_from_the_lowering():
    """At the cell's head shape and a length the kernel's block divides both
    paths are staged, the CPU takes the blocked code (flag 0), and the same
    call lowered for a TPU holds the kernel."""
    q, k, v, _ct = attention_operands(K.KERNEL_BLOCK, jnp.bfloat16, H=2)
    staged = str(jax.make_jaxpr(K.mla_attention)(q, k, v))
    assert "platform_index" in staged and "pallas_call" in staged
    assert "tpu_custom_call" not in jax.jit(K.mla_attention).lower(q, k, v).as_text()
    assert "tpu_custom_call" in jax.jit(K.mla_attention).trace(q, k, v).lower(
        lowering_platforms=("tpu",)).as_text()
    got, fused = jax.jit(K.mla_attention)(q, k, v)
    assert float(fused) == 0.0 and got.dtype == jnp.bfloat16 and got.shape == q.shape
