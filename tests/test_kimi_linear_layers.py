"""The language-model template's layers (rafiki_tpu/models/kimi_linear.py)
against the plain reference (benchmark/references/kimi_linear.py) at a small
size on seeded weights: the two mixers (chunked KDA, MLA and its fused
kernel, what a step counts of it) and the expert layer. The fused chunk
kernels of KDA: tests/test_kimi_linear_kda.py. Shared fixtures:
tests/kimi_linear_common.py."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kimi_linear_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, flat, interpreted, K, kda_operands, load_lm_cfg, program_of, R, REPO,
    step_metrics, value_and_grads)


def test_reference_starts_from_the_programs_initial_parameters(cfg):
    _m, _fns, params, ref = program_of(cfg)
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]), err_msg=k)
    assert R.parameters(cfg) == sum(v.size for v in got.values())


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_equals_the_recurrence(chunk, f32):
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    B, T, H, d = 2, 96, 4, 16
    q = K.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = K.l2norm(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    a = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    want = R.delta_rule(q, k, v, a, beta)
    got, fused = K.kda_chunked(q, k, v, a, beta, chunk)
    assert close(got, want, 1e-5) and float(fused) == 0.0
    assert close(R.delta_rule(q[:, :128], k[:, :128], v[:, :128], a[:, :128],
                              beta[:, :128], fit=True), want[:, :128], 1e-6)
    g = jax.grad(lambda a_: K.kda_chunked(q, k, v, a_, beta, chunk)[0].sum())(a)
    assert close(g, jax.grad(lambda a_: R.delta_rule(q, k, v, a_, beta).sum())(a), 1e-4)


def test_unit_lower_inverse_inverts():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 16, 16)), -1)
    eye = jnp.eye(16)
    assert close(jnp.matmul(K.unit_lower_inverse(A), A + eye, precision="highest"),
                 jnp.broadcast_to(eye, A.shape), 1e-4)


@pytest.mark.parametrize("mixer", ["kda", "mla"])
def test_each_mixer_matches_the_reference(cfg, mixer, f32):
    _m, fns, params, ref = program_of(cfg)
    layer = 2 if mixer == "kda" else 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, int(cfg["seq_len"]), 64))
    c = dict(fns["module"].cfg)
    if mixer == "kda":
        mod = K._Kda(c["num_heads"], c["kda_head_dim"], c["short_conv_kernel_size"],
                     c["kda_chunk"], c["rms_norm_eps"])
        want = R.kda(ref, f"layer_{layer}", x, cfg)
    else:
        mod = K._Mla(c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"])
        want = R.mla(ref, f"layer_{layer}", x, cfg)
        assert close(R.mla(ref, f"layer_{layer}", x, cfg, q_block=32), want, 1e-6)
    got, fused = mod.apply({"params": params[f"layer_{layer}"][mixer]}, x)
    assert not np.asarray(fused).any()    # 96 tokens, heads of 16: no kernel's shapes
    assert close(got, want, 2e-5)


def mla_operands(T, dtype=jnp.float32, B=1, H=2):
    """What is particular to MLA: queries and keys 192 wide, values 128."""
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k = (jax.random.normal(ks[i], (B, T, H, 192)).astype(dtype) for i in (0, 1))
    v = jax.random.normal(ks[2], (B, T, H, 128)).astype(dtype)
    return q, k, v, jax.random.normal(ks[3], (B, T, H, 128))


@pytest.mark.parametrize("against", ["whole_row_softmax", "blocked_path"])
def test_the_fused_kernel_matches(against, f32, monkeypatch, interpreted):
    """The kernel path (Pallas in interpret mode on the CPU) at a length of
    four of its blocks, float32 operands: value and all three gradients
    against the reference's whole-row softmax and against the blocked path."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = mla_operands(4 * 128)
    other = R.attention if against == "whole_row_softmax" else K._blocked_attention
    got = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    for name, a, b in zip(("value", "dq", "dk", "dv"), got,
                          value_and_grads(other, q, k, v, ct)):
        assert a.shape == b.shape and close(a, b, 2e-5), name


def test_the_fused_kernel_in_bfloat16_is_as_near_the_reference_as_the_blocked_path(monkeypatch, interpreted):
    """The contract both paths share: bfloat16 operands and result, float32
    products and softmax. What the kernel changes (q rounded after its
    scaling, an online softmax) must cost no more than bfloat16 itself."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = mla_operands(4 * 128, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(R.attention, *(x.astype(jnp.float32) for x in (q, k, v)), ct)
    fused = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    blocked = value_and_grads(K._blocked_attention, q, k, v, ct)
    assert K._fused_attention(q, k, v, interpret=True).dtype == jnp.bfloat16

    def gap(x, w):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32) - w)) / jnp.max(jnp.abs(w)))

    for name, a, b, w in zip(("value", "dq", "dk", "dv"), fused, blocked, want):
        assert a.dtype == b.dtype
        assert gap(b, w) < 0.02 and gap(a, w) < max(1.5 * gap(b, w), 0.01), (name, gap(a, w), gap(b, w))


@pytest.mark.parametrize("T", [4 * K.KERNEL_BLOCK, K.KERNEL_BLOCK + 96],
                         ids=["a_length_of_four_blocks", "a_length_no_block_divides"])
def test_which_attention_runs_is_read_from_the_length_and_the_lowering(T):
    """A length the kernel's block divides: both paths are staged, and the
    platform the program is lowered for takes its own (here the CPU: the
    blocked code, flag 0; ``tests/test_chip_compile.py`` lowers the same
    call for a described TPU). Any other length: the blocked code alone."""
    q, k, v, _ct = mla_operands(T, jnp.bfloat16)
    staged = str(jax.make_jaxpr(K.mla_attention)(q, k, v))
    assert ("platform_index" in staged) == ("pallas_call" in staged) == (T % K.KERNEL_BLOCK == 0)
    lowered = jax.jit(K.mla_attention).lower(q, k, v).as_text()
    assert "tpu_custom_call" not in lowered
    got, fused = jax.jit(K.mla_attention)(q, k, v)
    assert float(fused) == 0.0 and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(K._blocked_attention(q, k, v), np.float32))


@pytest.mark.parametrize("kernel", ["the_attention", "the_chunk_rule"])
def test_the_kernel_is_the_same_text_whatever_model_file_holds_it(kernel):
    """A tenant's model file is loaded as a module whose name differs from
    process to process, and the benchmark's differs from seed to seed. jax
    writes the file names of the traceback into a Pallas kernel's serialized
    body, which the persistent compile cache hashes: were the code's file
    name the module's, every process would build the step program anew
    (110 s on the chip). Lowered for a TPU from two such files, the
    attention is one text, and so is the chunk rule."""
    from drivers import sweep as sweep_driver
    from rafiki_tpu.model.base import load_model_class

    name, xs = (("mla_attention", mla_operands(K.KERNEL_BLOCK, jnp.bfloat16)[:3])
                if kernel == "the_attention" else
                ("kda_chunked", kda_operands(2 * K.KDA_KERNEL_CHUNK, jnp.bfloat16)[0]))
    texts, modules = [], []
    for seed in (1, 2):
        cls = load_model_class(sweep_driver.model_source(REPO, load_lm_cfg(), seed), "BenchModel")
        modules.append(cls.__module__)
        texts.append(jax.jit(getattr(sys.modules[cls.__module__], name)).trace(*xs).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True))
    assert modules[0] != modules[1]
    assert "tpu_custom_call" in texts[0] and "rafiki_model.py" in texts[0]
    assert texts[0] == texts[1]


def test_expert_layer_matches_the_reference_and_counts_its_rows(cfg, f32):
    _m, fns, params, ref = program_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, int(cfg["seq_len"]), 64))
    c = dict(fns["module"].cfg)
    mod = K._Moe(c["num_experts"], c["num_experts_per_token"], tuple(c["experts_held"]),
                 c["moe_intermediate_size"], c["routed_scaling_factor"])
    got, load = mod.apply({"params": params["layer_3"]["moe"]}, x)
    want = (R.routed_part(ref, "layer_3", x, cfg, R.dims(cfg)["held"])
            + R.shared_part(ref, "layer_3", x))
    assert close(got, want, 2e-5)
    ids, _w = R.router(ref, "layer_3", x, cfg)
    assert [int(v) for v in load] == [int((ids == e).sum()) for e in range(4)]


def test_the_shares_add_up_to_the_uncut_layer(cfg, f32):
    """Every chip's routed part (its 4 of the 16 experts, by the program's
    layer, told which it holds) plus the shared expert once is the uncut
    reference's layer with all 16."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2 * int(cfg["seq_len"]), 64))
    full = dict(cfg, experts_held=list(range(16)), num_experts=16)
    ref = R.init(key, full)
    L = "layer_2"
    want = (R.routed_part(ref, L, x, full, list(range(16))) + R.shared_part(ref, L, x))
    ids, w = K.route(x, ref[f"{L}/moe/w_router"], ref[f"{L}/moe/router_bias"], 4, 2.446)
    total = R.shared_part(ref, L, x)
    rows = 0
    for shard in range(4):
        held = tuple(range(4 * shard, 4 * shard + 4))
        part, load = K.expert_layer(
            x, ids, w, held, *(ref[f"{L}/moe/{n}"][4 * shard: 4 * shard + 4]
                               for n in ("w_gate", "w_up", "w_down")))
        total, rows = total + part, rows + int(load.sum())
    assert rows == x.shape[0] * 4          # every slot landed on one chip
    assert close(total, want, 2e-5)


def test_rows_past_a_ragged_products_groups_reach_neither_values_nor_gradients(cfg, f32, monkeypatch):
    """On the TPU a ragged product leaves the rows past its groups as they
    were in memory, in its result and in its left operand's gradient (the
    CPU zero-fills both). Planted here as NaN: the expert layer's result and
    every gradient must stay what they are."""
    real = jax.lax.ragged_dot

    def dead(x, sizes):
        return (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(dead(lhs, sizes), jnp.nan, real(lhs, rhs, sizes,
                                                         preferred_element_type=jnp.float32))

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        _y, vjp = jax.vjp(lambda a, b: real(a, b, sizes, preferred_element_type=jnp.float32),
                          lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(dead(lhs, sizes), 0.0, ct))
        return jnp.where(dead(lhs, sizes), jnp.nan, d_lhs).astype(lhs.dtype), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    _m, fns, params, ref = program_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (192, 64))
    ids, w = K.route(x, ref["layer_2/moe/w_router"], ref["layer_2/moe/router_bias"], 4, 2.446)
    ws = [ref[f"layer_2/moe/{n}"] for n in ("w_gate", "w_up", "w_down")]

    def total(x, w, *ws):
        return jnp.sum(K.expert_layer(x, ids, w, (0, 1, 2, 3), *ws)[0] ** 2)

    want = jax.grad(total, argnums=(0, 1, 2, 3, 4))(x, w, *ws)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, group_sizes, preferred_element_type=None:
                        poisoned(lhs, rhs, group_sizes))
    got = jax.grad(total, argnums=(0, 1, 2, 3, 4))(x, w, *ws)
    assert np.isfinite(float(total(x, w, *ws)))
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a))) and close(a, b, 1e-5)


@pytest.mark.parametrize("seq_len", [96, K.KERNEL_BLOCK])
def test_a_step_counts_its_mla_layers_and_none_of_them_fused_on_the_cpu(seq_len):
    metrics = step_metrics(seq_len)
    assert metrics["count.mla.layers"] == 1.0    # layers: dense, KDA, KDA, MLA, KDA
    assert metrics["count.mla.fused"] == 0.0
