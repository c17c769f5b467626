"""The whole second language-model template against its plain reference at
a small size: logits, loss, counts and every gradient leaf, the tied table's
gradient, the bfloat16 program, the counts at the published widths, and the
contract harness. Shared fixtures: tests/lfm2_moe_common.py."""

import jax
import jax.numpy as jnp

from lfm2_moe_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, FixedKnob, flat, load_lfm2_cfg, program_of, R, tokens, TRAIN, VAL)


def test_logits_loss_counts_and_every_gradient_leaf(cfg, f32):
    model, fns, params, ref = program_of(cfg, label_smoothing=0.07)
    x, y = tokens(cfg)
    module = fns["module"]
    h, head, _loads, _fused = module.apply({"params": params}, x, hidden=True)
    logits = R.forward(ref, x, cfg)
    assert head.shape == (64, 256)          # the table transposed: no head of its own
    assert close(jnp.einsum("btd,dv->btv", h, head, precision="highest"), logits, 5e-5)
    assert close(module.apply({"params": params}, x), logits[:, -1], 5e-5)
    batch = {"x": x, "y": y}
    (loss, metrics), grads = jax.value_and_grad(fns["loss_fn"], has_aux=True)(
        params, batch, None, {"label_smoothing": jnp.float32(0.07)})
    want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg, 0.07)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32), want, 1e-6)
    R.HEAD_BLOCK, block = 32, R.HEAD_BLOCK      # the fitting cuts at this size too
    try:
        assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32), want, 1e-6)
        assert int(R.stats(ref, x, y, cfg, fit=True)[1]) == int(R.stats(ref, x, y, cfg)[1])
    finally:
        R.HEAD_BLOCK = block
    _ce, hits, n = R.stats(ref, x, y, cfg)
    got_hits, got_n = fns["eval_count"](params, batch)
    assert (int(got_hits), int(got_n)) == (int(hits), int(n))
    assert abs(float(metrics["acc"]) - int(hits) / int(n)) < 1e-6
    got_g = flat(grads)
    assert set(got_g) == set(want_g)
    scale = max(float(jnp.max(jnp.abs(v))) for v in want_g.values())
    for k, g in want_g.items():
        assert close(got_g[k], g, 2e-4) or \
            float(jnp.max(jnp.abs(got_g[k] - g))) < 1e-6 * scale, k
    for layer in (3, 4, 5, 6):      # a buffer: selection is by integer ids
        assert float(jnp.max(jnp.abs(got_g[f"layer_{layer}/moe/expert_bias"]))) == 0.0
    # four sparse layers x top 4; a step's operators: one attention, five convolutions
    assert float(metrics["count.moe.slots_total"]) == x.size * 4 * 4
    assert 0 < float(metrics["count.moe.slots_held"]) < x.size * 4 * 4
    assert (float(metrics["count.attn.layers"]), float(metrics["count.attn.fused"]),
            float(metrics["count.conv.layers"])) == (1.0, 0.0, 5.0)


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(cfg, f32):
    """The table is read twice: rows taken for the first layer's input, and
    its transpose as the head. The program's one gradient leaf is the sum of
    the reference's two, each taken with the other use held constant."""
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    grads = jax.grad(lambda p: fns["loss_fn"](
        p, {"x": x, "y": y}, None, {"label_smoothing": jnp.float32(0.0)})[0])(params)

    def loss_of(taken_from, head_from):
        h = R.embed({"embed": taken_from}, x)
        for i, (op, sparse) in enumerate(R.layer_kinds(cfg), start=1):
            h = R.layer(ref, i, h, cfg, op, sparse)
        ce, _hits = R.head_stats({"embed": head_from}, R.final_norm(ref, h, cfg), y)
        return ce / y.size

    table = ref["embed"]
    as_input, as_head = jax.grad(loss_of, argnums=(0, 1))(table, table)
    assert float(jnp.max(jnp.abs(as_input))) > 0 and float(jnp.max(jnp.abs(as_head))) > 0
    assert not close(as_head, as_input + as_head, 1e-2)     # neither use alone is the whole
    assert close(grads["embed"], as_input + as_head, 2e-4)


def test_bfloat16_program_is_near_the_reference(cfg):
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    loss, _ = fns["loss_fn"](params, {"x": x, "y": y}, None,
                             {"label_smoothing": jnp.float32(0.0)})
    with jax.default_matmul_precision("highest"):
        want = R.loss(ref, x, y, cfg)
    assert abs(float(loss) - float(want)) < 5e-3 * float(want)


def test_the_counts_at_the_published_widths():
    cfg = load_lfm2_cfg()
    assert R.parameters(cfg) == cfg["parameters"] == 568_647_936
    d = R.dims(cfg)
    assert (d["D"], d["H"], d["Hkv"], d["d"], d["ffn"], d["moe"], d["experts"], d["top_k"],
            d["taps"], d["theta"]) == (2048, 32, 8, 64, 7168, 1792, 32, 4, 3, 1e6)
    assert R.layer_kinds(cfg) == [("conv", False), ("conv", False), ("attn", True),
                                  ("conv", True), ("conv", True), ("conv", True)]
    per_token = R.forward_flops(cfg)
    # 2 x the parameters a token meets (the table once, as the head: its rows
    # taken are not multiplied; the routed experts at their expected share,
    # which at 8 of 32 held and 4 a token is one expert a layer) + attention
    expert = 3 * d["D"] * d["moe"]
    met = R.parameters(cfg) - 4 * (len(d["held"]) - 1) * expert
    attention = (8192 + 1) / 2 * d["H"] * 2 * d["d"]
    assert abs(per_token - 2 * (met + attention)) < 0.001 * per_token
    assert 553e6 < per_token < 556e6 and 27e12 < 3 * 16384 * per_token < 27.5e12
    assert R.forward_flops(cfg, seq_len=4096) < per_token
    # the causal half of one attention layer, two sequences: 2 and 5 products
    kernel = R.attention_kernel_flops(cfg, batch=2)
    product = 2 * 2 * 32 * (8192 * 8193 / 2) * 64
    assert kernel == {"forward": 2 * product, "backward": 5 * product}
    assert 0.549e12 < kernel["forward"] < 0.551e12    # 0.275 TFLOP a sequence


def test_template_is_registered_and_passes_the_contract_harness():
    from rafiki_tpu.constants import TaskType
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.models import get_model_class

    cls = get_model_class("Lfm2Moe")
    fixed = {k: v.value for k, v in cls.get_knob_config().items()
             if isinstance(v, FixedKnob)}
    score, preds = test_model_class(
        cls, TaskType.LANGUAGE_MODELING.value, TRAIN, VAL, queries=[[5, 9, 3] * 8],
        knobs=dict(fixed, learning_rate=1e-3, label_smoothing=0.05))
    assert 0.0 <= score <= 1.0 and len(preds[0]) == 256
