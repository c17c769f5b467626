"""The whole language-model template against its plain reference at a small
size: logits, loss, counts and every gradient leaf, the bfloat16 program,
the flop count, and the contract harness. Shared fixtures:
tests/kimi_linear_common.py."""

import jax
import jax.numpy as jnp

from kimi_linear_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, FixedKnob, flat, load_lm_cfg, program_of, R, tokens,
    TRAIN, VAL)


def test_logits_loss_counts_and_every_gradient_leaf(cfg, f32):
    model, fns, params, ref = program_of(cfg, label_smoothing=0.07)
    x, y = tokens(cfg)
    module = fns["module"]
    h, head, _loads, _fused = module.apply({"params": params}, x, hidden=True)
    logits = R.forward(ref, x, cfg)
    assert close(jnp.einsum("btd,dv->btv", h, head, precision="highest"), logits, 5e-5)
    assert close(module.apply({"params": params}, x), logits[:, -1], 5e-5)
    hyper = {"label_smoothing": jnp.float32(0.07)}
    batch = {"x": x, "y": y}
    (loss, metrics), grads = jax.value_and_grad(fns["loss_fn"], has_aux=True)(
        params, batch, None, hyper)
    want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg, 0.07)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32, seq_block=1), want, 1e-6)
    R.SEGMENT, segment = 4, R.SEGMENT        # the fitting cuts at this size too
    try:
        assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32, seq_block=1), want, 1e-6)
        assert int(R.stats(ref, x, y, cfg, fit=True)[1]) == int(R.stats(ref, x, y, cfg)[1])
    finally:
        R.SEGMENT = segment
    _ce, hits, n = R.stats(ref, x, y, cfg)
    got_hits, got_n = fns["eval_count"](params, batch)
    assert (int(got_hits), int(got_n)) == (int(hits), int(n))
    assert abs(float(metrics["acc"]) - int(hits) / int(n)) < 1e-6
    got_g = flat(grads)
    scale = max(float(jnp.max(jnp.abs(v))) for v in want_g.values())
    for k, g in want_g.items():
        if k.endswith("router_bias"):
            continue  # held at zero: no gradient reaches it
        assert close(got_g[k], g, 2e-4) or \
            float(jnp.max(jnp.abs(got_g[k] - g))) < 1e-6 * scale, k
    assert float(jnp.max(jnp.abs(got_g["layer_2/moe/router_bias"]))) == 0.0
    assert float(metrics["count.moe.slots_total"]) == x.size * 4 * 4
    assert 0 < float(metrics["count.moe.slots_held"]) < x.size * 4 * 4


def test_bfloat16_program_is_near_the_reference(cfg):
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    loss, _ = fns["loss_fn"](params, {"x": x, "y": y}, None,
                             {"label_smoothing": jnp.float32(0.0)})
    with jax.default_matmul_precision("highest"):
        want = R.loss(ref, x, y, cfg)
    assert abs(float(loss) - float(want)) < 5e-3 * float(want)


def test_forward_flops_count_the_layers_and_parameters_at_the_published_widths():
    cfg = load_lm_cfg()
    assert R.parameters(cfg) == 602_434_432
    per_token = R.forward_flops(cfg)
    # 2 x the parameters a token meets (the routed experts at their expected
    # share, the embedding's rows not multiplied) + attention over the keys
    d = R.dims(cfg)
    expert = 3 * d["D"] * d["moe"]
    met = (R.parameters(cfg) - d["vocab"] * d["D"]
           - 4 * (len(d["held"]) - d["top_k"] * len(d["held"]) / d["experts"]) * expert)
    attention = (8192 + 1) / 2 * d["H"] * (d["nope"] + d["rope"] + d["dv"])
    recurrence = 4 * 3 * d["Hk"] * d["dk"] * d["dk"]  # decay-free: S k, k u^T, S q
    assert abs(per_token - 2 * (met + attention + recurrence)) < 0.001 * per_token
    assert 36e12 < 3 * 16384 * per_token < 39e12
    assert R.forward_flops(cfg, seq_len=4096) < per_token


def test_template_is_registered_and_passes_the_contract_harness():
    from rafiki_tpu.constants import TaskType
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.models import get_model_class

    cls = get_model_class("KimiLinear")
    fixed = {k: v.value for k, v in cls.get_knob_config().items()
             if isinstance(v, FixedKnob)}
    score, preds = test_model_class(
        cls, TaskType.LANGUAGE_MODELING.value, TRAIN, VAL, queries=[[5, 9, 3] * 8],
        knobs=dict(fixed, learning_rate=1e-3, label_smoothing=0.05))
    assert 0.0 <= score <= 1.0 and len(preds[0]) == 256
