"""The whole third language-model template against its plain reference at a
small size: the loop (R = 1 is the plain stack; R = 4 is the unrolled 4 x L
layers with tied weights), a layer's gradient as the sum over its visits, the
objective and every gradient leaf, evaluation from the last pass, the bfloat16
program, the counts at the published widths, and the contract harness. Shared
fixtures: tests/ouro_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ouro_common import (  # noqa: F401 (fixtures)
    cfg, close, f32, FixedKnob, flat, load_ouro_cfg, program_of, R, tiny_ouro, tokens,
    TRAIN, VAL)


def _objective(fns, params, x, y, smoothing=0.07):
    return fns["loss_fn"](params, {"x": x, "y": y}, None,
                          {"label_smoothing": jnp.float32(smoothing)})


def test_the_objective_counts_and_every_gradient_leaf(cfg, f32):
    """Tolerances: both sides float32 at "highest"; 1e-5 of the loss and 2e-4
    of a leaf's largest gradient are summation order over 4 passes (the
    bfloat16 program reads 5e-3 and 3e-2: the test below)."""
    _model, fns, params, ref = program_of(cfg, label_smoothing=0.07)
    x, y = tokens(cfg)
    module = fns["module"]
    hs, head, gates, fused = module.apply({"params": params}, x, hidden=True)
    assert hs.shape == (4, 2, 96, 64) and gates.shape == (4, 2, 96) and head.shape == (64, 256)
    want_hs = R.hidden_states(ref, x, cfg)
    for t in range(4):
        assert close(hs[t], want_hs[t], 5e-5), t
    assert close(gates, R.gate_logits(ref, want_hs), 5e-5)
    logits = R.forward(ref, x, cfg)
    assert close(module.apply({"params": params}, x), logits[:, -1], 5e-5)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: _objective(fns, p, x, y), has_aux=True)(params)
    want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg, 0.07)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32), want, 1e-6)
    R.HEAD_BLOCK, block = 32, R.HEAD_BLOCK      # the fitting cuts at this size too
    try:
        assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32), want, 1e-6)
    finally:
        R.HEAD_BLOCK = block
    got_g = flat(grads)
    assert set(got_g) == set(want_g)
    for k, g in want_g.items():
        assert close(got_g[k], g, 2e-4), k
    assert float(jnp.abs(got_g["gate_w"]).max()) > 0 and float(jnp.abs(got_g["gate_b"])) > 0
    assert (float(metrics["count.loop.passes"]), float(metrics["count.loop.layer_calls"]),
            float(metrics["count.attn.layers"]), float(metrics["count.attn.fused"])) == (
        4.0, 8.0, 8.0, 0.0)
    p = R.exit_distribution(R.gate_logits(ref, want_hs))
    assert abs(float(metrics["gauge.loop.last_pass_mass"]) - float(p[-1].mean())) < 1e-6
    assert abs(float(metrics["gauge.loop.expected_passes"])
               - float((jnp.arange(1, 5)[:, None, None] * p).sum(0).mean())) < 1e-5
    assert 1.0 < float(metrics["gauge.loop.expected_passes"]) < 4.0


def test_evaluation_counts_the_last_passs_hits(cfg, f32):
    """The score is of z_R: the template's counts are the reference's hits of
    the LAST pass over all positions, and differ from an earlier pass's."""
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg, n=4, seed=9)
    _total, hits, n = R.stats(ref, x, y, cfg)
    got_hits, got_n = fns["eval_count"](params, {"x": x, "y": y})
    assert (int(got_hits), int(got_n)) == (int(hits), int(n)) == (int(hits), 384)
    _loss, metrics = _objective(fns, params, x, y, 0.0)
    assert abs(float(metrics["acc"]) - int(hits) / int(n)) < 1e-6
    per_pass = [R.head_token_stats(ref, h, y)[1] for h in R.hidden_states(ref, x, cfg)]
    assert any(bool(jnp.any(early != per_pass[-1])) for early in per_pass[:-1])


def test_one_pass_is_the_plain_stack(f32):
    """R = 1: no loop. The hidden state is N_f of the layers applied once,
    the exit distribution is the one pass with weight 1 (entropy nought), and
    the objective is plain cross entropy."""
    cfg = tiny_ouro(load_ouro_cfg(), passes=1)
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    hs, _head, _gates, _fused = fns["module"].apply({"params": params}, x, hidden=True)
    h = R.embed(ref, x)
    for i in (1, 2):
        h = R.layer(ref, i, h, cfg)
    assert hs.shape[0] == 1 and close(hs[0], R.final_norm(ref, h, cfg), 5e-5)
    loss, _metrics = _objective(fns, params, x, y, 0.0)
    ce, _hits = R.head_stats(ref, R.final_norm(ref, h, cfg), y)
    assert abs(float(loss) - float(ce) / y.size) < 1e-5 * float(loss)


def test_four_passes_are_the_unrolled_stack_of_4_x_l_layers_with_tied_weights(cfg, f32):
    """R = 4 over L = 2 held layers equals a plain model of 8 layers whose
    layer 2t + i has layer i's weights, with N_f after every second layer: the
    reference's layer function applied 8 times by hand, no loop anywhere."""
    _model, fns, params, ref = program_of(cfg)
    x, _y = tokens(cfg)
    unrolled = dict(ref)
    for t in range(1, 4):
        for i in (1, 2):
            for k in [k for k in ref if k.startswith(f"layer_{i}/")]:
                unrolled[k.replace(f"layer_{i}/", f"layer_{2 * t + i}/")] = ref[k]
    h = R.embed(unrolled, x)
    for j in range(1, 9):
        h = R.layer(unrolled, j, h, cfg)
        if j % 2 == 0:
            h = R.final_norm(unrolled, h, cfg)
    hs, _head, _gates, _fused = fns["module"].apply({"params": params}, x, hidden=True)
    assert close(hs[-1], h, 5e-5)
    # a second visit with other weights is another model
    other = dict(unrolled, **{"layer_3/ffn/w_down": unrolled["layer_2/ffn/w_down"]})
    g = R.embed(other, x)
    for j in range(1, 9):
        g = R.layer(other, j, g, cfg)
        if j % 2 == 0:
            g = R.final_norm(other, g, cfg)
    assert not close(g, h, 1e-2)


def test_a_layers_gradient_is_the_sum_over_its_four_visits(cfg, f32):
    """The program has one gradient leaf a held weight. Against the unrolled
    8-layer reference, whose every visit has a leaf of its own: the program's
    leaf is the sum of the four visits' leaves, and no visit alone is it."""
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    grads = flat(jax.grad(lambda p: _objective(fns, p, x, y)[0])(params))
    visits = {k: jnp.stack([v] * 4) for k, v in ref.items() if k.startswith("layer_")}

    def unrolled_loss(visits):
        h, hs = R.embed(ref, x), []
        for t in range(4):
            mine = dict(ref, **{k: v[t] for k, v in visits.items()})
            for i in (1, 2):
                h = R.layer(mine, i, h, cfg)
            h = R.final_norm(ref, h, cfg)
            hs.append(h)
        ce = jnp.stack([R.head_token_stats(ref, h, y, 0.07)[0] for h in hs])
        return jnp.mean(R.objective(R.exit_distribution(R.gate_logits(ref, hs)), ce, 0.05))

    by_visit = jax.grad(unrolled_loss)(visits)
    for k in ("layer_1/attn/w_q", "layer_2/ffn/w_down", "layer_1/norm_ffn_out"):
        assert close(grads[k], by_visit[k].sum(0), 2e-4), k
        assert all(not close(by_visit[k][t], by_visit[k].sum(0), 5e-2) for t in range(4)), k


def test_bfloat16_program_is_near_the_reference(cfg):
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    (loss, _), grads = jax.value_and_grad(lambda p: _objective(fns, p, x, y, 0.0),
                                          has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg)
    gap = abs(float(loss) - float(want)) / float(want)
    assert 1e-7 < gap < 5e-3
    # the float32 tests' 2e-4 would fail this program: its products are bfloat16
    worst = max(float(jnp.max(jnp.abs(flat(grads)[k] - g)) / jnp.max(jnp.abs(g)))
                for k, g in want_g.items())
    assert 2e-3 < worst < 0.1


def test_the_counts_at_the_published_widths():
    cfg = load_ouro_cfg()
    assert R.parameters(cfg) == cfg["parameters"] == 509_661_185
    d = R.dims(cfg)
    assert (d["D"], d["H"], d["Hkv"], d["d"], d["ffn"], d["layers"], d["passes"], d["vocab"],
            d["theta"], d["eps"]) == (2048, 16, 16, 128, 5632, 6, 4, 49152, 1e6, 1e-6)
    assert cfg["published"] == {"num_hidden_layers": 48} and cfg["reduced"] == ["num_hidden_layers"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert R.parameters(cfg) == 6 * layer + 2 * 49152 * 2048 + 2048 + 2049
    # the published model: 48 sets of weights and two tables: "2.6B"
    assert round((48 * layer + 2 * 49152 * 2048) / 1e9, 2) == 2.67
    per_token = R.forward_flops(cfg)
    # by hand: a visit's products 2 x (51.38 M - norms) + the causal half of
    # attention at 8,192 tokens; 24 visits; 4 heads and gates
    visit = 2 * (layer - 4 * 2048) + 2 * (8192 + 1) / 2 * 16 * 2 * 128
    assert abs(per_token - (24 * visit + 4 * 2 * (49152 * 2048 + 2048))) < 1e-6 * per_token
    assert 4.07e9 < per_token < 4.09e9 and 200e12 < 3 * 16384 * per_token < 201e12
    assert R.forward_flops(cfg, seq_len=4096) < per_token
    kernel = R.attention_kernel_flops(cfg, batch=2)
    product = 2 * 2 * 16 * (8192 * 8193 / 2) * 128
    assert kernel == {"forward": 2 * product, "backward": 5 * product}
    assert 0.549e12 < kernel["forward"] < 0.551e12    # a visit; 24 visits a step


def test_template_is_registered_and_passes_the_contract_harness():
    from rafiki_tpu.constants import TaskType
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.models import get_model_class

    cls = get_model_class("Ouro")
    fixed = {k: v.value for k, v in cls.get_knob_config().items()
             if isinstance(v, FixedKnob)}
    score, preds = test_model_class(
        cls, TaskType.LANGUAGE_MODELING.value, TRAIN, VAL, queries=[[5, 9, 3] * 8],
        knobs=dict(fixed, learning_rate=1e-3, label_smoothing=0.05))
    assert 0.0 <= score <= 1.0 and len(preds[0]) == 256
