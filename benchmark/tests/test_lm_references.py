"""The language-model reference against the program on the CPU at a tiny
size, as ``test_references.py`` does for the image configurations: the
benchmark's copy of the token generator, the initial parameters, the first
step's loss and update signs, and the FLOP count. (The layer-by-layer
comparisons are tier-1: ``tests/test_kimi_linear.py``.)"""

import numpy as np
from lm_tiny import load_lm_cfg, template_knobs, tiny_lm

import check
import lm_check
import lm_datagen


def test_the_token_generator_is_the_programs():
    from rafiki_tpu.model.dataset import dataset_utils

    cfg = tiny_lm(load_lm_cfg())
    ds = dataset_utils.load(lm_datagen.token_uri(cfg, 8, 31))
    x, y = lm_datagen.tokens_of(cfg, 8, 31)
    np.testing.assert_array_equal(ds.x, x)
    np.testing.assert_array_equal(ds.y, y)
    assert ds.classes == cfg["vocab_size"]


def test_the_reference_follows_a_trial_of_the_program():
    """One trial of the template on the run's data against the reference's
    own: same first loss, update signs within a few percent (bfloat16 against
    float32), the same score of the stored parameters."""
    from drivers import sweep as sweep_driver
    from conftest import BENCH
    from rafiki_tpu.model.base import load_model_class

    cfg, seed = tiny_lm(load_lm_cfg()), 12345
    model_seed = sweep_driver.model_seed(seed)
    cls = load_model_class(sweep_driver.model_source(BENCH.parent, cfg, seed), "BenchModel")
    knobs = template_knobs(cfg, seed=model_seed, learning_rate=3e-4, label_smoothing=0.04)
    ref = lm_check.Reference(cfg, seed, model_seed)
    train_seed, val_seed = 2 * seed, 2 * seed + 1
    model = cls(**knobs)
    model.train(lm_datagen.token_uri(cfg, lm_check.first_step_rows(cfg), train_seed))
    stored = check.parse_params_blob(model.dump_parameters())
    p1, losses = ref.train(knobs, first_step=True)
    flips, _leaf = check.first_step_flips(
        ref.init_params(), {k: check.bf16_round(v) for k, v in p1.items()}, stored)
    assert flips < 0.08
    model = cls(**knobs)
    model.train(lm_datagen.token_uri(cfg, int(cfg["train_n"]), train_seed))
    score = model.evaluate(lm_datagen.token_uri(cfg, int(cfg["eval_n"]), val_seed))
    acc, _nll = ref.evaluate(check.parse_params_blob(model.dump_parameters()))
    assert abs(score - acc) <= 0.011


def test_the_reference_in_pieces_is_the_reference_whole():
    """``lm_check`` runs the reference a layer at a time (a kind of layer one
    program, the chain rule by hand, a block of sequences at a time): the
    loss and every gradient leaf are ``jax.value_and_grad`` of the
    reference's own ``loss``."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_lm(load_lm_cfg())
    ref = lm_check.Reference(cfg, 77, 5)
    ref.opts = dict(ref.opts, seq_block=1)      # a batch of two in two blocks, their sums added
    p = jax.tree.map(jnp.asarray, ref.init_params())
    X, Y = ref.first_set
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(ref.mod.loss)(p, jnp.asarray(X), jnp.asarray(Y),
                                                       cfg, 0.05)
    loss2, grads2 = ref.loss_and_grads(p, X, Y, 0.05)
    # a kind of layer is ONE program, whatever the number of its layers
    assert {k[0] for k in ref.pieces().exe} == {
        "init", "add", "embed.vjp", "head.vjp", "kda.ffn.fwd", "kda.ffn.vjp",
        "kda.moe.fwd", "kda.moe.vjp", "mla.moe.fwd", "mla.moe.vjp"}
    assert len(ref.pieces().exe) == 10
    assert abs(float(loss) - loss2) <= 1e-6 * float(loss)
    assert set(grads2) == set(grads)
    for k in grads:
        scale = float(np.max(np.abs(grads[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(grads2[k]), np.asarray(grads[k]),
                                   rtol=2e-4, atol=2e-5 * scale, err_msg=k)
    acc, nll = ref.evaluate(ref.init_params())
    with jax.default_matmul_precision("highest"):
        ce, hits, n = ref.mod.stats(p, *map(jnp.asarray, ref.val_set), cfg)
    assert acc == float(hits) / n and abs(nll - float(ce) / n) <= 1e-6 * nll


def test_forward_flops_at_the_published_widths():
    from references import kimi_linear as R

    cfg = load_lm_cfg()
    assert R.parameters(cfg) == cfg["parameters"] == 602_434_432
    assert 37e12 < 3 * 2 * cfg["seq_len"] * R.forward_flops(cfg) < 38.5e12
