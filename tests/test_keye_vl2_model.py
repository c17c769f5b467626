"""The whole sparse-attention template against its plain reference at a
small size: the loss with the indexer's, the counts and every gradient leaf;
a trial through the normal path (train, evaluate, staged dump, a fresh
instance); and the dev harness. Shared fixtures: tests/keye_vl2_common.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keye_vl2_common import (  # noqa: F401 (fixtures)
    cfg, check, close, f32, flat, program_of, R, small_class, telemetry, template_knobs, tokens)

TRAIN = "synthetic://tokens?vocab=256&n=8&len=64&seed=20&follow=0.5"
VAL = "synthetic://tokens?vocab=256&n=4&len=64&seed=21&follow=0.5"


def test_loss_counts_and_every_gradient_leaf(cfg, f32):
    """The step's loss (cross entropy with label smoothing plus the indexer's
    KL, each a mean over tokens) and every gradient leaf against
    ``jax.value_and_grad`` of the reference's own objective; the evaluation's
    hits; the step's counts."""
    _m, fns, params, ref = program_of(cfg, label_smoothing=0.07)
    x, y = tokens(cfg)
    batch = {"x": x, "y": y}
    (loss, metrics), grads = jax.value_and_grad(fns["loss_fn"], has_aux=True)(
        params, batch, None, {"label_smoothing": jnp.float32(0.07)})
    want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg, 0.07)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=16), want, 1e-6)
    ce, index_loss, hits, n = R.stats(ref, x, y, cfg, 0.07)
    assert abs(float(metrics["index_loss"]) - float(index_loss) / n) < 1e-5
    assert float(index_loss) > 0.0
    got_hits, got_n = fns["eval_count"](params, batch)
    assert (int(got_hits), int(got_n)) == (int(hits), int(n))
    got_g = flat(grads)
    assert set(got_g) == set(want_g)
    scale = max(float(jnp.max(jnp.abs(v))) for v in want_g.values())
    for k, g in want_g.items():
        assert close(got_g[k], g, 2e-4) or \
            float(jnp.max(jnp.abs(got_g[k] - g))) < 1e-6 * scale, k
    # two layers of a batch of two: keys, tiles (one a layer: no tile divides 64)
    T = int(cfg["seq_len"])
    assert float(metrics["count.dsa.keys_selected"]) == 2 * 2 * R._selected_keys(T, 16)
    assert (float(metrics["count.dsa.layers"]), float(metrics["count.dsa.fused"])) == (2.0, 0.0)
    # heads of 16: the blocked code, differentiated as a whole; nothing named to keep
    assert float(metrics["count.dsa.forward_kept"]) == 0.0
    assert float(metrics["count.dsa.tiles_visited"]) == float(metrics["count.dsa.tiles_total"]) == 4
    assert float(metrics["count.moe.slots_total"]) == x.size * 4 * 2


def test_a_trial_trains_scores_counts_and_reloads(cfg):
    """train -> evaluate -> staged dump -> a fresh instance gives the score;
    the epoch is a leaf span with its tags; the counts land in counters; the
    indexer's loss is logged beside the loss."""
    telemetry.reset()
    Small = small_class(cfg, 11)
    knobs = template_knobs(cfg, seed=11)
    model = Small(**knobs)
    model.train(TRAIN)
    score = model.evaluate(VAL)
    spans = [s for s in telemetry.span_records() if s["name"] == "train.epoch"]
    assert len(spans) == 1 and spans[0]["leaf"] and spans[0]["tags"]["steps"] == 4
    counters = telemetry.snapshot()["counters"]
    assert counters["dsa.layers"] == 4 * 2 and counters["dsa.fused"] == 0
    assert counters["dsa.keys_selected"] == 4 * 2 * 2 * R._selected_keys(64, 16)
    assert 0 < counters["moe.slots_held"] < counters["moe.slots_total"]
    blob = model.dump_parameters()
    model._loop.release_to_host(True)
    assert model._loop.state is None and model.dump_parameters() == blob
    stored = check.parse_params_blob(blob)
    assert stored["head"].shape == (64, 256) and stored["layer_2/attn/index_q"].shape == (64, 32)
    _m, _fns, _params, ref = program_of(cfg, seed=11)
    for name in ("layer_1/attn/index_w", "layer_2/attn/w_q", "embed"):
        assert not np.array_equal(stored[name], check.bf16_round(ref[name])), name
    fresh = Small(**knobs)
    fresh.load_parameters(blob)
    assert fresh.evaluate(VAL) == pytest.approx(score, abs=0.006)
    probs = np.asarray(fresh.predict([[5, 9, 3] * 16]))
    assert probs.shape == (1, 256) and abs(probs.sum() - 1.0) < 1e-3


def test_the_dev_harness_runs_the_template():
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.model.knobs import FixedKnob
    from rafiki_tpu.models.keye_vl2 import KeyeVl2

    fixed = {k: v.value for k, v in KeyeVl2.get_knob_config().items()
             if isinstance(v, FixedKnob)}
    score, _predictions = test_model_class(
        KeyeVl2, "LANGUAGE_MODELING", TRAIN, VAL, queries=[[5, 9, 3] * 8],
        knobs=dict(fixed, learning_rate=1e-3, label_smoothing=0.05))
    assert 0.0 <= score <= 1.0
