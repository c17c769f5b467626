"""The second language-model template through the normal path: scheduler ->
serial lane -> train / evaluate / dump -> ParamsStore -> a fresh instance,
exactly as the first (tests/test_kimi_linear_trials.py), and what trials
share one program. Shared fixtures: tests/lfm2_moe_common.py."""

import numpy as np
import pytest

from lfm2_moe_common import (  # noqa: F401 (fixtures)
    cfg, check, program_of, REPO, small_class, telemetry, template_knobs, TRAIN, VAL)


def test_a_trial_trains_scores_counts_and_reloads(cfg):
    """train -> evaluate -> staged dump -> a fresh instance gives the score;
    the epoch is a leaf span with its tags; the counts land in counters; the
    bias the router selects by is stored as it was drawn."""
    telemetry.reset()
    Small = small_class(cfg, 11)
    knobs = template_knobs(cfg, seed=11)
    model = Small(**knobs)
    model.train(TRAIN)
    score = model.evaluate(VAL)
    spans = [s for s in telemetry.span_records() if s["name"] == "train.epoch"]
    assert len(spans) == 1 and spans[0]["leaf"] and spans[0]["tags"]["steps"] == 4
    counters = telemetry.snapshot()["counters"]
    assert counters["moe.slots_total"] == 4 * 2 * 96 * 4 * 4
    assert 0 < counters["moe.slots_held"] < counters["moe.slots_total"]
    assert counters["moe.slots_held"] <= counters["moe.rows_room"] < counters["moe.slots_total"]
    assert telemetry.get_gauge("moe.held_load_max_over_mean") >= 1.0
    assert (counters["attn.layers"], counters["attn.fused"]) == (4, 0)   # a step each; the CPU
    assert counters["conv.layers"] == 20
    blob = model.dump_parameters()
    model._loop.release_to_host(True)
    assert model._loop.state is None and model.dump_parameters() == blob
    stored = check.parse_params_blob(blob)
    assert "head" not in stored and stored["embed"].shape == (256, 64)
    _m, _fns, _params, ref = program_of(cfg, seed=11)
    for layer in (3, 4, 5, 6):
        name = f"layer_{layer}/moe/expert_bias"
        np.testing.assert_array_equal(stored[name], check.bf16_round(ref[name]))
    assert not np.array_equal(stored["embed"], check.bf16_round(ref["embed"]))
    fresh = Small(**knobs)
    fresh.load_parameters(blob)
    assert fresh.evaluate(VAL) == pytest.approx(score, abs=0.006)
    probs = np.asarray(fresh.predict([[5, 9, 3] * 32]))
    assert probs.shape == (1, 256) and abs(probs.sum() - 1.0) < 1e-3


def test_a_sweep_through_the_scheduler_stores_what_reproduces_the_score(cfg, tmp_path):
    from drivers import sweep as sweep_driver
    from rafiki_tpu.config import Config, set_config
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore

    set_config(Config(data_dir=tmp_path / "data").ensure_dirs())
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    source = sweep_driver.model_source(REPO, cfg, 17)
    model = store.create_model("BenchModel", "LANGUAGE_MODELING", None, source, "BenchModel")
    job = store.create_train_job("lm", "LANGUAGE_MODELING", None, TRAIN, VAL,
                                 {"MODEL_TRIAL_COUNT": 2})
    store.create_sub_train_job(job["id"], model["id"])
    before = telemetry.get_counter("worker.packed_trials")
    result = LocalScheduler(store, params).run_train_job(
        job["id"], n_workers=1, advisor_kind="gp", trial_pack=1)
    assert result.status == "COMPLETED" and not result.errors
    assert telemetry.get_counter("worker.packed_trials") == before
    done = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(done) == 2
    cls = load_model_class(source, "BenchModel")
    for t in done:
        assert 3e-5 <= t["knobs"]["learning_rate"] <= 1e-3
        fresh = cls(**t["knobs"])
        fresh.load_parameters(params.load(t["params_id"]))
        # (stored in bfloat16: a near-tie among 384 scored tokens may flip)
        assert fresh.evaluate(VAL) == pytest.approx(t["score"], abs=0.006)
    store.close()


def test_label_smoothing_and_learning_rate_share_one_program(cfg):
    a, fa, _p, _r = program_of(cfg, label_smoothing=0.0, learning_rate=1e-4)
    b, fb, _p, _r = program_of(cfg, label_smoothing=0.1, learning_rate=1e-3)
    assert fa["program_key"] == fb["program_key"]
    assert fa["hyper"]["label_smoothing"] == 0.0 and fb["hyper"]["label_smoothing"] == 0.1
    Small = small_class(cfg)
    assert not Small.packable() and not Small.epoch_program()


def test_layer_types_must_name_the_layers_that_are_built(cfg):
    knobs = template_knobs(cfg, seed=0)
    with pytest.raises(ValueError, match="layer_types"):
        small_class(dict(cfg, knobs=dict(cfg["knobs"], layer_types={"fixed": "conv,conv"})))(
            **dict(knobs, layer_types="conv,conv")).module_config()
