"""The language-model template through the normal path: scheduler -> serial
lane -> train / evaluate / dump -> ParamsStore -> a fresh instance, and what
trials share one program. Shared fixtures: tests/kimi_linear_common.py."""

import numpy as np
import pytest

from kimi_linear_common import (  # noqa: F401 (fixtures)
    cfg, dataset_utils, program_of, REPO, small_class, telemetry,
    template_knobs, TRAIN, VAL)


def test_synthetic_tokens_is_seeded_and_next_token_labelled():
    import lm_datagen

    ds = dataset_utils.load(TRAIN)
    assert ds.x.shape == ds.y.shape == (8, 96) and ds.classes == 256 and ds.mask is None
    np.testing.assert_array_equal(ds.x[:, 1:], ds.y[:, :-1])
    x, y = lm_datagen.synthetic_tokens(256, 8, 96, 20, 0.5)
    np.testing.assert_array_equal(x, ds.x)
    np.testing.assert_array_equal(y, ds.y)
    other = dataset_utils.load(VAL)
    assert not np.array_equal(other.x[:4], ds.x[:4])
    assert lm_datagen.token_uri({"vocab_size": 256, "seq_len": 96, "follow": 0.5}, 8, 20) == TRAIN


def test_a_trial_trains_scores_counts_and_reloads(cfg):
    """train -> evaluate -> staged dump -> a fresh instance gives the score;
    the epoch is a leaf span with its tags; the expert counts land in
    counters; a staged dump is the unstaged blob byte for byte."""
    telemetry.reset()
    Small = small_class(cfg, 11)
    knobs = template_knobs(cfg, seed=11)
    model = Small(**knobs)
    model.train(TRAIN)
    score = model.evaluate(VAL)
    spans = [s for s in telemetry.span_records() if s["name"] == "train.epoch"]
    assert len(spans) == 1 and spans[0]["leaf"]
    assert spans[0]["tags"]["steps"] == 4 and spans[0]["tags"]["cold"] in (True, False)
    counters = telemetry.snapshot()["counters"]
    assert counters["moe.slots_total"] == 4 * 2 * 96 * 4 * 4
    assert 0 < counters["moe.slots_held"] < counters["moe.slots_total"]
    assert counters["moe.slots_held"] <= counters["moe.rows_room"] < counters["moe.slots_total"]
    assert telemetry.get_gauge("moe.held_load_max_over_mean") >= 1.0
    assert (counters["mla.layers"], counters["mla.fused"]) == (4, 0)   # a step each; the CPU
    assert (counters["kda.layers"], counters["kda.fused"]) == (16, 0)
    blob = model.dump_parameters()
    assert counters.get("persist.blob_bytes", 0) == 0 < telemetry.get_counter("persist.blob_bytes")
    model.release_train_state()            # the CPU reports no limit: nothing staged
    assert model._loop.state is not None and model._loop.host_copy is None
    model._loop.release_to_host(True)
    assert model._loop.state is None
    assert model.dump_parameters() == blob
    assert telemetry.get_counter("persist.serial_from_staged_copy") == 1
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
    assert not small_class(cfg).packable()
