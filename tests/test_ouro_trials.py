"""The third language-model template through the normal path: scheduler ->
serial lane -> train / evaluate / dump -> ParamsStore -> a fresh instance,
exactly as the first two (tests/test_kimi_linear_trials.py,
tests/test_lfm2_moe_trials.py), what trials share one program, and what the
three templates share of the base. Shared fixtures: tests/ouro_common.py."""

import numpy as np
import pytest

from ouro_common import (  # noqa: F401 (fixtures)
    cfg, check, K, M, program_of, REPO, small_class, telemetry, template_knobs, TRAIN, VAL)


def test_a_trial_trains_scores_counts_and_reloads(cfg):
    """train -> evaluate -> staged dump -> a fresh instance gives the score;
    the epoch is a leaf span with its tags; the loop's counts land in counters
    and its gauges are a distribution's."""
    telemetry.reset()
    Small = small_class(cfg, 11)
    knobs = template_knobs(cfg, seed=11)
    model = Small(**knobs)
    model.train(TRAIN)
    score = model.evaluate(VAL)
    spans = [s for s in telemetry.span_records() if s["name"] == "train.epoch"]
    assert len(spans) == 1 and spans[0]["leaf"] and spans[0]["tags"]["steps"] == 4
    counters = telemetry.snapshot()["counters"]
    # four steps of four passes over two held layers; the CPU runs no kernel
    assert (counters["loop.passes"], counters["loop.layer_calls"]) == (16, 32)
    assert (counters["attn.layers"], counters["attn.fused"]) == (32, 0)
    assert 1.0 < telemetry.get_gauge("loop.expected_passes") < 4.0
    assert 0.0 < telemetry.get_gauge("loop.last_pass_mass") < 1.0
    blob = model.dump_parameters()
    model._loop.release_to_host(True)
    assert model._loop.state is None and model.dump_parameters() == blob
    stored = check.parse_params_blob(blob)
    assert stored["head"].shape == (64, 256) and stored["embed"].shape == (256, 64)
    assert stored["gate_w"].shape == (64,) and stored["gate_b"].shape == ()
    assert sorted(k for k in stored if k.endswith("ffn/w_up")) == [
        "layer_1/ffn/w_up", "layer_2/ffn/w_up"]     # the stack once, whatever the passes
    _m, _fns, _params, ref = program_of(cfg, seed=11)
    for name in ("embed", "layer_2/attn/w_o", "gate_w"):    # trained: model and gate together
        assert not np.array_equal(stored[name], check.bf16_round(ref[name])), name
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
    # the passes and the entropy's weight are the program's: another key
    _c, fc, _p, _r = program_of(dict(cfg, knobs=dict(cfg["knobs"], total_ut_steps={"fixed": 3})))
    _d, fd, _p, _r = program_of(dict(cfg, knobs=dict(cfg["knobs"],
                                                      exit_entropy_beta={"fixed": 0.0})))
    assert len({fa["program_key"], fc["program_key"], fd["program_key"]}) == 3


def test_the_three_templates_share_the_base_and_only_two_the_experts():
    """``BlockedLossLm`` is what every language-model template needs; the
    sparse-expert half (the top-k knob, the loads, the ``moe.*`` counters) is
    ``SparseExpertLm``'s, which a dense template does not have."""
    from rafiki_tpu.models.lfm2_moe import Lfm2Moe

    for template in (K.KimiLinear, Lfm2Moe, M.Ouro):
        assert issubclass(template, K.BlockedLossLm)
        assert template._loop_fns is K.BlockedLossLm._loop_fns
        assert not template.packable() and not template.epoch_program()
    assert issubclass(K.KimiLinear, K.SparseExpertLm) and issubclass(Lfm2Moe, K.SparseExpertLm)
    assert not issubclass(M.Ouro, K.SparseExpertLm) and not hasattr(M.Ouro, "TOP_K_KNOB")
    knobs = M.Ouro.get_knob_config()
    assert not [k for k in knobs if "expert" in k or "moe" in k]
    assert {"total_ut_steps", "exit_entropy_beta", "head_dim"} <= set(knobs)
