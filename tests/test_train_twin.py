"""Train twin (docs/twin.md): analytic exactness, bit-identical
replay, validate polarities on synthetic journals, calibration
fail-loud, pregate forecasts, and the advisory placement hook."""

from __future__ import annotations

import json

import pytest

from rafiki_tpu.obs.journal import journal, read_dir
from rafiki_tpu.obs.twin.calibration import CalibrationError
from rafiki_tpu.obs.twin.train.calibration import (TrainCalibration,
                                                   TrainCalibrationError)
from rafiki_tpu.obs.twin.train.engine import (TrainTwinConfig, _assign,
                                              result_fingerprint, simulate)
from rafiki_tpu.obs.twin.train import pregate, validate as validate_mod


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _analytic_cal() -> TrainCalibration:
    """Hand-computable bundle: every (packing_key, width) the sweep
    touches has exactly ONE sample, so the simulation is arithmetic.

    pkA: width-2 packs, cold 2.0, warm 1.0, 3 epochs.
    pkB: width-1 packs, cold 3.0, warm 0.5, 2 epochs.
    """
    return TrainCalibration(
        steps={"pkA": {"2": [1.0]}, "pkB": {"1": [0.5]}},
        compiles={"pkA": {"2": [2.0]}, "pkB": {"1": [3.0]}},
        packs=[{"packing_key": "pkA", "k": 2, "epochs": 3},
               {"packing_key": "pkB", "k": 1, "epochs": 2}],
        sweep={"chips": 2, "trials_per_chip": 2, "n_trials": 6},
        cost={}, epoch_overhead_s=0.0, source="analytic")


def _analytic_trials():
    return ([{"id": f"a{i}", "packing_key": "pkA", "epochs": 3}
             for i in range(4)]
            + [{"id": f"b{i}", "packing_key": "pkB", "epochs": 2}
               for i in range(2)])


def _spread_cal() -> TrainCalibration:
    """Multi-sample distributions so different seeds draw differently."""
    return TrainCalibration(
        steps={"pkA": {"2": [round(0.5 + 0.1 * i, 3) for i in range(16)]}},
        compiles={"pkA": {"2": [4.0, 2.5]}},
        packs=[{"packing_key": "pkA", "k": 2, "epochs": 6}],
        sweep={"chips": 2, "trials_per_chip": 2, "n_trials": 8},
        cost={}, epoch_overhead_s=0.0, source="spread")


def _write_synthetic_journal(log_dir, step_scale: float = 1.0) -> None:
    """A captured 2-chip sweep as literal journal lines: per chip one
    pack (pk, width 2, 3 epochs) whose epochs are cold 2s + warm 1s +
    warm 1s back to back — measured wall exactly 4.0s, fitted
    epoch_overhead exactly 0."""
    rows = [
        {"ts": 1000.0, "kind": "mesh", "name": "sweep_started",
         "job_id": "j1", "chips": 2, "trials_per_chip": 2, "n_trials": 4},
    ]
    for chip in range(2):
        rows.append({"ts": 1000.5, "kind": "mesh", "name": "pack_formed",
                     "job_id": "j1", "chip": chip, "packing_key": "pk",
                     "k": 2, "fill_ratio": 1.0, "epochs": 3,
                     "trial_ids": [f"t{chip}a", f"t{chip}b"]})
        for ts, dt, cold in ((1002.0, 2.0 * step_scale, True),
                             (1003.0, 1.0 * step_scale, False),
                             (1004.0, 1.0 * step_scale, False)):
            rows.append({"ts": ts, "kind": "perf", "name": "step",
                         "key_hash": "kh", "dt": dt, "cold": cold,
                         "program_kind": "packed", "k": 2,
                         "packing_key": "pk"})
    with open(log_dir / "journal-test-1.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


# ---------------------------------------------------------------------------
# engine: analytic exactness + assignment mirror
# ---------------------------------------------------------------------------

def test_assignment_mirrors_mesh_round_robin():
    packs = _assign(_analytic_trials(), chips=2, k=2)
    # Bucket pkA first (first appearance), global cursor round-robins
    # its 4 rows a0..a3 across chips, then pkB's 2 rows continue.
    assert [(p["chip"], p["packing_key"], p["members"]) for p in packs] == [
        (0, "pkA", ["a0", "a2"]), (0, "pkB", ["b0"]),
        (1, "pkA", ["a1", "a3"]), (1, "pkB", ["b1"])]


def test_analytic_makespan_exact():
    cfg = TrainTwinConfig(chips=2, k=2, n_trials=6)
    res = simulate(_analytic_cal(), cfg, trials=_analytic_trials(), seed=0)
    # Per chip: pkA pack = 2.0 cold + 1.0 + 1.0 warm = 4.0s, then the
    # queued pkB pack = 3.0 cold + 0.5 warm = 3.5s -> 7.5s total, both
    # chips symmetric.
    assert res["status"] == "ok"
    assert res["makespan_s"] == 7.5
    assert res["completed"] == 6
    assert res["trials_per_hour"] == pytest.approx(6 / 7.5 * 3600)
    assert res["compile_s"] == 2 * (2.0 + 3.0)
    assert res["step_s"] == 2 * (1.0 + 1.0 + 0.5)
    assert res["utilization"] == 1.0


def test_cold_order_statistic_first_pack_pays_true_compile():
    # Two width-2 pkA packs on ONE chip: the first pays the slowest
    # cold sample (4.0 = the true compile), the second the 2.5 program
    # cache hit. Warm epochs pin to a single sample for exactness.
    cal = TrainCalibration(
        steps={"pkA": {"2": [1.0]}}, compiles={"pkA": {"2": [4.0, 2.5]}},
        packs=[], sweep={}, cost={}, epoch_overhead_s=0.0, source="t")
    packs = [{"chip": 0, "packing_key": "pkA", "epochs": 2,
              "members": ["x", "y"]},
             {"chip": 0, "packing_key": "pkA", "epochs": 2,
              "members": ["u", "v"]}]
    res = simulate(cal, TrainTwinConfig(chips=1, k=2), packs=packs, seed=0)
    assert res["makespan_s"] == (4.0 + 1.0) + (2.5 + 1.0)


def test_epoch_overhead_rides_every_epoch():
    cal = _analytic_cal()
    cal.epoch_overhead_s = 0.25
    cfg = TrainTwinConfig(chips=2, k=2, n_trials=6)
    res = simulate(cal, cfg, trials=_analytic_trials(), seed=0)
    # 5 epochs per chip (3 pkA + 2 pkB) x 0.25s on top of 7.5s.
    assert res["makespan_s"] == 7.5 + 5 * 0.25


def test_bit_identical_replay():
    cal = _spread_cal()
    cfg = TrainTwinConfig(chips=2, k=2, n_trials=8)
    a = simulate(cal, cfg, seed=7, record_events=True)
    b = simulate(cal, cfg, seed=7, record_events=True)
    assert a == b
    assert result_fingerprint(a) == result_fingerprint(b)
    c = simulate(cal, cfg, seed=8)
    assert c["event_log_sha1"] != a["event_log_sha1"]


def test_eviction_counts_completed_and_narrows_pack():
    cal = _spread_cal()
    cfg = TrainTwinConfig(chips=2, k=2, evict_prob=0.5)
    res = simulate(cal, cfg, seed=3)
    assert res["status"] == "ok"
    # Early-stopped members are verdicts, not losses: everything still
    # completes, and eviction must actually have fired at p=0.5.
    assert res["completed"] == res["trials"] == 4
    assert res["evicted"] > 0
    assert simulate(cal, cfg, seed=3) == res  # evict stream is seeded


def test_chaos_preempt_repacks_onto_survivor():
    cal = _spread_cal()
    cfg = TrainTwinConfig(chips=2, k=2, n_trials=8)
    spec = "scheduler.preempt:preempt:match=chip0:times=1"
    res = simulate(cal, cfg, seed=7, chaos_spec=spec)
    base = simulate(cal, cfg, seed=7)
    assert res["chaos_fired"] == 1
    assert res["chips_lost"] == [0]
    assert res["repacks"] > 0
    assert res["completed"] == res["trials"]  # nothing stranded
    assert res["makespan_s"] > base["makespan_s"]  # the loss cost time


def test_chaos_supervisor_host_loss_aborts():
    cal = _spread_cal()
    cfg = TrainTwinConfig(chips=4, k=2, n_trials=8, chips_per_host=2)
    res = simulate(cal, cfg, seed=0,
                   chaos_spec="host.loss:kill:match=g0h0:times=1")
    assert res["status"] == "supervisor_lost"
    ok = simulate(cal, cfg, seed=0,
                  chaos_spec="host.loss:kill:match=g0h1:times=1")
    assert ok["status"] == "ok"
    assert ok["hosts_lost"] == [1] and ok["chips_lost"] == [2, 3]


# ---------------------------------------------------------------------------
# calibration: fail-loud, scaling, roundtrip
# ---------------------------------------------------------------------------

def test_calibration_empty_dir_lists_both_missing_kinds(tmp_path):
    with pytest.raises(TrainCalibrationError) as ei:
        TrainCalibration.from_journal_dir(tmp_path)
    assert set(ei.value.missing) == {"perf/step", "mesh/pack_formed"}
    assert str(tmp_path) in str(ei.value)
    # Subclasses the serving error so shared handlers catch both.
    assert isinstance(ei.value, CalibrationError)


def test_calibration_partial_capture_names_the_absent_kind(tmp_path):
    with open(tmp_path / "journal-test-1.jsonl", "w") as f:
        f.write(json.dumps({"ts": 1.0, "kind": "perf", "name": "step",
                            "dt": 0.5, "cold": False, "k": 2,
                            "packing_key": "pk"}) + "\n")
    with pytest.raises(TrainCalibrationError) as ei:
        TrainCalibration.from_journal_dir(tmp_path)
    assert ei.value.missing == ["mesh/pack_formed"]


def test_scaled_rejects_unknown_segment():
    with pytest.raises(ValueError, match="step"):
        _analytic_cal().scaled({"forward": 2.0})


def test_calibration_roundtrip_and_version_gate(tmp_path):
    cal = _analytic_cal()
    path = tmp_path / "cal.json"
    cal.save(path)
    loaded = TrainCalibration.load(path)
    assert loaded.steps == cal.steps
    assert loaded.compiles == cal.compiles
    assert loaded.sweep == cal.sweep
    doc = json.loads(path.read_text())
    doc["train_calibration_version"] = 99
    with pytest.raises(ValueError, match="99"):
        TrainCalibration.from_dict(doc)


# ---------------------------------------------------------------------------
# validate: both polarities on synthetic journals
# ---------------------------------------------------------------------------

def test_validate_correct_calibration_passes(tmp_path):
    _write_synthetic_journal(tmp_path)
    doc = validate_mod.validate(tmp_path, seed=0)
    # Measured wall: last epoch end 1004.0 minus first epoch start
    # (1002.0 - 2.0) = 4.0s; replayed packs cost exactly 2+1+1 per
    # chip with zero fitted overhead -> both errors exactly 0.
    assert doc["measured"]["wall_s"] == 4.0
    assert doc["measured"]["trials"] == 4
    assert doc["predicted"]["wall_s"] == 4.0
    assert doc["tph_err"] == 0.0 and doc["wall_err"] == 0.0
    assert doc["ok"] is True
    # Byte-identical replay: the artifact hashes the same event log.
    again = validate_mod.validate(tmp_path, seed=0)
    assert again["event_log_sha1"] == doc["event_log_sha1"]


def test_validate_doctored_2x_step_time_fails(tmp_path):
    _write_synthetic_journal(tmp_path)
    doc = validate_mod.validate(tmp_path, seed=0,
                                scales={"step": 2.0})
    # Warm epochs double (cold unscaled): predicted 2+2+2=6.0 vs
    # measured 4.0 -> 50% wall error, far over the 25% gate.
    assert doc["predicted"]["wall_s"] == 6.0
    assert doc["wall_err"] == 0.5
    assert doc["ok"] is False


def test_validate_empty_dir_raises_calibration_error(tmp_path):
    with pytest.raises(TrainCalibrationError):
        validate_mod.validate(tmp_path, seed=0)


# ---------------------------------------------------------------------------
# pregate: autoscale forecast + veto, chaos forecast
# ---------------------------------------------------------------------------

def test_pregate_forecast_deterministic_and_gain_rides_back():
    cal = _spread_cal()
    a = pregate.forecast(1, 4, calibration=cal, seed=0)
    assert a == pregate.forecast(1, 4, calibration=cal, seed=0)
    assert a["veto"] is False
    assert a["delta_trials_per_hour"] > 0
    assert a["target_forecast"]["makespan_s"] < a["baseline"]["makespan_s"]


def test_pregate_vetoes_pointless_scale_up():
    # One single trial: a second chip cannot speed up one pack, so the
    # predicted gain is 0% < the 2% bar -> veto, with a reason.
    cal = _spread_cal()
    f = pregate.forecast(1, 2, calibration=cal, n_trials=1, seed=0)
    assert f["veto"] is True
    assert "trials/hour" in f["veto_reason"]


def test_pregate_lane_filter():
    cal = _spread_cal()
    fn = pregate.sweep_chip_pregate(calibration=cal)
    assert fn("sweep", 1, 4) is not None
    assert fn("serving", 1, 4) is None
    assert fn("sweep", 2, 2) is None


def test_chaos_forecast_only_on_sweep_sites():
    cal = _spread_cal()
    assert pregate.chaos_forecast("gateway.admit:drop:p=0.5",
                                  calibration=cal) is None
    cf = pregate.chaos_forecast(
        "scheduler.preempt:preempt:match=chip0:times=1",
        calibration=cal, chips=2, seed=0)
    assert cf["chaos_fired"] == 1
    assert cf["delta_makespan_s"] > 0


# ---------------------------------------------------------------------------
# placement hook: advisory consultation, journaled
# ---------------------------------------------------------------------------

def test_placement_consult_journals_recommendation(tmp_path):
    cap = tmp_path / "cap"
    cap.mkdir()
    _write_synthetic_journal(cap)
    out = tmp_path / "out"
    out.mkdir()
    journal.configure(out, role="test")
    try:
        from rafiki_tpu.obs.twin.train import placement
        rec = placement.consult(job_id="j1", chips=2, k=2,
                                budget={"MODEL_TRIAL_COUNT": 4},
                                log_dir=str(cap), seed=0)
    finally:
        journal.close()
    assert rec["best_k"] and rec["best_split"]["chips"] >= 1
    assert rec["calibration_source"] == str(cap)
    recs = [r for r in read_dir(out)
            if r.get("kind") == "twin" and r.get("name") == "placement"]
    assert len(recs) == 1
    assert recs[0]["advisory"] is True
    assert recs[0]["recommendation"]["best_split"] == rec["best_split"]


def test_mesh_sweep_consults_twin_at_admission(tmp_path, monkeypatch):
    """RAFIKI_TWIN_PLACEMENT end to end: a real mini sweep whose log
    dir is pre-populated with a prior capture journals an advisory
    twin/placement record at admission, then runs untouched — and its
    own mesh/pack_formed + packing-key-stamped perf/step records make
    the NEXT calibration (the closed loop the twin rides)."""
    from rafiki_tpu.chaos.scenarios import FF_SOURCE, TRAIN, VAL
    from rafiki_tpu.scheduler import MeshSweepScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore

    _write_synthetic_journal(tmp_path)  # prior capture -> calibration
    monkeypatch.setenv("RAFIKI_LOG_DIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_TWIN_PLACEMENT", "1")
    journal.configure(tmp_path, role="test")
    try:
        store = MetaStore(tmp_path / "meta.sqlite3")
        params = ParamsStore(tmp_path / "params")
        model = store.create_model("twinff", "IMAGE_CLASSIFICATION", None,
                                   FF_SOURCE, "ChaosFF")
        job = store.create_train_job("twinhook", "IMAGE_CLASSIFICATION",
                                     None, TRAIN, VAL,
                                     {"MODEL_TRIAL_COUNT": 2})
        store.create_sub_train_job(job["id"], model["id"])
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=2, trials_per_chip=1, advisor_kind="random")
    finally:
        journal.close()
    assert result.status == "COMPLETED", result.errors
    recs = read_dir(tmp_path)
    placements = [r for r in recs if r.get("kind") == "twin"
                  and r.get("name") == "placement"
                  and r.get("job_id") == job["id"]]
    assert len(placements) == 1
    assert placements[0]["advisory"] is True
    assert placements[0].get("error") is None
    assert placements[0]["recommendation"]["best_split"]
    # Satellite records the twin itself feeds on, from the real sweep:
    formed = [r for r in recs if r.get("kind") == "mesh"
              and r.get("name") == "pack_formed"
              and r.get("job_id") == job["id"]]
    assert formed and all(r["trial_ids"] and r["packing_key"]
                          for r in formed)
    stamped = [r for r in recs if r.get("kind") == "perf"
               and r.get("name") == "step" and r.get("packing_key")
               and r.get("program_kind") == "packed"]
    assert stamped


def test_train_twin_validates_against_a_captured_mesh_sweep(tmp_path,
                                                            monkeypatch,
                                                            capsys):
    """Capture -> calibrate -> validate (both polarities) -> what-if, on
    the journals of a REAL mini mesh sweep (2 virtual chips x k=2 packed
    trials, one ``propose_batch(4)`` draft) rather than synthetic ones:
    replaying the captured packs lands predicted-vs-measured trials/hour
    and wall inside tolerance (exit 0); with both epoch segments doubled
    the same gate FAILS (exit 1) — the mini sweep's epochs are
    compile-dominated at this size, so the doctored polarity scales
    both; the pure 2x step-time polarity is pinned above on synthetic
    journals where the step cost dominates. The what-if sweep over the
    captured calibration is byte-identical under one seed and every row
    carries its event-log fingerprint."""
    from rafiki_tpu.chaos.scenarios import FF_SOURCE, TRAIN, VAL
    from rafiki_tpu.obs import cli
    from rafiki_tpu.scheduler import MeshSweepScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore

    log_dir = tmp_path / "obs"
    monkeypatch.setenv("RAFIKI_LOG_DIR", str(log_dir))
    journal.configure(log_dir, role="sweep")
    try:
        store = MetaStore(tmp_path / "meta.sqlite3")
        params = ParamsStore(tmp_path / "params")
        model = store.create_model("twinff", "IMAGE_CLASSIFICATION", None,
                                   FF_SOURCE, "ChaosFF")
        job = store.create_train_job("traintwin", "IMAGE_CLASSIFICATION",
                                     None, TRAIN, VAL,
                                     {"MODEL_TRIAL_COUNT": 4})
        store.create_sub_train_job(job["id"], model["id"])
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    finally:
        journal.close()
    assert result.status == "COMPLETED", result.errors

    cal = TrainCalibration.from_journal_dir(log_dir)
    assert len(cal.to_dict()["packs"]) >= 2

    twin = ["--dir", str(log_dir), "--json", "twin", "train"]
    capsys.readouterr()
    assert cli.main(twin + ["validate", "--seed", "7"]) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True
    assert cli.main(twin + ["validate", "--seed", "7", "--scale", "step=2.0",
                            "--scale", "compile=2.0"]) == 1
    assert json.loads(capsys.readouterr().out)["ok"] is False

    sweep = twin + ["sweep", "--seed", "7", "--grid", "chips=1,2",
                    "--grid", "pack=1,2", "--best-k", "--split"]
    assert cli.main(sweep) == 0
    first = capsys.readouterr().out
    assert cli.main(sweep) == 0
    assert capsys.readouterr().out == first
    doc = json.loads(first)
    assert len(doc["rows"]) == 4
    assert all(r["event_log_sha1"] for r in doc["rows"])
    assert len(doc["best_k"]) >= 1 and doc["split"]["best"] is not None
