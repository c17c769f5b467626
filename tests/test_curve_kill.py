"""Learning-curve early-kill + speculative scoring (docs/early_kill.md).

The contract under test:
  * **off polarity is bit-exact** — with both ``RAFIKI_CURVE_KILL`` and
    ``RAFIKI_CURVE_SPECULATE`` off, ``CurveCoordinator.from_env()`` is
    None, a disabled coordinator threaded through a GP loop leaves the
    proposal stream byte-identical to a loop with no coordinator at
    all, and the journal carries zero curve-plane records;
  * **serial kill end to end** — a doomed trial dies at the first
    eligible epoch boundary with an ERRORED row, a predicted-score
    consolation feedback charged to the doomed bucket, and
    ``advisor/predict`` + ``advisor/kill`` records that reconcile;
  * **speculation** — in-flight curves are fed to the engine exactly
    once in sorted-hash order, a later real score journals the
    correction, and PR 15 rehydration replays uncorrected speculations
    to byte-identical proposals (and would diverge without them).
"""

import json
import math

import pytest

from rafiki_tpu.advisor.curve import KillConfig, fit_curve
from rafiki_tpu.advisor.speculative import CurveCoordinator
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob, IntegerKnob
from rafiki_tpu.obs.journal import journal, read_dir
from rafiki_tpu.obs.search.ledger import search_ledger

CURVE_RECORD_NAMES = {"predict", "kill", "speculate", "correct",
                      "false_kill"}


@pytest.fixture
def journaled(tmp_path):
    search_ledger.reset()
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()
        search_ledger.reset()


def _knob_config(lr_max=1e-1):
    return {"lr": FloatKnob(1e-4, lr_max, is_exp=True),
            "units": IntegerKnob(4, 64),
            "b": FixedKnob(8)}


def _saturating(final, e, tau=2.0):
    return final * (1.0 - math.exp(-(e + 1) / tau))


def _curve_records(log_dir):
    return [r for r in read_dir(log_dir)
            if r.get("kind") == "advisor"
            and r.get("name") in CURVE_RECORD_NAMES]


# -- config + fit ------------------------------------------------------------


def test_from_env_off_is_none(monkeypatch):
    for var in ("RAFIKI_CURVE_KILL", "RAFIKI_CURVE_SPECULATE"):
        monkeypatch.delenv(var, raising=False)
    assert CurveCoordinator.from_env() is None
    monkeypatch.setenv("RAFIKI_CURVE_KILL", "1")
    coord = CurveCoordinator.from_env()
    assert coord is not None and coord.config.enabled
    assert not coord.config.speculate
    monkeypatch.delenv("RAFIKI_CURVE_KILL")
    monkeypatch.setenv("RAFIKI_CURVE_SPECULATE", "1")
    coord = CurveCoordinator.from_env()
    assert coord is not None and coord.config.speculate
    assert not coord.config.enabled


def test_fit_extrapolates_saturating_curve():
    pts = [(e, _saturating(0.9, e)) for e in range(6)]
    fit = fit_curve(pts, 16)
    assert fit is not None
    assert abs(fit.predicted_final - 0.9) < 0.1
    assert fit.lo <= fit.predicted_final <= fit.hi
    rec = fit.to_record()
    for key in ("family", "decay", "n_obs", "rmse", "predicted",
                "band", "lo", "hi", "horizon"):
        assert key in rec, key


def test_should_kill_gates_warmup_minobs_best_and_margin():
    cfg = KillConfig(enabled=True, warmup_epochs=2, margin=0.1, min_obs=3)
    low = fit_curve([(e, _saturating(0.15, e)) for e in range(3)], 16)
    assert low is not None and low.hi < 0.3
    assert not cfg.should_kill(low, epoch=0, best_so_far=0.9)  # warmup
    assert not cfg.should_kill(low, epoch=2, best_so_far=None)  # no best
    short = fit_curve([(e, _saturating(0.15, e)) for e in range(2)], 16)
    if short is not None:  # min_obs
        assert not cfg.should_kill(short, epoch=4, best_so_far=0.9)
    assert cfg.should_kill(low, epoch=2, best_so_far=0.9)
    assert not cfg.should_kill(low, epoch=2, best_so_far=low.hi + 0.05)


# -- off polarity is bit-exact -----------------------------------------------


def test_disabled_coordinator_leaves_gp_stream_byte_identical(journaled):
    """The regression pin for `RAFIKI_CURVE_KILL` off: threading a
    disabled coordinator through the ask/tell loop must not change one
    byte of the proposal stream, and must journal nothing."""
    from rafiki_tpu.advisor.gp import GpAdvisor

    kc = _knob_config()

    def _stream(coord):
        adv = GpAdvisor(kc, seed=11, n_initial=3)
        out = []
        for t in range(5):
            knobs = adv.propose()
            out.append(knobs)
            score = 0.5 + 0.1 * math.sin(t)
            if coord is not None:
                for e in range(4):
                    coord.observe(knobs, e, _saturating(score, e))
                    assert coord.kill_verdict(knobs, e) is None
                assert coord.speculate_inflight(adv) == 0
            adv.feedback(score, knobs)
            if coord is not None:
                coord.note_scored(knobs, score)
        return json.dumps(out, sort_keys=True)

    plain = _stream(None)
    threaded = _stream(CurveCoordinator(KillConfig()))  # both knobs off
    assert plain == threaded
    journal.close()
    assert _curve_records(journaled) == []


# -- serial worker kill end to end -------------------------------------------


class _Recorder:
    """Advisor handle that scripts proposals and records feedback."""

    def __init__(self, finals):
        self.finals = list(finals)
        self.feedbacks = []

    def propose(self):
        return {"final": self.finals.pop(0), "epochs": 6}

    def feedback(self, score, knobs):
        self.feedbacks.append((knobs["final"], score))


from rafiki_tpu.model.base import BaseModel


class _CurveModel(BaseModel):
    """Logs a saturating acc curve toward its ``final`` knob."""

    def __init__(self, final, epochs):
        from rafiki_tpu.model.log import logger

        super().__init__(final=final, epochs=epochs)
        self.final, self.epochs, self._logger = final, epochs, logger

    @staticmethod
    def get_knob_config():
        return {"final": FloatKnob(0.05, 0.95), "epochs": FixedKnob(6)}

    def train(self, uri):
        for e in range(self.epochs):
            self._logger.log(epoch=e, acc=_saturating(self.final, e),
                             loss=1.0 - _saturating(self.final, e))

    def evaluate(self, uri):
        return self.final

    def predict(self, queries):
        return []

    def dump_parameters(self):
        return b"params"

    def destroy(self):
        pass


def _worker(tmp_path, advisor, monkeypatch, kill):
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import TrainWorker

    for var in ("RAFIKI_CURVE_KILL", "RAFIKI_CURVE_SPECULATE"):
        monkeypatch.delenv(var, raising=False)
    if kill:
        monkeypatch.setenv("RAFIKI_CURVE_KILL", "1")
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    mrow = store.create_model("curvekill", "T", None, b"x = 1", "X")
    job = store.create_train_job("app", "T", None, "t", "v", {})
    store.create_sub_train_job(job["id"], mrow["id"])
    sub = store.get_sub_train_jobs(job["id"])[0]
    worker = TrainWorker(store, params, sub["id"], _CurveModel, advisor,
                         "t", "v", {}, worker_id="curve-w0",
                         async_persist=False)
    return store, worker


def test_serial_worker_kills_doomed_trial(journaled, monkeypatch):
    adv = _Recorder([0.9, 0.1])
    store, worker = _worker(journaled, adv, monkeypatch, kill=True)
    healthy = worker.run_trial(adv.propose())
    doomed = worker.run_trial(adv.propose())
    assert healthy["status"] == "COMPLETED" and healthy["score"] == 0.9
    assert doomed["status"] == "ERRORED"
    assert "early_killed" in (doomed.get("error") or "")
    # Consolation feedback carries the conservative PREDICTED score —
    # below best by construction of the kill rule — not a 0.0 floor.
    assert adv.feedbacks[0] == (0.9, 0.9)
    killed_final, consolation = adv.feedbacks[1]
    assert killed_final == 0.1 and 0.0 < consolation < 0.9 - 0.02
    journal.close()
    recs = _curve_records(journaled)
    kills = [r for r in recs if r["name"] == "kill"]
    assert len(kills) == 1
    # First eligible boundary: warmup=2 and min_obs=3 meet at epoch 2.
    assert kills[0]["epoch"] == 2
    assert kills[0]["best_so_far"] == 0.9
    assert any(r["name"] == "predict" for r in recs)
    # The scripted handle bypasses record_feedback, so the doomed
    # bucket isn't charged here (the A/B test below pins that);
    # the kill counter rides record_kill and must land regardless.
    assert search_ledger.snapshot()["n_killed"] == 1


def test_serial_worker_off_polarity_completes_everything(journaled,
                                                         monkeypatch):
    adv = _Recorder([0.9, 0.1])
    store, worker = _worker(journaled, adv, monkeypatch, kill=False)
    assert worker.curve is None
    assert worker.run_trial(adv.propose())["status"] == "COMPLETED"
    assert worker.run_trial(adv.propose())["status"] == "COMPLETED"
    assert [s for _, s in adv.feedbacks] == [0.9, 0.1]
    journal.close()
    assert _curve_records(journaled) == []
    assert search_ledger.snapshot()["n_killed"] == 0


# -- speculation + rehydration -----------------------------------------------


class _SpecSink:
    def __init__(self):
        self.calls = []

    def speculate(self, score, knobs, fit=None):
        self.calls.append((score, dict(knobs), fit))


def test_speculate_inflight_sorted_once_and_retired(journaled):
    from rafiki_tpu.obs.search.audit import knobs_hash

    coord = CurveCoordinator(KillConfig(speculate=True, min_obs=2))
    a, b, young = {"lr": 0.01}, {"lr": 0.02}, {"lr": 0.03}
    for e in range(3):
        coord.observe(a, e, _saturating(0.8, e))
        coord.observe(b, e, _saturating(0.6, e))
    coord.observe(young, 0, 0.1)  # below min_obs: not fed
    sink = _SpecSink()
    assert coord.speculate_inflight(sink) == 2
    fed = [knobs_hash(k) for _, k, _ in sink.calls]
    assert fed == sorted(fed)
    assert all(f is not None and "predicted" in f for *_, f in sink.calls)
    # Once per hash, and a retired curve is never speculated again.
    assert coord.speculate_inflight(sink) == 0
    coord.note_scored(a, 0.8)
    coord.note_done(b)
    coord.observe(a, 3, 0.79)
    coord.observe(b, 3, 0.59)
    assert coord.speculate_inflight(sink) == 0
    # Journaling rides the advisor's speculate() path (record_speculate
    # in advisor/base.py) — pinned by the correction test below.


def test_feedback_after_speculation_journals_correction(journaled):
    from rafiki_tpu.advisor.rehydrate import journal_speculations
    from rafiki_tpu.advisor.service import AdvisorService

    svc = AdvisorService()
    aid = svc.create_advisor(_knob_config(), kind="gp",
                             engine_kwargs={"n_initial": 2}, seed=0)
    k = svc.propose_batch(aid, 3)
    svc.feedback(aid, 0.8, k[0])
    svc.speculate(aid, 0.55, k[2])
    svc.feedback(aid, 0.61, k[2])  # the truth lands: correction
    journal.close()
    recs = read_dir(journaled)
    corrections = [r for r in recs if r.get("kind") == "advisor"
                   and r.get("name") == "correct"]
    assert len(corrections) == 1
    assert corrections[0]["predicted"] == 0.55
    assert corrections[0]["actual"] == 0.61
    assert abs(corrections[0]["error"] - 0.06) < 1e-9
    # Corrected speculations are no longer in flight for rehydration.
    assert journal_speculations(recs) == []
    assert search_ledger.snapshot()["n_corrections"] == 1


def test_journal_speculations_uncorrected_last_wins_sorted():
    from rafiki_tpu.advisor.rehydrate import journal_speculations
    from rafiki_tpu.obs.search.audit import knobs_hash

    k1, k2, k3 = {"lr": 0.01}, {"lr": 0.02}, {"lr": 0.03}
    recs = [
        {"kind": "advisor", "name": "speculate", "knobs": k1,
         "knobs_hash": knobs_hash(k1), "predicted": 0.4},
        {"kind": "advisor", "name": "speculate", "knobs": k1,
         "knobs_hash": knobs_hash(k1), "predicted": 0.45},  # last wins
        {"kind": "advisor", "name": "speculate", "knobs": k2,
         "knobs_hash": knobs_hash(k2), "predicted": 0.6},
        {"kind": "advisor", "name": "feedback",
         "knobs_hash": knobs_hash(k2), "score": 0.62},  # corrected
        {"kind": "advisor", "name": "speculate", "knobs": k3,
         "knobs_hash": knobs_hash(k3), "predicted": 0.7},
        {"kind": "event", "name": "noise"},
    ]
    out = journal_speculations(recs)
    assert [(p, knobs_hash(kn)) for kn, p, _ in out] == sorted(
        [(0.45, knobs_hash(k1)), (0.7, knobs_hash(k3))],
        key=lambda t: t[1])
    assert journal_speculations(
        recs, exclude_hashes={knobs_hash(k1)}) == [(k3, 0.7, None)]


def test_rehydration_replays_speculation_byte_identically(journaled):
    """The PR 15 contract with a speculation in flight: rehydrating
    from journals equals a fresh advisor hand-fed the same real-then-
    speculative sequence, byte for byte — and dropping the speculation
    changes the proposals, so the replay is load-bearing."""
    from rafiki_tpu.advisor.rehydrate import rehydrate_advisor
    from rafiki_tpu.advisor.service import AdvisorService

    kc = _knob_config()
    svc = AdvisorService()
    aid = svc.create_advisor(kc, kind="gp",
                             engine_kwargs={"n_initial": 2}, seed=0)
    k = svc.propose_batch(aid, 3)
    svc.feedback(aid, 0.8, k[0])
    svc.feedback(aid, 0.5, k[1])
    svc.speculate(aid, 0.72, k[2])  # still in flight at the "crash"
    journal.close()
    recs = read_dir(journaled)

    def _batch(service):
        return json.dumps(service.propose_batch(aid, 2), sort_keys=True)

    hydrated = []
    for _ in range(2):
        s = AdvisorService()
        rehydrate_advisor(s, kc, "gp", aid, completed=[],
                          journal_records=recs, seed=0,
                          engine_kwargs={"n_initial": 2})
        hydrated.append(_batch(s))
    assert hydrated[0] == hydrated[1]

    manual = AdvisorService()
    manual.create_advisor(kc, kind="gp", seed=0, advisor_id=aid,
                          engine_kwargs={"n_initial": 2})
    manual.feedback(aid, 0.8, k[0])
    manual.feedback(aid, 0.5, k[1])
    manual.speculate(aid, 0.72, k[2])
    assert _batch(manual) == hydrated[0]

    unspeculated = AdvisorService()
    rehydrate_advisor(
        unspeculated, kc, "gp", aid, completed=[],
        journal_records=[r for r in recs if r.get("name") != "speculate"],
        seed=0, engine_kwargs={"n_initial": 2})
    assert _batch(unspeculated) != hydrated[0]


# -- the A/B: what killing buys, and an over-aggressive killer caught --------
#
# One RandomAdvisor proposal stream trained twice over a synthetic
# epoch-curve objective with real per-epoch sleeps. Half the knob box is
# doomed: the curve saturates low and the trial diverges on its final
# epoch — consolation feedback, doomed bucket — in BOTH polarities, so the
# scored set (and therefore the final best) is identical by construction
# and the only difference the ledger can see is wall: kill-off sinks
# AB_EPOCHS sleeps into every doomed trial, kill-on only min_obs.

AB_TRIALS, AB_EPOCHS, AB_EPOCH_S, AB_SEED = 8, 10, 0.03, 10
KILL_CFG = {"warmup_epochs": 2, "margin": 0.35, "min_obs": 3}
DOCTORED_KILL_CFG = {"warmup_epochs": 0, "margin": 0.0, "min_obs": 2}


def _curve_profile(knobs):
    """A trial's destiny from the knob assignment itself — the 'sibling
    re-run' ground truth is this function again. Finals are bimodal
    (doomed plateau 0.10-0.18, healthy 0.70-0.90) so a sane margin
    separates the bands."""
    from rafiki_tpu.obs.search import audit as search_audit

    h = int(search_audit.knobs_hash(knobs), 16)
    doomed = (h >> 8) % 2 == 1
    final = (0.10 + (h % 97) / 97.0 * 0.08) if doomed \
        else (0.70 + (h % 89) / 89.0 * 0.20)
    return round(final, 6), doomed, h


def _epoch_score(h_int, final, e):
    """Saturating curve with a deterministic per-trial wiggle: enough
    noise that a 2-observation fit can be badly wrong (the doctored
    killer's trap) while a min_obs=3 fit still lands inside the band."""
    wiggle = 1.0 + 0.06 * math.sin((h_int % 7) + 1.7 * e)
    return round(final * (1.0 - math.exp(-(e + 1) / 2.0)) * wiggle, 6)


def _curved_sweep(log_dir, kill_cfg):
    """Run the seeded stream once, journaled under ``log_dir``;
    ``kill_cfg=None`` is the kill-off polarity (no coordinator at all).
    Returns the run's counters and its reconstructed artifact."""
    import time

    from rafiki_tpu.advisor.random_advisor import RandomAdvisor
    from rafiki_tpu.obs.search import audit as search_audit
    from rafiki_tpu.obs.search import reconstruct

    search_ledger.reset()
    journal.configure(log_dir, role="sweep")
    counts = {"killed": 0, "diverged": 0, "scored": 0, "false_kills": 0}
    killed = []  # (knobs, predicted_final, best_at_kill)
    try:
        adv = RandomAdvisor(_knob_config(lr_max=3e-2), seed=AB_SEED)
        coord = (CurveCoordinator(KillConfig(enabled=True, **kill_cfg))
                 if kill_cfg else None)
        for t in range(AB_TRIALS):
            knobs = adv.propose()
            final, doomed, h_int = _curve_profile(knobs)
            was_killed, score = False, 0.0
            for e in range(AB_EPOCHS):
                time.sleep(AB_EPOCH_S)
                score = _epoch_score(h_int, final, e)
                if coord is None:
                    continue
                coord.observe(knobs, e, score, trial_id=f"t{t:02d}",
                              horizon=AB_EPOCHS)
                fit = coord.kill_verdict(knobs, e, trial_id=f"t{t:02d}")
                if fit is not None:
                    killed.append((knobs, fit.predicted_final,
                                   coord.best_so_far))
                    search_audit.note_doomed(knobs)
                    adv.feedback(0.0, knobs)
                    was_killed = True
                    break
            if was_killed:
                counts["killed"] += 1
            elif doomed:
                # The trial diverges at the end — the consolation path
                # the workers take, identical in both polarities.
                search_audit.note_doomed(knobs)
                adv.feedback(0.0, knobs)
                if coord is not None:
                    coord.note_done(knobs)
                counts["diverged"] += 1
            else:
                adv.feedback(score, knobs)
                if coord is not None:
                    coord.note_scored(knobs, score)
                counts["scored"] += 1
        # Hindsight pass: every killed trial's knobs re-run to completion
        # (the analytic profile IS the sibling); a false-kill verdict is
        # journaled when the sibling beats best-so-far.
        for knobs, predicted, best_at in killed:
            sibling, _, h_int = _curve_profile(knobs)
            sibling_score = _epoch_score(h_int, sibling, AB_EPOCHS - 1)
            if best_at is not None and sibling_score > best_at:
                search_audit.record_false_kill(
                    knobs, killed_predicted=predicted,
                    sibling_score=sibling_score, best_so_far=best_at)
                counts["false_kills"] += 1
    finally:
        journal.close()
        search_ledger.reset()
    return counts, reconstruct.artifact(
        reconstruct.reconstruct(read_dir(log_dir)))


def test_kill_on_buys_effective_trials_per_hour_at_an_equal_best(tmp_path):
    """Kill-on condemns the doomed trials off the curve fit after
    ``min_obs`` epochs: >= 1.3x kill-off's effective trials/hour at a
    byte-equal final best, with zero false kills — each killed trial's
    sibling, re-run to completion, stays below best-so-far."""
    c_off, art_off = _curved_sweep(tmp_path / "off", None)
    c_on, art_on = _curved_sweep(tmp_path / "on", KILL_CFG)
    assert c_on["false_kills"] == 0 and c_on["killed"] >= 2
    assert c_on["scored"] == c_off["scored"] >= 3
    assert art_on["best_score"] is not None
    assert art_on["best_score"] == art_off["best_score"]
    assert (art_on["effective_trials_per_hour"]
            >= 1.3 * art_off["effective_trials_per_hour"])
    assert art_on["n_kills"] == c_on["killed"]
    assert art_on["n_false_kills"] == 0 and art_on["kill_precision"] == 1.0
    assert (art_off.get("n_kills") or 0) == 0


def test_an_over_aggressive_killer_is_caught_by_hindsight(tmp_path):
    """The same stream under margin=0, warmup=0, min_obs=2: at least one
    hindsight false kill journaled and kill_precision < 1 in the
    reconstruction. A killer the false-kill pass cannot catch would let
    a 'faster' sweep quietly discard its best trials."""
    counts, art = _curved_sweep(tmp_path / "doctored", DOCTORED_KILL_CFG)
    assert counts["false_kills"] >= 1
    assert art["n_false_kills"] == counts["false_kills"]
    assert art["kill_precision"] < 1.0
