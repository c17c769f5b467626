"""Search anatomy plane (docs/search_anatomy.md): every advisor
decision leaves an audit record, sweeps reconstruct from journals
alone, trial lineage survives evict/backfill/repack/resume, and the
SWEEP_r* trend gates both ways."""

import math

import pytest

from rafiki_tpu.model.knobs import FixedKnob, FloatKnob, IntegerKnob
from rafiki_tpu.obs.journal import journal, read_dir
from rafiki_tpu.obs.search import audit, lineage, reconstruct, stats
from rafiki_tpu.obs.search.ledger import search_ledger


KC = {"lr": FloatKnob(1e-4, 3e-2, is_exp=True),
      "units": IntegerKnob(4, 64),
      "b": FixedKnob(8)}


def _objective(knobs):
    """One interior optimum — gives the GP something to exploit and the
    regret curve a real shape."""
    return round(1.0 - (math.log10(knobs["lr"]) + 2.5) ** 2 * 0.2
                 - abs(knobs["units"] - 32) / 64 * 0.2, 6)


@pytest.fixture()
def journaled(tmp_path):
    """Global journal into a tmp dir + a clean search ledger, both
    guaranteed back to pristine afterwards."""
    search_ledger.reset()
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()
        search_ledger.reset()


def _sweep(advisor, n=6):
    for _ in range(n):
        knobs = advisor.propose()
        advisor.feedback(_objective(knobs), knobs)


def _advisor(kind, seed=0, n_initial=3):
    from rafiki_tpu.advisor.gp import GpAdvisor
    from rafiki_tpu.advisor.random_advisor import RandomAdvisor
    from rafiki_tpu.advisor.tpe import TpeAdvisor

    if kind == "gp":
        return GpAdvisor(KC, seed=seed, n_initial=n_initial)
    if kind == "tpe":
        return TpeAdvisor(KC, seed=seed, n_initial=n_initial)
    return RandomAdvisor(KC, seed=seed)


# ---------------------------------------------------------------------------
# Decision audit completeness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,phases", [
    ("gp", {"warmup", "ei"}),
    ("tpe", {"warmup", "tpe", "epsilon"}),
    ("random", {"random"}),
])
def test_audit_complete_per_engine(journaled, kind, phases):
    """Every propose and every feedback of every engine leaves a
    journal record carrying the acquisition 'why'."""
    adv = _advisor(kind, seed=3, n_initial=3)
    _sweep(adv, n=7)
    journal.close()
    recs = [r for r in read_dir(journaled) if r.get("kind") == "advisor"]
    proposes = [r for r in recs if r["name"] == "propose"]
    feedbacks = [r for r in recs if r["name"] == "feedback"]
    assert len(proposes) == 7 and len(feedbacks) == 7
    seen_phases = {p["acquisition"]["phase"] for p in proposes}
    assert seen_phases <= phases and "warmup" in seen_phases or kind == "random"
    assert all(p["engine"] == kind for p in proposes)
    assert all(p["knobs_hash"] == audit.knobs_hash(p["knobs"])
               for p in proposes)
    # feedback joins back to its proposal by hash, and best_so_far
    # includes the score it reports
    ph = [p["knobs_hash"] for p in proposes]
    assert all(f["knobs_hash"] in ph for f in feedbacks)
    assert all(f["best_so_far"] >= f["score"] for f in feedbacks)


def test_gp_ei_acquisition_internals(journaled):
    """Past warmup the GP must journal what it saw: EI of the chosen
    candidate, posterior mean/std, pool size, fit wall-time."""
    adv = _advisor("gp", seed=1, n_initial=3)
    _sweep(adv, n=6)
    journal.close()
    ei_recs = [r for r in read_dir(journaled)
               if r.get("kind") == "advisor" and r["name"] == "propose"
               and r["acquisition"]["phase"] == "ei"]
    assert ei_recs, "no post-warmup EI proposal was journaled"
    for r in ei_recs:
        acq = r["acquisition"]
        assert acq["ei"] >= 0 and acq["sigma"] >= 0
        assert acq["pool"] > 0 and acq["fit_s"] >= 0
        assert "mu" in acq


def test_propose_batch_journals_liar_state(journaled):
    adv = _advisor("gp", seed=2, n_initial=2)
    _sweep(adv, n=3)
    adv.propose_batch(3)
    journal.close()
    batches = [r for r in read_dir(journaled)
               if r.get("kind") == "advisor" and r["name"] == "propose_batch"]
    assert len(batches) == 1
    b = batches[0]
    assert b["n"] == 3 and len(b["knobs_hashes"]) == 3
    assert b["strategy"] == "constant_liar_min"
    assert b["liar"]["lies_planted"] == 3


# ---------------------------------------------------------------------------
# propose_batch over HTTP (satellite 1)
# ---------------------------------------------------------------------------


def _http_client():
    from werkzeug.test import Client
    from werkzeug.wrappers import Response

    from rafiki_tpu.advisor.app import AdvisorApp
    from rafiki_tpu.advisor.service import AdvisorService

    service = AdvisorService()
    aid = service.create_advisor(KC, kind="random", seed=0)
    return Client(AdvisorApp(service), Response), aid


def test_http_propose_batch_roundtrip(journaled):
    client, aid = _http_client()
    r = client.post(f"/advisors/{aid}/propose_batch", json={"n": 3})
    assert r.status_code == 200
    knobs_list = r.get_json()["knobs_list"]
    assert len(knobs_list) == 3
    assert all(set(k) == set(KC) for k in knobs_list)
    journal.close()
    recs = [r2 for r2 in read_dir(journaled) if r2.get("kind") == "advisor"]
    batches = [r2 for r2 in recs if r2["name"] == "propose_batch"]
    # journaled exactly like the in-proc path: one batch record whose
    # member hashes all have propose records, stamped with the registry id
    assert len(batches) == 1 and batches[0]["n"] == 3
    assert batches[0]["advisor_id"] == aid
    ph = [r2["knobs_hash"] for r2 in recs if r2["name"] == "propose"]
    assert all(h in ph for h in batches[0]["knobs_hashes"])


def test_http_propose_batch_rejects_bad_n(journaled):
    client, aid = _http_client()
    assert client.post(f"/advisors/{aid}/propose_batch",
                       json={"n": 0}).status_code == 400
    assert client.post(f"/advisors/{aid}/propose_batch",
                       json={}).status_code == 400
    assert client.post("/advisors/nope/propose_batch",
                       json={"n": 2}).status_code == 404


# ---------------------------------------------------------------------------
# Ledger: effective trials per hour, doomed accounting
# ---------------------------------------------------------------------------


def test_ledger_charges_doomed_wall_separately(journaled):
    from rafiki_tpu import telemetry

    adv = _advisor("random", seed=9)
    k1 = adv.propose()
    audit.note_doomed(k1)           # the worker's error path
    adv.feedback(0.0, k1)           # consolation feedback
    k2 = adv.propose()
    adv.feedback(0.8, k2)           # a real score
    journal.close()
    snap = search_ledger.snapshot()
    assert snap["n_proposed"] == 2
    assert snap["n_doomed"] == 1 and snap["n_scored"] == 1
    assert snap["best_score"] == 0.8
    assert snap["doomed_wall_s"] >= 0 and snap["scored_wall_s"] >= 0
    # the feedback record itself carries the doomed flag
    fb = [r for r in read_dir(journaled)
          if r.get("kind") == "advisor" and r["name"] == "feedback"]
    assert [f["doomed"] for f in fb] == [True, False]
    # and the telemetry gauges are live for prom/SLO consumers
    tsnap = telemetry.snapshot()
    assert tsnap["gauges"]["search.best_score"] == 0.8
    assert "search" in tsnap


# ---------------------------------------------------------------------------
# Reconstruction: regret, lift CI, reconciliation
# ---------------------------------------------------------------------------


def _two_engine_records(tmp_path, n=10):
    from rafiki_tpu.advisor.gp import GpAdvisor
    from rafiki_tpu.advisor.random_advisor import RandomAdvisor

    _sweep(GpAdvisor(KC, seed=5, n_initial=4), n=n)
    _sweep(RandomAdvisor(KC, seed=105), n=n)
    journal.close()
    return read_dir(tmp_path)


def test_reconstruct_regret_monotone_and_joined(journaled):
    recs = _two_engine_records(journaled)
    doc = reconstruct.reconstruct(recs)
    assert doc["engine"] == "gp" and doc["reconciliation"]["ok"]
    assert doc["n_proposals"] == 10 and doc["n_scored"] == 10
    best = doc["curve"]["best_so_far"]
    regret = doc["curve"]["regret"]
    assert all(a <= b for a, b in zip(best, best[1:]))
    assert all(a >= b for a, b in zip(regret, regret[1:]))
    assert regret[-1] == 0.0
    assert all(p["acquisition"]["phase"] for p in doc["proposals"])
    # lift vs the random baseline carries its bootstrap CI
    assert doc["lift"]["lo"] <= doc["advisor_lift"] <= doc["lift"]["hi"]


def test_reconstruct_lift_ci_deterministic(journaled):
    recs = _two_engine_records(journaled)
    a = reconstruct.reconstruct(recs, boot_seed=0)
    b = reconstruct.reconstruct(recs, boot_seed=0)
    assert a["lift"] == b["lift"]
    c = reconstruct.reconstruct(recs, boot_seed=1)
    assert c["lift"]["mean"] == a["lift"]["mean"]  # data-determined
    assert c["lift"] != a["lift"]                  # resamples are not


def test_bootstrap_ci_seeded_and_degenerate():
    d = [0.1, -0.2, 0.3, 0.05, 0.0]
    assert stats.bootstrap_ci(d, seed=7) == stats.bootstrap_ci(d, seed=7)
    ci = stats.bootstrap_ci(d, seed=7)
    assert ci["lo"] <= ci["mean"] <= ci["hi"]
    empty = stats.bootstrap_ci([])
    assert empty["n"] == 0 and empty["mean"] is None
    one = stats.bootstrap_ci([0.4])
    assert one["mean"] == one["lo"] == one["hi"] == 0.4


def test_reconciliation_fails_on_unjournaled_decision(journaled):
    recs = _two_engine_records(journaled)
    cut = next(i for i, r in enumerate(recs)
               if r.get("kind") == "advisor" and r["name"] == "propose"
               and r.get("engine") == "gp")
    doctored = recs[:cut] + recs[cut + 1:]
    doc = reconstruct.reconstruct(doctored)
    assert not doc["reconciliation"]["ok"]
    errs = doc["reconciliation"]["errors"]
    assert any(e["type"] == "feedback_without_propose" for e in errs)
    # and the artifact slice refuses to look like a healthy round
    art = reconstruct.artifact(doc)
    assert art["error"] == "sweep reconciliation failed"


def test_artifact_slice_is_trendable(journaled):
    recs = _two_engine_records(journaled)
    art = reconstruct.artifact(reconstruct.reconstruct(recs))
    assert art["sweep_schema_version"] == reconstruct.SWEEP_SCHEMA_VERSION
    assert "error" not in art
    for k in ("best_score", "regret", "advisor_lift",
              "lift_ci_low", "lift_ci_high"):
        assert k in art, k


# ---------------------------------------------------------------------------
# Lineage across evict + backfill and repack + resume
# ---------------------------------------------------------------------------


def test_lineage_evict_and_backfill(journaled, monkeypatch):
    from rafiki_tpu import telemetry
    from rafiki_tpu.advisor import AdvisorService
    from rafiki_tpu.chaos.scenarios import EVICT_SOURCE
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.model.knobs import knob_config_signature
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import (InProcAdvisorHandle,
                                         PackedTrialRunner, TrainWorker)
    from tests.test_scheduler import TRAIN, VAL

    telemetry.reset()
    store = MetaStore(journaled / "meta.sqlite3")
    params = ParamsStore(journaled / "params")
    model = store.create_model("evictff", "IMAGE_CLASSIFICATION", None,
                               EVICT_SOURCE, "EvictFF")
    job = store.create_train_job("searchobs", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 3})
    store.create_sub_train_job(job["id"], model["id"])
    sub = store.get_sub_train_jobs(job["id"])[0]
    cls = load_model_class(EVICT_SOURCE, "EvictFF")
    advisors = AdvisorService()
    aid = advisors.create_advisor(cls.get_knob_config(), kind="random")
    worker = TrainWorker(store, params, sub["id"], cls,
                         InProcAdvisorHandle(advisors, aid), TRAIN, VAL,
                         {"MODEL_TRIAL_COUNT": 3}, worker_id="evict-w0",
                         async_persist=False)
    kc = cls.get_knob_config()
    base = {"hidden_units": 16, "batch_size": 32, "epochs": 3}
    rows = []
    # lr >= 0.02 trips EvictFF's early-stop at epoch 0 (the straggler);
    # the freed slot is backfilled mid-pack — same shape as PR 7's
    # test_pack_straggler_evicted_and_backfilled.
    for kn in (dict(base, learning_rate=0.025),
               dict(base, learning_rate=0.005)):
        t = store.create_trial(sub["id"], "EvictFF", kn,
                               shape_sig=knob_config_signature(kc, kn),
                               budget_max=3)
        rows.append((t["id"], kn))
    assert PackedTrialRunner(worker, 2).run_assigned(rows, budget_max=3) == 3
    journal.close()
    trials = lineage.build(read_dir(journaled))
    assert len(trials) == 3
    assert sum(t["n_evictions"] for t in trials.values()) >= 1
    assert any(t["backfilled"] for t in trials.values()), \
        "the backfilled trial's lineage lost its origin"
    evicted = trials[rows[0][0]]
    assert evicted["n_evictions"] == 1
    # an evicted-but-scored member is a completed trial, not an orphan
    assert lineage.reconcile(trials) == []
    # and walk() resolves unique id prefixes like the CLI does
    assert lineage.walk(trials, rows[0][0][:8])["trial_id"] == rows[0][0]


def test_lineage_repack_resume_after_chip_loss(journaled, monkeypatch):
    from rafiki_tpu import telemetry
    from rafiki_tpu.chaos import FaultPlane, install, uninstall
    from rafiki_tpu.chaos.scenarios import FF_SOURCE as CHAOS_FF_SOURCE
    from rafiki_tpu.scheduler import MeshSweepScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore
    from tests.test_scheduler import TRAIN, VAL

    telemetry.reset()
    # subprocess chip workers journal via RAFIKI_LOG_DIR; the
    # scheduler's own mesh/* records ride the fixture's journal
    monkeypatch.setenv("RAFIKI_LOG_DIR", str(journaled))
    monkeypatch.setenv("RAFIKI_CHECKPOINT_EVERY", "1")
    store = MetaStore(journaled / "meta.sqlite3")
    params = ParamsStore(journaled / "params")
    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               CHAOS_FF_SOURCE, "ChaosFF")
    job = store.create_train_job("searchobs", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 4})
    store.create_sub_train_job(job["id"], model["id"])
    install(FaultPlane.from_spec(
        "seed=11;scheduler.preempt:kill:after=2:times=1:match=chip1"))
    try:
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    finally:
        uninstall()
    journal.close()
    assert result.status == "COMPLETED", result.errors
    trials = lineage.build(read_dir(journaled))
    assert len(trials) == 4
    # the killed chip's rows moved: repack recorded, and at least one
    # trial restarted on the survivor (second incarnation or resume)
    moved = [t for t in trials.values() if t["repacked_from"]]
    assert moved, "mesh/repack left no lineage trace"
    assert any(t["n_incarnations"] > 1 or t["n_resumes"] >= 1
               for t in trials.values())
    # every incarnation accounted for: NO orphans fleet-wide
    assert lineage.reconcile(trials) == []
    statuses = {t["status"] for t in trials.values()}
    assert statuses == {"trial_completed"}, statuses


def test_lineage_reconcile_flags_orphans():
    """A started-never-terminated incarnation must surface loudly."""
    recs = [
        {"kind": "event", "name": "trial_started", "ts": 1.0,
         "trial_id": "t1", "worker_id": "w0", "knobs": {"lr": 0.1}},
        {"kind": "event", "name": "trial_completed", "ts": 2.0,
         "trial_id": "t1", "worker_id": "w0", "score": 0.5},
        {"kind": "event", "name": "trial_started", "ts": 1.5,
         "trial_id": "t2", "worker_id": "w1", "knobs": {"lr": 0.2}},
    ]
    trials = lineage.build(recs)
    orphans = lineage.reconcile(trials)
    assert [o["trial_id"] for o in orphans] == ["t2"]
    assert trials["t2"]["status"] == "orphaned"
