"""Failure detection + orphaned-trial recovery."""

import time

import pytest

from rafiki_tpu.constants import ServiceStatus, ServiceType
from rafiki_tpu.scheduler.recovery import recover_orphaned_trials
from rafiki_tpu.store import MetaStore, ParamsStore

from tests.test_checkpoint_resume import FF3_SOURCE, TRAIN, VAL


@pytest.fixture()
def env(tmp_path):
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    row = store.create_model("ff3", "IMAGE_CLASSIFICATION", None, FF3_SOURCE, "FF3")
    job = store.create_train_job("recapp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 2})
    sub = store.create_sub_train_job(job["id"], row["id"])
    return store, params, sub


def test_orphan_detection(env):
    store, params, sub = env
    svc_live = store.create_service(ServiceType.TRAIN_WORKER.value)
    svc_dead = store.create_service(ServiceType.TRAIN_WORKER.value)
    knobs = {"learning_rate": 3e-3, "batch_size": 32, "epochs": 3}
    t_live = store.create_trial(sub["id"], "FF3", knobs, worker_id="w0",
                                service_id=svc_live["id"])
    t_dead = store.create_trial(sub["id"], "FF3", knobs, worker_id="w1",
                                service_id=svc_dead["id"])
    store.update_service(svc_dead["id"], status=ServiceStatus.ERRORED.value)
    store.update_service(svc_live["id"], heartbeat=True)

    orphans = store.get_orphaned_trials(stale_after_s=60)
    assert [t["id"] for t in orphans] == [t_dead["id"]]

    # a live trial goes stale once its service stops heartbeating
    orphans = store.get_orphaned_trials(stale_after_s=-1)  # everything stale
    assert {t["id"] for t in orphans} == {t_live["id"], t_dead["id"]}


def test_completed_trials_never_orphaned(env):
    store, params, sub = env
    svc = store.create_service(ServiceType.TRAIN_WORKER.value)
    t = store.create_trial(sub["id"], "FF3", {"epochs": 3}, service_id=svc["id"])
    store.mark_trial_as_completed(t["id"], 0.9, None)
    store.update_service(svc["id"], status=ServiceStatus.ERRORED.value)
    assert store.get_orphaned_trials(stale_after_s=-1) == []


def test_admin_recover_sync_and_background(tmp_config):
    """Admin.recover_trials: wait=True returns terminal rows; wait=False
    claims orphans (RUNNING, new owner) and finishes in background."""
    import time as _time

    from rafiki_tpu.admin import Admin

    admin = Admin(config=tmp_config)
    try:
        store = admin.store
        row = store.create_model("ff3", "IMAGE_CLASSIFICATION", None,
                                 FF3_SOURCE, "FF3")
        job = store.create_train_job("recadm", "IMAGE_CLASSIFICATION", None,
                                     TRAIN, VAL, {"MODEL_TRIAL_COUNT": 2})
        sub = store.create_sub_train_job(job["id"], row["id"])
        knobs = {"learning_rate": 3e-3, "batch_size": 32, "epochs": 3}

        def orphan():
            svc = store.create_service(ServiceType.TRAIN_WORKER.value)
            t = store.create_trial(sub["id"], "FF3", knobs, worker_id="dead",
                                   service_id=svc["id"])
            store.update_service(svc["id"], status=ServiceStatus.ERRORED.value)
            return t

        t1 = orphan()
        out = admin.recover_trials(stale_after_s=60, wait=True)
        assert [o["id"] for o in out] == [t1["id"]]
        assert out[0]["status"] == "COMPLETED"

        t2 = orphan()
        out = admin.recover_trials(stale_after_s=60, wait=False)
        assert [o["id"] for o in out] == [t2["id"]]
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            if store.get_trial(t2["id"])["status"] == "COMPLETED":
                break
            _time.sleep(0.5)
        assert store.get_trial(t2["id"])["status"] == "COMPLETED"
    finally:
        admin.stop()


def test_recover_orphaned_trial_end_to_end(env):
    """A trial whose worker died mid-run is detected and re-run to
    completion by the recovery sweep (from its checkpoint when present)."""
    store, params, sub = env
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.worker.train import TrainWorker

    model_row = store.get_model(sub["model_id"])
    cls = load_model_class(model_row["model_file"], "FF3")

    class Crashy(cls):  # type: ignore[misc, valid-type]
        def evaluate(self, uri):
            raise KeyboardInterrupt  # hard death: no ERRORED mark

    Crashy.__name__ = "FF3"
    svc = store.create_service(ServiceType.TRAIN_WORKER.value)
    w = TrainWorker(store, params, sub["id"], Crashy, None, TRAIN, VAL,
                    {"MODEL_TRIAL_COUNT": 2}, worker_id="dying",
                    async_persist=False, checkpoint_every=1)
    w.service_id = svc["id"]
    knobs = {"learning_rate": 3e-3, "batch_size": 32, "epochs": 3}
    with pytest.raises(KeyboardInterrupt):
        w.run_trial(knobs)
    store.update_service(svc["id"], status=ServiceStatus.ERRORED.value)

    # the trial is RUNNING with a dead service → orphan
    orphans = store.get_orphaned_trials(stale_after_s=60)
    assert len(orphans) == 1
    assert params.latest_checkpoint(orphans[0]["id"]) is not None

    results = recover_orphaned_trials(store, params, stale_after_s=60)
    assert len(results) == 1
    assert results[0]["status"] == "COMPLETED"
    assert results[0]["score"] is not None
    assert results[0]["params_id"]
    # sweep is now clean
    assert store.get_orphaned_trials(stale_after_s=60) == []


# ---------------------------------------------------------------------------
# Sweep WAL (scheduler/wal.py)
# ---------------------------------------------------------------------------


@pytest.fixture
def journaled(tmp_path):
    from rafiki_tpu.obs.journal import journal

    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()


def test_wal_roundtrip_and_torn_tail(tmp_path):
    from rafiki_tpu.scheduler.wal import SweepWal, WalError, read_wal

    p = tmp_path / "wal" / "sweep-j1.wal"
    wal = SweepWal(p, generation=0)
    txn = wal.intent("budget_claim", sub_id="s1", knobs_hash="h1")
    wal.commit(txn, "budget_claim", trial_id="t1")
    wal.note("sweep_config", advisor_kind="gp", chips=2)
    wal.close()

    recs = read_wal(p)
    assert [r["rec"] for r in recs] == ["intent", "commit", "note"]
    assert recs[0]["txn"] == recs[1]["txn"] == txn
    assert recs[0]["lsn"] == 1 and recs[2]["gen"] == 0

    # A torn FINAL line (death mid-write, pre-fsync-return: the writer
    # never acted on it) is dropped silently.
    with open(p, "a") as fh:
        fh.write('{"lsn": 4, "rec": "inte')
    assert len(read_wal(p)) == 3

    # A torn INTERIOR line is corruption, not a crash artifact.
    lines = p.read_text().splitlines()
    lines[1] = lines[1][:10]
    p.write_text("\n".join(lines) + "\n")
    with pytest.raises(WalError):
        read_wal(p)


def test_wal_txn_ids_unique_across_handles(tmp_path):
    """Two handles on the same file (the resume process opens an
    adoption-phase log AND the continuation run_sweep's) must never
    collide on txn ids even though they share a pid."""
    from rafiki_tpu.scheduler.wal import SweepWal, read_wal

    p = tmp_path / "w.wal"
    a, b = SweepWal(p), SweepWal(p, generation=1)
    txns = {a.intent("budget_claim"), b.intent("budget_claim"),
            a.intent("backfill"), b.intent("backfill")}
    a.close(), b.close()
    assert len(txns) == 4
    assert len({r["txn"] for r in read_wal(p)}) == 4


def test_reconcile_proves_clean_accounting():
    from rafiki_tpu.scheduler.wal import reconcile

    trials = [{"id": "t1", "knobs": {"lr": 0.01}, "no": 1}]
    records = [
        {"rec": "intent", "op": "budget_claim", "txn": "w1-ab-1",
         "sub_id": "s1"},
        {"rec": "commit", "op": "budget_claim", "txn": "w1-ab-1",
         "trial_id": "t1"},
        {"rec": "intent", "op": "budget_claim", "txn": "w1-ab-2",
         "sub_id": "s1"},
        {"rec": "commit", "op": "budget_claim", "txn": "w1-ab-2",
         "denied": True},
    ]
    r = reconcile(records, trials, sub={"claimed": 1}, sub_id="s1")
    assert r.ok, r.errors
    assert r.claims == {"t1": 1} and r.denied == 1


def test_reconcile_catches_doctored_wal():
    """The polarity check: a committed-but-unclaimed slot (the WAL
    says a claim landed; no store row exists) must be CAUGHT."""
    from rafiki_tpu.scheduler.wal import WalReconcileError, reconcile

    records = [
        {"rec": "intent", "op": "budget_claim", "txn": "w1-cd-1"},
        {"rec": "commit", "op": "budget_claim", "txn": "w1-cd-1",
         "trial_id": "ghost"},
    ]
    r = reconcile(records, [])
    assert not r.ok
    assert {e["type"] for e in r.errors} == {"committed_unclaimed"}
    with pytest.raises(WalReconcileError, match="committed_unclaimed"):
        r.raise_if_failed()

    # ...and the inverse: a store row no WAL claim covers.
    r2 = reconcile([], [{"id": "tX", "knobs": {}, "no": 1}])
    assert {e["type"] for e in r2.errors} == {"unlogged_claim"}


def test_reconcile_resolves_in_doubt_intent_by_knobs_hash():
    from rafiki_tpu.obs.search.audit import knobs_hash
    from rafiki_tpu.scheduler.wal import reconcile

    knobs = {"learning_rate": 0.003}
    trials = [{"id": "t1", "knobs": knobs, "no": 1}]
    records = [{"rec": "intent", "op": "budget_claim", "txn": "w9-ef-1",
                "knobs_hash": knobs_hash(knobs)}]
    r = reconcile(records, trials)
    assert r.ok, r.errors
    assert r.in_doubt == [{"txn": "w9-ef-1", "op": "budget_claim",
                           "landed": True}]
    assert r.claims == {"t1": 1}


# ---------------------------------------------------------------------------
# resume_sweep (scheduler/recovery.py)
# ---------------------------------------------------------------------------


def test_resume_refuses_doctored_wal(env, journaled):
    """resume_sweep must NOT adopt a job whose WAL-vs-store accounting
    is provably wrong — compounding damage is worse than staying down."""
    from rafiki_tpu.obs.journal import read_dir
    from rafiki_tpu.scheduler.recovery import resume_sweep
    from rafiki_tpu.scheduler.wal import SweepWal, WalReconcileError, wal_path

    store, params, sub = env
    job_id = sub["train_job_id"]
    wal = SweepWal(wal_path(store.path, job_id))
    wal.note("sweep_config", advisor_kind="random", chips=1,
             trials_per_chip=1)
    txn = wal.intent("budget_claim", sub_id=sub["id"], knobs_hash="h")
    wal.commit(txn, "budget_claim", trial_id="ghost")  # doctored
    wal.close()

    with pytest.raises(WalReconcileError):
        resume_sweep(store, params, job_id, stale_after_s=60)
    recs = read_dir(journaled)
    assert any(r.get("kind") == "recovery"
               and r.get("name") == "reconcile_failed" for r in recs)


def test_resume_without_wal_degrades_loudly(env, journaled):
    from rafiki_tpu.obs.journal import read_dir
    from rafiki_tpu.scheduler.recovery import resume_sweep

    store, params, sub = env
    summary = resume_sweep(store, params, sub["train_job_id"],
                           stale_after_s=60)
    assert summary["mode"] == "orphan_only"
    recs = read_dir(journaled)
    assert any(r.get("kind") == "recovery" and r.get("name") == "no_wal"
               for r in recs), "no-WAL degrade must be journaled loudly"


def test_double_resume_adoption_is_cas(env):
    """The double-resume race: both resumers see the same orphan; the
    CAS adopt means exactly one wins and the loser backs off."""
    store, params, sub = env
    from rafiki_tpu.constants import ServiceType

    svc_dead = store.create_service(ServiceType.TRAIN_WORKER.value)
    t = store.create_trial(sub["id"], "FF3", {"epochs": 3},
                           service_id=svc_dead["id"])
    s1 = store.create_service(ServiceType.TRAIN_WORKER.value)
    s2 = store.create_service(ServiceType.TRAIN_WORKER.value)
    won1 = store.adopt_trial(t["id"], svc_dead["id"], s1["id"], "r1",
                             expected_status=t["status"])
    won2 = store.adopt_trial(t["id"], svc_dead["id"], s2["id"], "r2",
                             expected_status=t["status"])
    assert won1 and not won2
    assert store.get_trial(t["id"])["service_id"] == s1["id"]

    # A zombie worker finishing first also beats adoption: terminal
    # status never regresses to RUNNING.
    store.mark_trial_as_completed(t["id"], 0.5, None)
    s3 = store.create_service(ServiceType.TRAIN_WORKER.value)
    assert not store.adopt_trial(t["id"], s1["id"], s3["id"], "r3")
    assert store.get_trial(t["id"])["status"] == "COMPLETED"


def test_recovery_advisor_routes_adopted_scores(journaled):
    from rafiki_tpu.obs.journal import read_dir
    from rafiki_tpu.scheduler.recovery import _RecoveryAdvisor

    class Inner:
        def __init__(self):
            self.seen = []

        def feedback(self, score, knobs):
            self.seen.append((score, dict(knobs)))

    inner = Inner()
    routed = _RecoveryAdvisor(inner)
    routed.feedback(0.75, {"learning_rate": 0.01})
    assert inner.seen == [(0.75, {"learning_rate": 0.01})]

    orphan_only = _RecoveryAdvisor(None)
    orphan_only.feedback(0.25, {"learning_rate": 0.02})  # must not raise

    with pytest.raises(RuntimeError):
        routed.propose()
    with pytest.raises(RuntimeError):
        routed.propose_batch(2)

    recs = [r for r in read_dir(journaled)
            if r.get("kind") == "recovery" and r.get("name") == "feedback"]
    assert [r["routed"] for r in recs] == [True, False]
    assert all(r.get("knobs_hash") for r in recs)


# ---------------------------------------------------------------------------
# Advisor rehydration (advisor/rehydrate.py)
# ---------------------------------------------------------------------------


def _gp_knob_config():
    from rafiki_tpu.model.knobs import FixedKnob, FloatKnob

    return {"learning_rate": FloatKnob(1e-3, 3e-2, is_exp=True),
            "batch_size": FixedKnob(32), "epochs": FixedKnob(3)}


def test_rehydrated_advisor_proposes_byte_identically(journaled):
    """The equivalence contract: a rehydrated advisor's proposals are
    byte-identical to a fresh advisor fed the same observations —
    REGARDLESS of the order the crashed process's rows are replayed in
    (rehydrate sorts them canonically)."""
    import json

    from rafiki_tpu.advisor.rehydrate import rehydrate_advisor
    from rafiki_tpu.advisor.service import AdvisorService

    obs = [({"learning_rate": lr, "batch_size": 32, "epochs": 3}, score)
           for lr, score in ((0.001, 0.4), (0.004, 0.7),
                             (0.012, 0.55), (0.028, 0.3))]

    ref = AdvisorService()
    aid_ref = ref.create_advisor(_gp_knob_config(), kind="gp", seed=7,
                                 engine_kwargs={"n_initial": 4})
    for kn, score in obs:
        ref.feedback(aid_ref, score, kn)
    want = ref.propose_batch(aid_ref, 3)

    rows = [{"id": f"t{i}", "no": i + 1, "knobs": kn, "score": score,
             "status": "COMPLETED"} for i, (kn, score) in enumerate(obs)]
    rows.reverse()  # crashed-process row order must not matter
    re = AdvisorService()
    aid = rehydrate_advisor(re, _gp_knob_config(), "gp", "dead-advisor-id",
                            completed=rows, seed=7,
                            engine_kwargs={"n_initial": 4})
    assert aid == "dead-advisor-id"
    got = re.propose_batch(aid, 3)

    assert json.dumps(got, sort_keys=True) == json.dumps(want, sort_keys=True)


def test_rehydrate_supplements_from_advisor_journals(journaled):
    """Scores the store never saw as completed rows (doomed-trial
    consolation feedback) come back from the kind="advisor" journals:
    feedback joined to its propose by knobs_hash."""
    from rafiki_tpu.advisor.rehydrate import journal_observations
    from rafiki_tpu.obs.search.audit import knobs_hash

    k1 = {"learning_rate": 0.002, "batch_size": 32, "epochs": 3}
    k2 = {"learning_rate": 0.009, "batch_size": 32, "epochs": 3}
    records = [
        {"kind": "advisor", "name": "propose", "advisor_id": "a1",
         "knobs": k1, "knobs_hash": knobs_hash(k1)},
        {"kind": "advisor", "name": "propose", "advisor_id": "a1",
         "knobs": k2, "knobs_hash": knobs_hash(k2)},
        {"kind": "advisor", "name": "feedback", "advisor_id": "a1",
         "knobs_hash": knobs_hash(k1), "score": 0.6},
        {"kind": "advisor", "name": "feedback", "advisor_id": "a1",
         "knobs_hash": knobs_hash(k2), "score": 0.8},
        # another advisor's records never bleed in
        {"kind": "advisor", "name": "feedback", "advisor_id": "OTHER",
         "knobs_hash": knobs_hash(k1), "score": 0.0},
    ]
    got = journal_observations(records, advisor_id="a1")
    assert sorted(s for _, s in got) == [0.6, 0.8]
    # store-covered hashes are excluded (the store row wins)
    got = journal_observations(records, advisor_id="a1",
                               exclude_hashes={knobs_hash(k1)})
    assert [s for _, s in got] == [0.8]


# ---------------------------------------------------------------------------
# Dead-supervisor detection + services-manager reaper
# ---------------------------------------------------------------------------


def test_dead_supervisor_detection(env):
    from rafiki_tpu.constants import TrainJobStatus

    store, params, sub = env
    job_id = sub["train_job_id"]
    store.update_train_job_status(job_id, TrainJobStatus.RUNNING.value)
    assert store.get_jobs_with_dead_supervisor(60) == []  # no supervisor row

    store.create_service(ServiceType.SUPERVISOR.value, job_id=job_id,
                         worker_index=0)
    assert store.get_jobs_with_dead_supervisor(60) == []  # fresh heartbeat
    time.sleep(0.15)
    dead = store.get_jobs_with_dead_supervisor(0.1)
    assert [j["id"] for j in dead] == [job_id]

    # A live next-generation supervisor clears the alarm.
    store.create_service(ServiceType.SUPERVISOR.value, job_id=job_id,
                         worker_index=1)
    assert store.get_jobs_with_dead_supervisor(0.1) == []


def test_reaper_detects_and_resumes_dead_supervisor(env, journaled):
    from rafiki_tpu.admin.services_manager import ServicesManager
    from rafiki_tpu.constants import TrainJobStatus
    from rafiki_tpu.obs.journal import read_dir

    store, params, sub = env
    job_id = sub["train_job_id"]
    store.update_train_job_status(job_id, TrainJobStatus.RUNNING.value)
    store.create_service(ServiceType.SUPERVISOR.value, job_id=job_id,
                         worker_index=0)
    time.sleep(0.15)

    sm = ServicesManager(store, params)
    sm.start_resume_reaper(poll_s=0.05, stale_after_s=0.1)
    try:
        deadline = time.monotonic() + 15
        seen = set()
        while time.monotonic() < deadline:
            seen = {r.get("name") for r in read_dir(journaled)
                    if r.get("kind") == "recovery"
                    and r.get("job_id") == job_id}
            if {"reaper_detected", "resume_started"} <= seen:
                break
            time.sleep(0.05)
        assert {"reaper_detected", "resume_started"} <= seen, seen
    finally:
        sm.stop_resume_reaper()
    # idempotent stop/start
    sm.start_resume_reaper(poll_s=10, stale_after_s=10)
    sm.start_resume_reaper(poll_s=10, stale_after_s=10)
    sm.stop_all()


# ---------------------------------------------------------------------------
# Chaos acceptance scenarios (slow: full subprocess sweeps)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_supervisor_kill_mid_sweep_acceptance():
    """ISSUE 15 acceptance: SIGKILLed sweep resumes in a fresh process
    with (a) best score equal to an unfaulted run under the same
    seeds, (b) zero double-claimed slots by WAL reconcile, (c) a
    non-warmup post-resume propose_batch in the audit journals."""
    from rafiki_tpu.chaos.runner import run_scenario

    report = run_scenario("supervisor-kill-mid-sweep")
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok) \
        + (f"\n{report.error}" if report.error else "")
    names = {c.name for c in report.checks}
    assert {"best_score_matches_unfaulted", "no_double_claims",
            "post_resume_batch_non_warmup",
            "obs_resume_reconstructs"} <= names


@pytest.mark.slow
def test_host_loss_mid_sweep_acceptance():
    from rafiki_tpu.chaos.runner import run_scenario

    report = run_scenario("host-loss-mid-sweep")
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok) \
        + (f"\n{report.error}" if report.error else "")
    names = {c.name for c in report.checks}
    assert {"survivors_repacked", "wal_reconciles_clean"} <= names


def test_sigkilled_sweep_resumes_in_a_fresh_process(tmp_path, capsys):
    """The crash-recovery chain across REAL process boundaries: a 4-trial
    random sweep run through ``scheduler/sweep_proc.py`` with a
    ``supervisor.tick:kill`` fault installed dies by SIGKILL after its
    warm-up claims; a second ``sweep_proc resume`` process adopts the
    job from the WAL, reconciles it with zero duplicate claims, drives
    it to COMPLETED with exactly budget-many trial rows, and ``obs
    resume`` reconstructs the timeline from the journals alone. (The
    slow acceptance scenario above also runs the unfaulted twin and
    compares best scores; this is its tier-1 core.)"""
    from rafiki_tpu.chaos.scenarios import (_make_job, _sweep_proc,
                                            _sweep_proc_env, _train_env)
    from rafiki_tpu.obs import cli
    from rafiki_tpu.scheduler.wal import read_wal, reconcile, wal_path

    budget, chips, per_chip = 4, 2, 2
    log_dir = tmp_path / "obs"

    def child_env(chaos):
        env = _sweep_proc_env(chaos=False)  # never inherit a caller's spec
        env.update(RAFIKI_LOG_DIR=str(log_dir),
                   RAFIKI_SUPERVISOR_HEARTBEAT_S="0.2",
                   RAFIKI_CHECKPOINT_EVERY="1")
        if chaos:
            env["RAFIKI_CHAOS"] = \
                "seed=23;supervisor.tick:kill:after=30:times=1:match=g0"
        return env

    store, params, model = _train_env(tmp_path)
    job = _make_job(store, model, {"MODEL_TRIAL_COUNT": budget})
    killed, _ = _sweep_proc("run", store, params, job["id"], chips=chips,
                            trials_per_chip=per_chip, advisor="random",
                            env=child_env(chaos=True))
    assert killed.returncode == -9, killed.stderr[-300:]
    resumed, summary = _sweep_proc("resume", store, params, job["id"],
                                   chips=chips, trials_per_chip=per_chip,
                                   stale_after_s=0.4,
                                   env=child_env(chaos=False))
    assert resumed.returncode == 0, resumed.stderr[-300:]
    assert summary["mode"] == "wal" and summary["adopted"] > 0
    assert summary["status"] == "COMPLETED"

    trials = store.get_trials_of_train_job(job["id"])
    assert len(trials) == budget
    assert all(t["status"] == "COMPLETED" for t in trials)
    rec = reconcile(read_wal(wal_path(store.path, job["id"])), trials)
    assert rec.ok, rec.errors
    assert [e for r in summary.get("reconcile", [])
            for e in r.get("errors", [])
            if e["type"] == "duplicate_claim"] == []
    capsys.readouterr()
    assert cli.main(["--dir", str(log_dir), "resume", job["id"]]) == 0
    assert "resumed:" in capsys.readouterr().out
