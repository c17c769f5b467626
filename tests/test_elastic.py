"""In-job elasticity: the scheduler must survive worker death mid-job.

SURVEY.md §5 failure-detection row — the reference got crash-restart
for free from Docker Swarm's restart policy; here the ProcessScheduler
supervise loop is the restart policy: a worker group any member of
which dies is torn down and respawned (bounded retries, backoff), and
the replacement leader CAS-adopts the dead worker's orphaned RUNNING
trial so the job still completes its exact trial budget.

The kill is made deterministic by model templates that SIGKILL their
own worker process from inside train() — first attempt only, gated on
a flag file — which is exactly the mid-trial death window (trial row
exists and is RUNNING, params not yet persisted).
"""

import pathlib
import threading
import time

import pytest

from rafiki_tpu.scheduler import ProcessScheduler
from rafiki_tpu.store import MetaStore, ParamsStore

from tests.test_scheduler import FF_SOURCE, TRAIN, VAL

CRASH_ONCE_SRC = FF_SOURCE.replace(
    b"class TinyFF(JaxModel):",
    b"""class CrashOnceFF(JaxModel):
    def train(self, uri):
        import os, pathlib
        flag = pathlib.Path(os.environ["RAFIKI_TEST_CRASH_FLAG"])
        if not flag.exists():
            flag.write_text("crashed")
            os.kill(os.getpid(), 9)  # SIGKILL: no cleanup, no excepthook
        super().train(uri)
""").replace(b'"TinyFF"', b'"CrashOnceFF"')

ALWAYS_CRASH_SRC = FF_SOURCE.replace(
    b"class TinyFF(JaxModel):",
    b"""class AlwaysCrashFF(JaxModel):
    def train(self, uri):
        import os
        os.kill(os.getpid(), 9)
""").replace(b'"TinyFF"', b'"AlwaysCrashFF"')

# Multihost variants: only the named group process kills itself, and
# only once — the other process blocks in (or heads toward) a
# collective its peer abandoned, which the scheduler must tear down
# directly instead of waiting out the gloo transport timeout.
_MH_CRASH_TMPL = b"""class MhCrashFF(JaxModel):
    def train(self, uri):
        import os, pathlib
        import jax
        flag = pathlib.Path(os.environ["RAFIKI_TEST_CRASH_FLAG"])
        if jax.process_index() == %d and not flag.exists():
            flag.write_text("crashed")
            os.kill(os.getpid(), 9)
        super().train(uri)
"""


def _mh_crash_src(process_index: int) -> bytes:
    return FF_SOURCE.replace(
        b"class TinyFF(JaxModel):", _MH_CRASH_TMPL % process_index,
    ).replace(b'"TinyFF"', b'"MhCrashFF"')


@pytest.fixture()
def env(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_TEST_CRASH_FLAG", str(tmp_path / "crash.flag"))
    monkeypatch.setenv("RAFIKI_WORKER_RESTART_BACKOFF_S", "0.1")
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    return store, params, tmp_path


def _job(store, model, budget):
    job = store.create_train_job("elasticapp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, budget)
    store.create_sub_train_job(job["id"], model["id"])
    return job


def test_sigkilled_worker_restarts_and_budget_completes(env):
    """kill -9 mid-trial: the job must still complete its FULL budget —
    the orphaned trial is adopted (not errored and replaced) and the
    remaining trials run on the replacement worker."""
    store, params, tmp = env
    model = store.create_model("crashff", "IMAGE_CLASSIFICATION", None,
                               CRASH_ONCE_SRC, "CrashOnceFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 3})
    result = ProcessScheduler(store, params).run_train_job(
        job["id"], n_workers=1, advisor_kind="random", platform="cpu",
        poll_s=0.2)
    assert (tmp / "crash.flag").exists(), "the crash never happened"
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 3, "budget shrank or overshot after restart"
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    # Every surviving trial ran on (or was adopted by) the restarted
    # worker, whose id carries the attempt suffix.
    assert {t["worker_id"] for t in result.trials} == \
        {f"{job['id'][:8]}-p0-r1"}
    # The adopted trial's params are loadable like any other's.
    assert len(params.load(result.best_trials[0]["params_id"])) > 100


def test_restarts_exhausted_marks_job_errored(env, monkeypatch):
    """A worker that dies on every attempt must not loop forever: after
    max_restarts the group is given up, its orphan is marked ERRORED,
    and the failure is recorded on the result."""
    store, params, _ = env
    monkeypatch.setenv("RAFIKI_WORKER_MAX_RESTARTS", "1")
    model = store.create_model("alwayscrash", "IMAGE_CLASSIFICATION", None,
                               ALWAYS_CRASH_SRC, "AlwaysCrashFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 2})
    result = ProcessScheduler(store, params).run_train_job(
        job["id"], n_workers=1, advisor_kind="random", platform="cpu",
        poll_s=0.2)
    assert result.status == "ERRORED"
    assert result.errors, "permanent worker death left no trace"
    assert all(t["status"] == "ERRORED" for t in result.trials)
    assert all("restarts exhausted" in (t["error"] or "")
               for t in result.trials)


def test_stop_during_backoff_terminates_orphan(env, monkeypatch):
    """Stopping a job while a crashed group waits out its restart
    backoff must not leave the orphaned trial RUNNING — a later
    periodic recovery sweep would resurrect a trial of a job the user
    explicitly stopped."""
    store, params, _ = env
    monkeypatch.setenv("RAFIKI_WORKER_RESTART_BACKOFF_S", "60")
    model = store.create_model("alwayscrash", "IMAGE_CLASSIFICATION", None,
                               ALWAYS_CRASH_SRC, "AlwaysCrashFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 5})
    stop = threading.Event()
    out = {}

    def run():
        out["result"] = ProcessScheduler(store, params).run_train_job(
            job["id"], n_workers=1, advisor_kind="random", platform="cpu",
            poll_s=0.2, stop_event=stop)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    # Wait until the crash landed the group in its 60s backoff window
    # (trial exists and its worker is dead), then stop the job.
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        trials = store.get_trials_of_train_job(job["id"])
        if trials:
            time.sleep(2)  # let the supervise loop notice the corpse
            break
        time.sleep(0.2)
    stop.set()
    th.join(timeout=60)
    assert not th.is_alive()
    assert out["result"].status == "STOPPED"
    for t in store.get_trials_of_train_job(job["id"]):
        assert t["status"] in ("TERMINATED", "COMPLETED", "ERRORED"), \
            f"orphan left {t['status']} on a stopped job"


@pytest.mark.parametrize("crash_process", [1, 0],
                         ids=["follower-killed", "leader-killed"])
def test_multihost_group_member_sigkill_respawns_group(env, crash_process):
    """kill -9 one member of a 2-process dp group: the scheduler tears
    the whole group down at once (no transport-timeout wait) and
    respawns it; the new leader adopts the orphan and the budget still
    completes."""
    store, params, tmp = env
    model = store.create_model("mhcrash", "IMAGE_CLASSIFICATION", None,
                               _mh_crash_src(crash_process), "MhCrashFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 2})
    t0 = time.monotonic()
    result = ProcessScheduler(store, params).run_train_job(
        job["id"], n_workers=1, devices_per_trial=1, advisor_kind="random",
        platform="cpu", poll_s=0.2, multihost_processes=2)
    wall = time.monotonic() - t0
    assert (tmp / "crash.flag").exists(), "the crash never happened"
    assert result.status == "COMPLETED", result.errors
    completed = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(completed) == 2
    # Group teardown is direct process supervision; it must not have
    # waited out a multi-minute collective transport timeout.
    assert wall < 180, f"group teardown took {wall:.0f}s — timeout-bound?"


# ---------------------------------------------------------------------------
# Mesh sweep elasticity (docs/mesh_sweep.md): k packed trials per chip
# × N chips, re-packed onto survivors when a chip is lost, degraded to
# single-chip mode when the mesh cannot form. CPU mesh: the conftest
# pins 8 virtual host devices.
# ---------------------------------------------------------------------------

# ChaosFF (3 epochs, lr the only tuned knob → ONE packing bucket, so
# assignment splits deterministically across chips) and EvictFF (its
# early-stop variant) come from the chaos catalog — same fixtures the
# scenario runner exercises.
from rafiki_tpu.chaos.scenarios import EVICT_SOURCE  # noqa: E402
from rafiki_tpu.chaos.scenarios import FF_SOURCE as CHAOS_FF_SOURCE  # noqa: E402


def test_mesh_sweep_packs_trials_across_chips(env):
    from rafiki_tpu.scheduler import MeshSweepScheduler

    store, params, _ = env
    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               CHAOS_FF_SOURCE, "ChaosFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 4})
    result = MeshSweepScheduler(store, params).run_sweep(
        job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 4
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert all(t.get("score") is not None for t in result.trials)
    # One packing bucket round-robins across both chips: each trained 2.
    workers = sorted({t["worker_id"] for t in result.trials})
    assert workers == [f"{job['id'][:8]}-mesh-c0", f"{job['id'][:8]}-mesh-c1"]


def test_mesh_chip_killed_mid_sweep_repacks_onto_survivor(env, monkeypatch):
    from rafiki_tpu import telemetry
    from rafiki_tpu.chaos import FaultPlane, install, uninstall
    from rafiki_tpu.scheduler import MeshSweepScheduler

    store, params, _ = env
    monkeypatch.setenv("RAFIKI_CHECKPOINT_EVERY", "1")
    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               CHAOS_FF_SOURCE, "ChaosFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 4})
    telemetry.reset()
    install(FaultPlane.from_spec(
        "seed=11;scheduler.preempt:kill:after=2:times=1:match=chip1"))
    try:
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    finally:
        uninstall()
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 4, "chip loss lost or duplicated rows"
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert all(t.get("score") is not None for t in result.trials), \
        "a surviving trial finished without a recorded score"
    assert telemetry.get_counter("mesh.chips_lost") >= 1.0
    # The re-packed rows finished under the surviving chip's worker.
    assert any((t["worker_id"] or "").endswith("-mesh-c0")
               for t in result.trials)


def test_pack_straggler_evicted_and_backfilled(env):
    from rafiki_tpu import telemetry
    from rafiki_tpu.advisor import AdvisorService
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.model.knobs import knob_config_signature
    from rafiki_tpu.worker.train import (InProcAdvisorHandle,
                                         PackedTrialRunner, TrainWorker)

    store, params, _ = env
    telemetry.reset()
    model = store.create_model("evictff", "IMAGE_CLASSIFICATION", None,
                               EVICT_SOURCE, "EvictFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 3})
    sub = store.get_sub_train_jobs(job["id"])[0]
    cls = load_model_class(EVICT_SOURCE, "EvictFF")
    advisors = AdvisorService()
    advisor_id = advisors.create_advisor(cls.get_knob_config(), kind="random")
    worker = TrainWorker(
        store, params, sub["id"], cls,
        InProcAdvisorHandle(advisors, advisor_id), TRAIN, VAL,
        {"MODEL_TRIAL_COUNT": 3}, worker_id="evict-w0", async_persist=False)
    knob_config = cls.get_knob_config()
    base = {"hidden_units": 16, "batch_size": 32, "epochs": 3}
    rows = []
    # lr >= 0.02 trips EvictFF.should_stop_early at epoch 0: member 0
    # is the straggler, member 1 trains its full 3-epoch budget.
    for kn in (dict(base, learning_rate=0.025),
               dict(base, learning_rate=0.005)):
        trial = store.create_trial(
            sub["id"], "EvictFF", kn,
            shape_sig=knob_config_signature(knob_config, kn), budget_max=3)
        rows.append((trial["id"], kn))
    n = PackedTrialRunner(worker, 2).run_assigned(rows, budget_max=3)
    assert n == 3, "the freed slot was not backfilled"
    trials = store.get_trials_of_train_job(job["id"])
    assert len(trials) == 3
    assert all(t["status"] == "COMPLETED" for t in trials)
    assert all(t.get("score") is not None for t in trials)
    assert telemetry.get_counter("trial_pack.evictions") >= 1.0, \
        "the straggler was never evicted from the stacked state"
    assert telemetry.get_counter("trial_pack.backfills") >= 1.0, \
        "no freshly proposed trial was admitted mid-pack"


def test_mesh_backfill_respects_trial_budget(env):
    """Mid-pack backfill on the MESH path must claim atomic budget
    slots. Threshold 0.005 puts the seed-0 proposal sequence (0.0087,
    0.0025, 0.0011, …) one early-stopper per pack round: each eviction
    frees a slot that backfill refills until MODEL_TRIAL_COUNT drains.
    Without budget_max threaded through the chip runner into
    run_assigned, backfill's create_trial skips the slot claim — trials
    exceed the budget and the pack never drains (this test hanging is
    the failure mode)."""
    from rafiki_tpu.scheduler import MeshSweepScheduler

    store, params, _ = env
    src = EVICT_SOURCE.replace(b">= 0.02", b">= 0.005")
    model = store.create_model("allstopff", "IMAGE_CLASSIFICATION", None,
                               src, "EvictFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 5})
    result = MeshSweepScheduler(store, params).run_sweep(
        job["id"], chips=1, trials_per_chip=2, advisor_kind="random")
    assert result.status == "COMPLETED", result.errors
    trials = store.get_trials_of_train_job(job["id"])
    assert len(trials) == 5, \
        f"backfill bypassed the trial-count budget ({len(trials)} rows)"
    assert all(t["status"] == "COMPLETED" for t in trials)
    assert all(t.get("score") is not None for t in trials)


def test_mesh_degrades_to_single_chip(env, monkeypatch):
    from rafiki_tpu import telemetry
    from rafiki_tpu.chaos import FaultPlane, install, uninstall
    from rafiki_tpu.scheduler import MeshSweepScheduler

    store, params, _ = env
    monkeypatch.setenv("RAFIKI_MESH_INIT_RETRIES", "2")
    monkeypatch.setenv("RAFIKI_MESH_INIT_BACKOFF_S", "0.01")
    monkeypatch.setenv("RAFIKI_MESH_FORM_GRACE_S", "5")
    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               CHAOS_FF_SOURCE, "ChaosFF")
    job = _job(store, model, {"MODEL_TRIAL_COUNT": 2})
    telemetry.reset()
    install(FaultPlane.from_spec("seed=17;collective.init:error:times=8"))
    try:
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    finally:
        uninstall()
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 2
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert telemetry.get_counter("mesh.degraded_single_chip") >= 1.0
    assert telemetry.get_counter("mesh.init_retries") >= 2.0
    # Everything ran on the single surviving chip's worker.
    assert {t["worker_id"] for t in result.trials} == \
        {f"{job['id'][:8]}-mesh-c0"}


def test_mesh_sweep_honours_a_time_budget_by_rounds(env):
    """With TIME_HOURS the sweep runs round after round while the budget's
    clock runs: more trials than one round's chips x k slots, every one
    completed, none started after the deadline's round; with a trial-count
    budget beside it the count still bounds the sweep."""
    from rafiki_tpu.scheduler import MeshSweepScheduler

    store, params, _ = env
    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               CHAOS_FF_SOURCE, "ChaosFF")
    # One round without the clock first: it compiles the pack's programs,
    # which on a loaded machine takes longer than the budget below.
    warm = _job(store, model, {"MODEL_TRIAL_COUNT": 4})
    result = MeshSweepScheduler(store, params).run_sweep(
        warm["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    assert result.status == "COMPLETED" and len(result.trials) == 4
    budget_s = 6.0
    job = _job(store, model, {"TIME_HOURS": budget_s / 3600.0})
    t0 = time.monotonic()
    result = MeshSweepScheduler(store, params).run_sweep(
        job["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    wall = time.monotonic() - t0
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) > 4 and len(result.trials) % 4 == 0
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert budget_s <= wall < 60.0
    # The last round was started because the one before it ended inside the
    # budget (a trial's own ``started_at`` lags its round's start, so it is
    # the earlier rounds' ends that are exact).
    earlier = sorted(result.trials, key=lambda t: t["started_at"])[:-4]
    assert max(t["stopped_at"] for t in earlier) - job["created_at"] < budget_s
    both = _job(store, model, {"TIME_HOURS": 1.0, "MODEL_TRIAL_COUNT": 6})
    result = MeshSweepScheduler(store, params).run_sweep(
        both["id"], chips=2, trials_per_chip=2, advisor_kind="random")
    assert result.status == "COMPLETED" and len(result.trials) == 6
