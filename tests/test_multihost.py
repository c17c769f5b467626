"""Multi-host DCN path: a dp train step really spanning 2 processes.

Exercises the previously-dead ``jax.distributed.initialize`` hook in
worker/main.py end to end: ProcessScheduler emits the coordinator env
for a 2-process worker group; process 0 (leader) runs the trial loop,
process 1 mirrors it (worker/follower.py); each process contributes 2
fake CPU devices, so every train step is a 4-device dp program whose
gradient all-reduce crosses the process boundary over the gloo
transport (DCN's stand-in on CPU). Completion is itself load-bearing
evidence: the leader's collectives BLOCK unless the follower joins
them — a dead DCN path hangs the job, it cannot quietly pass.
"""

import threading

import pytest

from rafiki_tpu.scheduler import ProcessScheduler
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.utils.events import events

from tests.test_scheduler import FF_SOURCE, TRAIN, VAL


@pytest.fixture()
def env(tmp_path):
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    model = store.create_model("tinyff", "IMAGE_CLASSIFICATION", None,
                               FF_SOURCE, "TinyFF")
    prev = events.path
    events.configure(tmp_path / "logs")
    yield store, params, model
    if prev is not None:
        events.configure(prev.parent)
    else:
        events._path = None
        events._fh = None


def test_multihost_dp_train_job(env):
    store, params, model = env
    job = store.create_train_job("mhapp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 2})
    store.create_sub_train_job(job["id"], model["id"])
    sched = ProcessScheduler(store, params)
    result = sched.run_train_job(job["id"], n_workers=1, devices_per_trial=2,
                                 advisor_kind="random", platform="cpu",
                                 multihost_processes=2)
    assert result.status == "COMPLETED", result.errors
    completed = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(completed) == 2
    assert all(t["params_id"] for t in completed)

    # Both processes joined one jax.distributed cluster and saw the
    # 4-device global mesh (2 local x 2 processes).
    inits = list(events.read("multihost_init"))
    assert {e["process_id"] for e in inits} == {0, 1}
    assert all(e["process_count"] == 2 for e in inits)
    assert all(e["global_devices"] == 4 for e in inits)
    assert all(e["local_devices"] == 2 for e in inits)


def test_multihost_two_groups_do_not_cross_mirror(env):
    """Two 2-process groups on one sub-job: each follower must mirror
    ONLY its own leader's trials (a follower entering another group's
    collectives deadlocks the job — this test hanging is the failure
    mode)."""
    store, params, model = env
    job = store.create_train_job("mh2g", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 6})
    store.create_sub_train_job(job["id"], model["id"])
    sched = ProcessScheduler(store, params)
    result = sched.run_train_job(job["id"], n_workers=2, devices_per_trial=2,
                                 advisor_kind="random", platform="cpu",
                                 multihost_processes=2)
    assert result.status == "COMPLETED", result.errors
    completed = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(completed) == 6
    inits = list(events.read("multihost_init"))
    assert len(inits) == 4  # 2 groups x 2 processes


def test_multihost_time_budget_terminates(env):
    """A TIME_HOURS-only budget (no trial count) must still terminate
    the whole group: the leader marks its service row stopped before
    exiting and the follower watches it — otherwise follower waits for
    a sub-job status the scheduler only writes after the follower
    itself exits (circular wait)."""
    store, params, model = env
    job = store.create_train_job("mhtime", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"TIME_HOURS": 8.0 / 3600})
    store.create_sub_train_job(job["id"], model["id"])
    sched = ProcessScheduler(store, params)
    result = sched.run_train_job(job["id"], n_workers=1, devices_per_trial=2,
                                 advisor_kind="random", platform="cpu",
                                 multihost_processes=2)
    # Termination IS the assertion (the deadlock would hang this test);
    # trial count depends on how much of the 8s window startup ate.
    assert result.status == "COMPLETED", result.errors


def test_backend_init_watchdog_exits_structured(tmp_path):
    """A worker whose backend init hangs (unreachable runtime or
    coordinator) must exit with a structured error instead of stalling
    the scheduler's supervise loop forever."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "RAFIKI_WORKER_DB": str(tmp_path / "meta.sqlite3"),
        "RAFIKI_WORKER_PARAMS_DIR": str(tmp_path / "params"),
        "RAFIKI_WORKER_SUB_JOB_ID": "nope",
        "RAFIKI_WORKER_ADVISOR_URL": "http://127.0.0.1:1",
        "RAFIKI_WORKER_ADVISOR_ID": "nope",
        # coordinator that will never answer -> distributed init blocks
        "RAFIKI_COORDINATOR_ADDRESS": "127.0.0.1:1",
        "RAFIKI_NUM_PROCESSES": "2",
        "RAFIKI_PROCESS_ID": "1",
        "RAFIKI_BACKEND_INIT_TIMEOUT_S": "2",
    })
    r = subprocess.run([sys.executable, "-m", "rafiki_tpu.worker.main"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 17
    assert "backend init exceeded" in r.stdout


def test_multihost_stop_event(env):
    """Stopping a multihost job terminates leader AND followers."""
    store, params, model = env
    job = store.create_train_job("mhstop", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 10_000})
    store.create_sub_train_job(job["id"], model["id"])
    sched = ProcessScheduler(store, params)
    stop = threading.Event()
    out = {}

    def run():
        out["result"] = sched.run_train_job(
            job["id"], n_workers=1, devices_per_trial=2,
            advisor_kind="random", platform="cpu",
            multihost_processes=2, stop_event=stop)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    import time

    time.sleep(20)
    stop.set()
    th.join(timeout=90)
    assert not th.is_alive()
    assert out["result"].status == "STOPPED"


# ---------------------------------------------------------------------------
# Collective-init retry (worker/main.py initialize_collective): the
# flakiest moment of a multihost job gets bounded retries with backoff.
# Driven with a fake initialize fn — no real jax.distributed cluster.
# ---------------------------------------------------------------------------


def test_collective_init_retries_transient_failure(monkeypatch):
    from rafiki_tpu.worker.main import initialize_collective

    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_RETRIES", "3")
    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_BACKOFF_S", "0.01")
    calls = []

    def flaky(coordinator_address, num_processes, process_id):
        calls.append((coordinator_address, num_processes, process_id))
        if len(calls) == 1:
            raise RuntimeError("transient barrier race")

    initialize_collective(flaky, "127.0.0.1:9999", 2, 1)
    assert len(calls) == 2, "the failed attempt was not retried"
    assert calls[-1] == ("127.0.0.1:9999", 2, 1)


def test_collective_init_exhaustion_reraises(monkeypatch):
    from rafiki_tpu.worker.main import initialize_collective

    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_RETRIES", "2")
    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_BACKOFF_S", "0.01")
    calls = []

    def dead(coordinator_address, num_processes, process_id):
        calls.append(1)
        raise RuntimeError("coordinator unreachable")

    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        initialize_collective(dead, "127.0.0.1:9999", 2, 0)
    assert len(calls) == 3, "retries + the final attempt"


def test_collective_init_chaos_fault_absorbed_by_retry(monkeypatch):
    """An injected collective.init error (the chaos site armed per
    attempt) must be absorbed exactly like a real init failure: the
    faulted attempt never reaches the initialize fn, the retry does."""
    from rafiki_tpu.chaos import FaultPlane, install, uninstall
    from rafiki_tpu.worker.main import initialize_collective

    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_RETRIES", "3")
    monkeypatch.setenv("RAFIKI_COLLECTIVE_INIT_BACKOFF_S", "0.01")
    calls = []

    def ok(coordinator_address, num_processes, process_id):
        calls.append(1)

    install(FaultPlane.from_spec("seed=5;collective.init:error:times=1"))
    try:
        initialize_collective(ok, "127.0.0.1:9999", 2, 0)
    finally:
        uninstall()
    assert len(calls) == 1, "the injected-fault attempt leaked through"
