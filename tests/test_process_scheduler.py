"""Process-per-chip scheduling: subprocess workers + HTTP advisor.

Runs real OS subprocesses (CPU platform) sharing the sqlite meta store
and a loopback advisor server — the production scheduler shape,
exercised hermetically.
"""

import threading
import time

import pytest

from rafiki_tpu.scheduler import ProcessScheduler, worker_device_env
from rafiki_tpu.store import MetaStore, ParamsStore

from tests.test_scheduler import FF_SOURCE, TRAIN, VAL


@pytest.fixture()
def env(tmp_path):
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    model = store.create_model("tinyff", "IMAGE_CLASSIFICATION", None,
                               FF_SOURCE, "TinyFF")
    return store, params, model


def _make_job(store, model, budget):
    job = store.create_train_job("procapp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, budget)
    store.create_sub_train_job(job["id"], model["id"])
    return job


def test_device_env_cpu():
    env = worker_device_env("cpu", 0, devices_per_trial=2)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "device_count=2" in env["XLA_FLAGS"]


def test_device_env_tpu():
    env = worker_device_env("tpu", 3, devices_per_trial=1)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    env2 = worker_device_env("tpu", 1, devices_per_trial=2)
    assert env2["TPU_VISIBLE_CHIPS"] == "2,3"


SLOW_FF_SOURCE = FF_SOURCE.replace(
    b"class TinyFF(JaxModel):",
    b"""class SlowFF(JaxModel):
    def train(self, uri):
        import time
        time.sleep(1.0)  # outlast subprocess startup skew
        super().train(uri)
""",
).replace(b'"TinyFF"', b'"SlowFF"')


def test_process_train_job(env, tmp_path):
    """BOTH subprocess workers must really run trials (budget shared
    via the sqlite atomic claim): each trial sleeps 1s, so one worker
    cannot drain the 8-trial budget during the other's startup skew
    (both spawn concurrently; skew between them is well under 8s)."""
    store, params, _ = env
    model = store.create_model("slowff", "IMAGE_CLASSIFICATION", None,
                               SLOW_FF_SOURCE, "SlowFF")
    job = _make_job(store, model, {"MODEL_TRIAL_COUNT": 8})
    sched = ProcessScheduler(store, params)
    result = sched.run_train_job(job["id"], n_workers=2,
                                 advisor_kind="random", platform="cpu")
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 8
    completed = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(completed) == 8
    workers = {t["worker_id"] for t in completed}
    assert len(workers) == 2, f"budget drained by one worker: {workers}"
    # params written by the subprocess are loadable here
    best = result.best_trials[0]
    assert len(params.load(best["params_id"])) > 100


def test_workers_populate_persistent_xla_cache(env, tmp_path, monkeypatch):
    """Subprocess workers enable jax's on-disk compilation cache
    (worker/main.py): after a job, compiled executables are on disk for
    future processes to load instead of recompiling."""
    cache_dir = tmp_path / "xla-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    monkeypatch.setenv("RAFIKI_XLA_CACHE_MIN_S", "0")  # CPU compiles are fast
    store, params, model = env
    job = _make_job(store, model, {"MODEL_TRIAL_COUNT": 1})
    sched = ProcessScheduler(store, params)
    result = sched.run_train_job(job["id"], n_workers=1,
                                 advisor_kind="random", platform="cpu")
    assert result.status == "COMPLETED", result.errors
    entries = list(cache_dir.glob("*"))
    assert entries, "no persistent-cache entries written by the worker"


def test_process_job_stop_event(env):
    store, params, model = env
    # Budget must exceed what 2 workers can finish in the 10s window
    # below, or stop_event has nothing left to interrupt — with a warm
    # persistent XLA cache throughput tops 50 trials/s, so 500 was
    # within reach.
    job = _make_job(store, model, {"MODEL_TRIAL_COUNT": 5000})
    sched = ProcessScheduler(store, params)
    stop = threading.Event()
    out = {}

    def run():
        out["result"] = sched.run_train_job(job["id"], n_workers=2,
                                            advisor_kind="random",
                                            platform="cpu", stop_event=stop)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    time.sleep(10)
    stop.set()
    th.join(timeout=60)
    assert not th.is_alive()
    assert out["result"].status == "STOPPED"
    assert len(out["result"].trials) < 5000


# ---------------------------------------------------------------------------
# Group liveness: a follower exiting rc=0 mid-trial (round-4 ADVICE d)
# ---------------------------------------------------------------------------


class _StubProc:
    """poll()-only stand-in for a subprocess.Popen in _WorkerGroup."""

    def __init__(self, rc):
        self._rc = rc

    def poll(self):
        return self._rc


def _group(*rcs):
    from rafiki_tpu.scheduler.process import _WorkerGroup

    g = _WorkerGroup(0)
    g.procs = [_StubProc(rc) for rc in rcs]
    return g


def test_follower_rc0_midtrial_fails_group_after_grace(monkeypatch):
    """The wedge: follower gone rc=0, leader alive. The group must go
    'failed' once the grace window elapses — not sit 'running' until
    the collective transport timeout minutes later."""
    monkeypatch.setenv("RAFIKI_FOLLOWER_EXIT_GRACE_S", "0.2")
    g = _group(None, 0)  # leader alive, follower exited clean
    assert g.state() == "running"  # first observation arms the clock
    assert g.partial_exit_at is not None
    time.sleep(0.3)
    assert g.state() == "failed"


def test_follower_rc0_within_grace_stays_running(monkeypatch):
    monkeypatch.setenv("RAFIKI_FOLLOWER_EXIT_GRACE_S", "30")
    g = _group(None, 0)
    assert g.state() == "running"
    assert g.state() == "running"  # second poll inside grace: still up


def test_clean_group_exit_is_ok_not_failed():
    g = _group(0, 0)
    assert g.state() == "ok"
    assert g.partial_exit_at is None


def test_follower_nonzero_exit_fails_immediately():
    g = _group(None, 1)  # crash path keeps its zero-delay behavior
    assert g.state() == "failed"


def test_leader_clean_exit_with_follower_draining_stays_running():
    # Leader done (budget drained), follower still flushing: normal
    # shutdown tail, must NOT arm the partial-exit clock.
    g = _group(0, None)
    assert g.state() == "running"
    assert g.partial_exit_at is None
