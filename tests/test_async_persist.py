"""Async parameter persistence: durability, ordering, failure containment."""

import time

import pytest

from rafiki_tpu.advisor import AdvisorService
from rafiki_tpu.model.base import load_model_class
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.worker.train import InProcAdvisorHandle, TrainWorker

from tests.test_scheduler import FF_SOURCE, TRAIN, VAL


@pytest.fixture()
def env(tmp_path):
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    model_row = store.create_model("tinyff", "IMAGE_CLASSIFICATION", None,
                                   FF_SOURCE, "TinyFF")
    job = store.create_train_job("aspp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": 3})
    sub = store.create_sub_train_job(job["id"], model_row["id"])
    cls = load_model_class(model_row["model_file"], "TinyFF")
    advisors = AdvisorService()
    aid = advisors.create_advisor(cls.get_knob_config(), kind="random")
    return store, params, job, sub, cls, InProcAdvisorHandle(advisors, aid)


def test_async_persist_all_durable_after_run(env):
    store, params, job, sub, cls, advisor = env
    worker = TrainWorker(store, params, sub["id"], cls, advisor,
                         TRAIN, VAL, job["budget"], async_persist=True)
    n = worker.run()
    assert n == 3
    trials = store.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == 3
    # flush() in run() guarantees every trial is terminal + durable
    assert all(t["status"] == "COMPLETED" for t in trials)
    for t in trials:
        assert t["params_id"] and len(params.load(t["params_id"])) > 100


def test_sync_and_async_agree(env):
    store, params, job, sub, cls, advisor = env
    w = TrainWorker(store, params, sub["id"], cls, advisor, TRAIN, VAL,
                    {"MODEL_TRIAL_COUNT": 1}, async_persist=False)
    assert w.run() == 1
    t = store.get_trials_of_sub_train_job(sub["id"])[0]
    assert t["status"] == "COMPLETED" and t["params_id"]


def test_persist_failure_marks_trial_errored(env, monkeypatch):
    store, params, job, sub, cls, advisor = env

    def boom(parts, params_id=None):
        raise OSError("disk full")

    # every road into the store ends here: ``save`` is one part of it
    monkeypatch.setattr(params, "save_parts", boom)
    worker = TrainWorker(store, params, sub["id"], cls, advisor,
                         TRAIN, VAL, {"MODEL_TRIAL_COUNT": 1},
                         async_persist=True)
    worker.run()
    t = store.get_trials_of_sub_train_job(sub["id"])[0]
    assert t["status"] == "ERRORED"
    assert "params persist failed" in t["error"]
