"""Persist off the critical path of a pack round (ISSUE 26).

The contract under test:
  * a finished pack round copies its stacked parameters to the host once
    and every member's blob from that copy is, byte for byte, the blob a
    fetch of the member's own device slices gives, under both stored
    dtypes, and loads to the same predictions;
  * a round's persist issues one stacked cast and no per-member slice, and
    the saver's thread does no device work at all;
  * what is not a live slice view of the round's pack (a detached member,
    a serial trial) keeps the per-member fetch, and two counters say which
    path a member took;
  * the saver is bounded by rounds: with a store that blocks, the worker
    stalls at the second round's first submit and not before, two rounds'
    host copies at most are alive, and an idle saver keeps none;
  * a copy or a write that raises errors exactly the trials it touched and
    the saver's thread lives on; ``flush()`` returns only when every row
    of the round is COMPLETED with parameters that load.
"""

import gc
import pickle
import threading
import time

import numpy as np
import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.chaos.scenarios import EVICT_SOURCE
from rafiki_tpu.utils import serial
from rafiki_tpu.utils.serial import StackedHostCopy, dump_pytree

from tests.test_trial_pack import _ScriptedAdvisor

TRAIN = "synthetic://images?classes=4&n=256&w=8&h=8&c=1&seed=0"
VAL = "synthetic://images?classes=4&n=100&w=8&h=8&c=1&seed=1"
LRS = [1e-2, 3e-3, 1e-3, 3e-2]      # as tests/test_trial_pack.py's
PACK = 4

SRC = b"""
from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models.ff import _Mlp

class RoundFF(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "learning_rate": FloatKnob(1e-3, 3e-2, is_exp=True),
            "batch_size": FixedKnob(64),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Mlp(hidden_layers=1, hidden_units=32, num_classes=num_classes)
"""


def _cls(src=SRC, name="RoundFF"):
    from rafiki_tpu.model.base import load_model_class

    return load_model_class(src, name)


def _counters():
    c = telemetry.snapshot()["counters"]
    return (c.get("persist.members_from_round_copy", 0.0),
            c.get("persist.members_fetched_alone", 0.0))


@pytest.fixture()
def stored_dtype(request):
    from rafiki_tpu.config import Config, get_config, set_config

    prev = get_config()
    set_config(Config(data_dir=prev.data_dir,
                      serving_params_dtype=request.param))
    yield request.param
    set_config(prev)


def _trained_pack(cls=None, lrs=LRS):
    cls = cls or _cls()
    models = [cls(learning_rate=lr, batch_size=64, epochs=1, seed=0)
              for lr in lrs]
    cls.train_packed(models, TRAIN)
    return cls, models


# -- (a) the blob ---------------------------------------------------------------


@pytest.mark.parametrize("stored_dtype", ["bfloat16", "float32"],
                         indirect=True)
def test_every_members_blob_from_the_round_copy_is_the_fetched_blob(
        stored_dtype):
    cls, models = _trained_pack()
    packed = models[0]._loop.packed
    cast = stored_dtype == "bfloat16"
    alone = [m.dump_parameters() for m in models]       # today's path
    want = [dump_pytree(packed.trial_params(i), cast_f32_to_bf16=cast)
            for i in range(PACK)]
    before = _counters()
    cls.stage_packed_dump(models)
    assert packed.host_copy is not None and not packed.host_copy.fetched
    for i, m in enumerate(models):
        blob = m.dump_parameters()
        assert pickle.loads(blob)["packed"] == want[i]
        assert blob == alone[i]
    assert packed.host_copy.fetched
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (PACK, 0)
    import jax

    assert {a.dtype.name for a in
            jax.tree.leaves(serial.load_pytree(want[0]))} == {stored_dtype}
    # ... and is served like any other
    x = np.random.default_rng(0).normal(size=(16, 8, 8, 1)).astype(np.float32)
    for m, blob in zip(models, alone):
        fresh, ref = cls(**m.knobs), cls(**m.knobs)
        fresh.load_parameters(m.dump_parameters())
        ref.load_parameters(blob)
        np.testing.assert_array_equal(fresh.predict_proba(x),
                                      ref.predict_proba(x))
        np.testing.assert_allclose(fresh.predict_proba(x), m.predict_proba(x),
                                   atol=2e-2)


def test_a_host_tree_is_dumped_without_the_device(monkeypatch):
    import jax.numpy as jnp

    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16), "n": jnp.int32(3)}}
    host = {"a": np.asarray(tree["a"]),
            "b": {"c": np.asarray(tree["b"]["c"]), "n": np.asarray(3, np.int32)}}
    want = dump_pytree(tree, cast_f32_to_bf16=False)

    class _NoDevice:
        def __getattr__(self, name):
            raise AssertionError(f"jnp.{name} on a host tree")

    monkeypatch.setattr(serial, "jnp", _NoDevice())
    assert dump_pytree(host, cast_f32_to_bf16=False) == want


def test_a_64_bit_numpy_leaf_is_still_narrowed():
    # as ``jnp.asarray`` always did: the bytes of older blobs do not change
    blob = dump_pytree({"w": np.arange(3, dtype=np.float64),
                        "i": np.arange(3, dtype=np.int64)},
                       cast_f32_to_bf16=False)
    out = serial.load_pytree(blob)
    assert out["w"].dtype == np.float32 and out["i"].dtype == np.int32


def test_anything_that_changes_the_state_drops_the_copy():
    _cls_, models = _trained_pack()
    packed = models[0]._loop.packed
    for change in (lambda: packed.evict(1),
                   lambda: packed.admit(7, {k: float(v[0]) for k, v in
                                            packed.state[4].items()})):
        packed.stage_host_params(True)
        assert models[0]._loop.host_copy is packed.host_copy is not None
        change()
        assert packed.host_copy is None


# -- the worker -----------------------------------------------------------------


def _mk_worker(tmp_path, n_trials, trial_pack=PACK, async_persist=True,
               params_store=None):
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import TrainWorker

    store = MetaStore(tmp_path / "meta.sqlite3")
    params = params_store or ParamsStore(tmp_path / "params")
    model = store.create_model("roundff", "IMAGE_CLASSIFICATION", None,
                               SRC, "RoundFF")
    job = store.create_train_job("app", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, {"MODEL_TRIAL_COUNT": n_trials})
    sub = store.create_sub_train_job(job["id"], model["id"])
    adv = _ScriptedAdvisor(dict(batch_size=64, epochs=1, seed=0))
    worker = TrainWorker(store, params, sub["id"], _cls(), adv, TRAIN, VAL,
                         {"MODEL_TRIAL_COUNT": n_trials},
                         async_persist=async_persist, trial_pack=trial_pack)
    return store, params, worker, adv, sub


def _rows(store, sub):
    return sorted(store.get_trials_of_sub_train_job(sub["id"]),
                  key=lambda t: t["started_at"])


# -- (b) one stacked cast a round, nothing on the saver's thread ---------------


def test_a_rounds_persist_is_one_stacked_cast_and_the_saver_stays_off_the_device(
        tmp_path, monkeypatch):
    import jax

    from rafiki_tpu.ops.train import PackedTrainLoop
    from rafiki_tpu.worker.train import TrainWorker

    calls = []

    def spy(what, real, shape_of=None):
        def wrapped(*a, **kw):
            calls.append((what, threading.current_thread().name,
                          shape_of(*a) if shape_of else None))
            return real(*a, **kw)
        return wrapped

    monkeypatch.setattr(serial, "_cast_tree_bf16", spy(
        "cast", serial._cast_tree_bf16,
        lambda tree: jax.tree.leaves(tree)[0].shape[0]))
    monkeypatch.setattr(PackedTrainLoop, "trial_params", spy(
        "slice", PackedTrainLoop.trial_params))
    monkeypatch.setattr(PackedTrainLoop, "trial_state", spy(
        "slice", PackedTrainLoop.trial_state))
    monkeypatch.setattr(jax, "device_get", spy("device_get", jax.device_get))
    real_persist = TrainWorker._persist

    def guarded(self, tid, model, score):
        # On the CPU a slice of a device array is what the guard refuses
        # (its index is a host-to-device transfer): a per-member fetch
        # here would error the trial.
        with jax.transfer_guard("disallow"):
            return real_persist(self, tid, model, score)

    monkeypatch.setattr(TrainWorker, "_persist", guarded)
    store, params, worker, _adv, sub = _mk_worker(tmp_path, 2 * PACK)
    telemetry.reset()
    before = _counters()
    assert worker.run() == 2 * PACK
    rows = _rows(store, sub)
    assert [t["status"] for t in rows] == ["COMPLETED"] * (2 * PACK), \
        [t.get("error") for t in rows]
    assert [(w, k) for w, _t, k in calls if w == "cast"] == [("cast", PACK)] * 2
    assert not [c for c in calls if c[0] == "slice"]
    assert not [c for c in calls if c[1].startswith("saver-")]
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (2 * PACK, 0)
    # once a round, on the saver's thread: the wait for the stacked copy
    fetches = [r for r in telemetry.span_records()
               if r["name"] == "persist.fetch"
               and r["thread"] == f"saver-{worker.worker_id}"]
    assert len(fetches) == 2


def test_without_a_saver_the_rounds_copy_serves_the_workers_own_thread(tmp_path):
    # the mesh scheduler's workers persist synchronously (run_assigned)
    store, _params, worker, _adv, sub = _mk_worker(tmp_path, 2 * PACK,
                                                   async_persist=False)
    telemetry.reset()
    assert worker.run() == 2 * PACK
    assert [t["status"] for t in _rows(store, sub)] == ["COMPLETED"] * (2 * PACK)
    assert _counters() == (2 * PACK, 0)
    me = threading.current_thread().name
    by_name = {}
    for r in telemetry.span_records():
        if r["name"].startswith("persist."):
            assert r["thread"] == me
            by_name[r["name"]] = by_name.get(r["name"], 0) + 1
    assert by_name == {"persist.dispatch": 2, "persist.fetch": 2,
                       "persist.write": 2 * PACK, "persist.mark": 2 * PACK}


# -- (c) what keeps the per-member fetch ----------------------------------------


def test_a_serial_trial_fetches_alone(tmp_path):
    store, _params, worker, _adv, sub = _mk_worker(tmp_path, 3, trial_pack=1)
    before = _counters()
    assert worker.run() == 3
    assert [t["status"] for t in _rows(store, sub)] == ["COMPLETED"] * 3
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 3)


def test_a_detached_member_fetches_alone_beside_the_rounds_copy():
    from rafiki_tpu.ops.train import PackedSliceLoop, TrainLoop

    cls = _cls(EVICT_SOURCE, "EvictFF")
    # lr >= 0.02 stops after its first epoch: detached into a TrainLoop;
    # the other two end together, as live slice views of a pack of two.
    models = [cls(hidden_units=16, batch_size=32, epochs=3, learning_rate=lr)
              for lr in (0.025, 0.005, 0.002)]
    cls.train_packed(models, TRAIN)
    assert isinstance(models[0]._loop, TrainLoop)
    assert all(isinstance(m._loop, PackedSliceLoop) for m in models[1:])
    want = [dump_pytree(m._loop.params) for m in models]
    before = _counters()
    cls.stage_packed_dump(models)
    got = [pickle.loads(m.dump_parameters())["packed"] for m in models]
    assert got == want
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (2, 1)


def test_a_dispatch_the_device_refuses_costs_the_shortcut_not_the_trials(
        tmp_path, monkeypatch):
    from rafiki_tpu.ops.train import PackedTrainLoop

    def refused(self, cast):
        raise MemoryError("RESOURCE_EXHAUSTED")

    monkeypatch.setattr(PackedTrainLoop, "stage_host_params", refused)
    store, _params, worker, _adv, sub = _mk_worker(tmp_path, PACK)
    before = _counters()
    errors0 = telemetry.get_counter("persist.dispatch_errors")
    assert worker.run() == PACK
    assert [t["status"] for t in _rows(store, sub)] == ["COMPLETED"] * PACK
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (0, PACK)
    assert telemetry.get_counter("persist.dispatch_errors") == errors0 + 1


# -- (d) bounded by rounds --------------------------------------------------------


def _wait_for(cond, timeout=60.0):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(0.02)
    return False


def _blocking_store(tmp_path):
    """A ParamsStore whose ``save_parts`` says when it is entered and then waits
    for the gate."""
    from rafiki_tpu.store import ParamsStore

    gate, entered = threading.Event(), threading.Event()

    class Blocking(ParamsStore):
        def save_parts(self, parts, params_id=None):
            entered.set()
            assert gate.wait(120)
            return super().save_parts(parts, params_id)

    return Blocking(tmp_path / "params"), gate, entered


def _live_copies():
    gc.collect()
    return [o for o in gc.get_objects() if isinstance(o, StackedHostCopy)]


def test_a_store_that_blocks_stalls_the_worker_at_the_second_rounds_first_submit(
        tmp_path):
    blocking, gate, entered = _blocking_store(tmp_path)
    store, _params, worker, adv, sub = _mk_worker(
        tmp_path, 3 * PACK, params_store=blocking)
    copies0 = len(_live_copies())
    done = []
    runner = threading.Thread(target=lambda: done.append(worker.run()))
    runner.start()
    try:
        assert entered.wait(120)
        # Round 1's other three submits did not block: the worker went on
        # to train and evaluate round 2 and fed its first score back ...
        assert _wait_for(lambda: len(adv.fed) == PACK + 1)
        # ... and there it stands, behind round 1's unwritten members.
        time.sleep(0.5)
        assert len(adv.fed) == PACK + 1 and runner.is_alive() and not done
        rows = _rows(store, sub)
        assert len(rows) == 2 * PACK
        assert {t["status"] for t in rows} == {"RUNNING"}
        assert len(_live_copies()) - copies0 == 2
    finally:
        gate.set()
        runner.join(120)
    assert not runner.is_alive() and done == [3 * PACK]
    rows = _rows(store, sub)
    assert [t["status"] for t in rows] == ["COMPLETED"] * (3 * PACK)
    # feedback before the hand to the saver, per trial, in creation order
    assert [score for score, _knobs in adv.fed] == \
        [pytest.approx(t["score"], abs=1e-6) for t in rows]
    assert len(_live_copies()) == copies0      # an idle saver keeps nothing


def test_a_serial_save_still_waits_behind_one_pending_save(tmp_path):
    blocking, gate, entered = _blocking_store(tmp_path)
    store, _params, worker, adv, sub = _mk_worker(
        tmp_path, 4, trial_pack=1, params_store=blocking)
    done = []
    runner = threading.Thread(target=lambda: done.append(worker.run()))
    runner.start()
    try:
        assert entered.wait(120)
        # one being written, one queued, the third blocked at its submit
        assert _wait_for(lambda: len(adv.fed) == 3)
        time.sleep(0.5)
        assert len(adv.fed) == 3 and runner.is_alive()
    finally:
        gate.set()
        runner.join(120)
    assert done == [4]
    assert [t["status"] for t in _rows(store, sub)] == ["COMPLETED"] * 4


# -- (e) failures -------------------------------------------------------------------


@pytest.mark.parametrize("what", ["copy", "write"])
def test_a_failed_copy_or_write_errors_the_trials_it_touched_and_no_others(
        tmp_path, monkeypatch, what):
    from rafiki_tpu.store import ParamsStore

    first_copy = []
    real_fetch = StackedHostCopy.fetch

    def fetch(self):
        if not first_copy:
            first_copy.append(self)
        if what == "copy" and self is first_copy[0]:
            raise RuntimeError("device lost the copy")
        return real_fetch(self)

    monkeypatch.setattr(StackedHostCopy, "fetch", fetch)
    saves = []

    class Failing(ParamsStore):
        def save_parts(self, parts, params_id=None):
            saves.append(params_id)
            if what == "write" and len(saves) == 3:
                raise OSError("disk full")
            return super().save_parts(parts, params_id)

    store, params, worker, _adv, sub = _mk_worker(
        tmp_path, 2 * PACK, params_store=Failing(tmp_path / "params"))
    assert worker.run() == 2 * PACK
    rows = _rows(store, sub)
    bad = list(range(PACK)) if what == "copy" else [2]
    for i, t in enumerate(rows):
        if i in bad:
            assert t["status"] == "ERRORED", (i, t["status"])
            assert t["error"].startswith("params persist failed")
            assert not t.get("params_id")
        else:
            assert t["status"] == "COMPLETED", (i, t.get("error"))
            assert params.exists(t["params_id"])
    # the saver's thread lived on: round 2 was written by it
    assert all(t["status"] == "COMPLETED" for t in rows[PACK:])


# -- (f) flush ----------------------------------------------------------------------


def test_flush_returns_when_every_row_of_the_round_is_durable(tmp_path):
    from rafiki_tpu.store import ParamsStore
    from rafiki_tpu.worker.train import PackedTrialRunner

    class Slow(ParamsStore):
        def save_parts(self, parts, params_id=None):
            time.sleep(0.15)
            return super().save_parts(parts, params_id)

    store, params, worker, _adv, sub = _mk_worker(
        tmp_path, PACK, params_store=Slow(tmp_path / "params"))
    try:
        ran, _drained = PackedTrialRunner(worker, PACK).run_round(PACK)
        assert ran == PACK
        # handed to the saver, not yet written: a row turns COMPLETED only
        # once its blob is in the store
        assert "RUNNING" in {t["status"] for t in _rows(store, sub)}
        worker._saver.flush()
        rows = _rows(store, sub)
        assert [t["status"] for t in rows] == ["COMPLETED"] * PACK
        cls = _cls()
        for t in rows:
            m = cls(**t["knobs"])
            m.load_parameters(params.load(t["params_id"]))
            assert m.evaluate(VAL) == pytest.approx(t["score"], abs=0.02)
    finally:
        worker._saver.close()
