"""Compile amortization: same-signature trials must NOT recompile.

This is the throughput decider (SURVEY.md §7 hard part #2): a worker
runs trials back to back, and every retrace/recompile it pays between
trials comes straight out of trials/hour. The contract under test:

  * two trials whose traced computation is identical — same model
    class, same shape-affecting knobs, ANY lr / warmup / dropout /
    epochs / seed — share one cached ``Program`` AND one compiled XLA
    executable (``jit._cache_size() == 1``);
  * the dynamic-hyperparameter path is numerically equivalent to the
    baked-optimizer path it replaces;
  * trials that do change the architecture get their own program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from rafiki_tpu.models.ff import FeedForward
from rafiki_tpu.models.vgg import Vgg
from rafiki_tpu.ops.train import (
    TrainLoop,
    cross_entropy_loss,
    dropout,
    program_cache_stats,
)

TRAIN = "synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=0"
VAL = "synthetic://images?classes=4&n=64&w=8&h=8&c=1&seed=1"


def _ff_knobs(**over):
    knobs = dict(hidden_layers=1, hidden_units=32, learning_rate=1e-3,
                 batch_size=32, epochs=1, seed=0)
    knobs.update(over)
    return knobs


def _run_trial(model_cls, knobs):
    model = model_cls(**knobs)
    model.train(TRAIN)
    model.evaluate(VAL)
    return model


def test_second_same_sig_trial_reuses_program():
    """The core amortization claim: trial 2 (different lr, epochs,
    seed) is a pure cache hit — same Program object, no new compiled
    executable in the jit cache."""
    m1 = _run_trial(FeedForward, _ff_knobs())
    prog1 = m1._loop.program
    before = program_cache_stats()
    # Trials run epochs through the device-resident scan program.
    n_exec_before = prog1.train_epoch._cache_size()

    m2 = _run_trial(FeedForward, _ff_knobs(learning_rate=3e-2, epochs=2))
    after = program_cache_stats()

    assert m2._loop.program is prog1
    assert after["misses"] == before["misses"], "second trial compiled a new program"
    assert after["hits"] == before["hits"] + 1
    # the jitted epoch served trial 2 from its existing executable
    assert prog1.train_epoch._cache_size() == n_exec_before
    m1.destroy(), m2.destroy()


def test_vgg_dropout_and_lr_are_dynamic():
    """VGG's continuous knobs (dropout, lr) ride in the traced hyper
    dict: sweeping them reuses ONE program (this is what makes a GP
    sweep over the VGG space compile ~once per shape bucket)."""
    base = dict(depth=11, width_mult=0.25, dropout=0.1, learning_rate=1e-3,
                batch_size=64, epochs=1, seed=0)
    tr = "synthetic://images?classes=4&n=128&w=8&h=8&c=3&seed=0"
    va = "synthetic://images?classes=4&n=64&w=8&h=8&c=3&seed=1"

    m1 = Vgg(**base)
    m1.train(tr)
    m1.evaluate(va)
    prog1 = m1._loop.program
    before = program_cache_stats()

    m2 = Vgg(**dict(base, dropout=0.45, learning_rate=2e-2))
    m2.train(tr)
    m2.evaluate(va)

    assert m2._loop.program is prog1
    assert program_cache_stats()["misses"] == before["misses"]
    assert prog1.train_epoch._cache_size() == 1
    m1.destroy(), m2.destroy()


def test_shape_knob_change_builds_new_program():
    m1 = _run_trial(FeedForward, _ff_knobs())
    before = program_cache_stats()
    m2 = _run_trial(FeedForward, _ff_knobs(hidden_units=64))
    after = program_cache_stats()
    assert m2._loop.program is not m1._loop.program
    assert after["misses"] == before["misses"] + 1
    m1.destroy(), m2.destroy()


def test_worker_trials_hit_program_cache(tmp_path):
    """End-to-end through the TrainWorker loop: a 4-trial job on one
    worker compiles at most once per shape signature."""
    from rafiki_tpu.advisor import AdvisorService
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import InProcAdvisorHandle, TrainWorker

    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    src = open("rafiki_tpu/models/ff.py", "rb").read()
    model = store.create_model("ff", "IMAGE_CLASSIFICATION", None, src, "FeedForward")
    job = store.create_train_job("app", "IMAGE_CLASSIFICATION", None, TRAIN, VAL,
                                 {"MODEL_TRIAL_COUNT": 4})
    sub = store.create_sub_train_job(job["id"], model["id"])

    # Advisor fixed to one shape bucket: only lr/epochs vary.
    class OneSigAdvisor:
        def __init__(self):
            self._i = 0

        def propose(self):
            self._i += 1
            return _ff_knobs(learning_rate=10.0 ** -(1 + self._i % 3))

        def feedback(self, score, knobs):
            pass

    from rafiki_tpu.model.base import load_model_class

    cls = load_model_class(src, "FeedForward")
    worker = TrainWorker(store, params, sub["id"], cls, OneSigAdvisor(),
                         TRAIN, VAL, {"MODEL_TRIAL_COUNT": 4},
                         async_persist=False)
    before = program_cache_stats()
    n = worker.run()
    after = program_cache_stats()
    assert n == 4
    # ≤1 new program for 4 trials; ≥3 cache hits
    assert after["misses"] - before["misses"] <= 1
    assert after["hits"] - before["hits"] >= 3


def test_dynamic_lr_matches_baked_adam():
    """scale_by_adam + traced lr scaling ≡ optax.adam(lr): same init,
    same batches → same params."""
    rng = np.random.default_rng(0)
    batch = {
        "x": rng.uniform(-1, 1, size=(16, 8)).astype(np.float32),
        "y": rng.integers(0, 3, size=(16,)).astype(np.int32),
    }

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        return {"w": jax.random.normal(k1, (8, 3)) * 0.1,
                "b": jnp.zeros((3,))}

    def apply_fn(params, b):
        return b["x"] @ params["w"] + params["b"]

    def loss_fn(params, b, rng):
        loss, acc = cross_entropy_loss(apply_fn(params, b), b["y"])
        return loss, {"acc": acc}

    lr = 3e-3
    dyn = TrainLoop(init_fn, apply_fn, loss_fn, seed=0,
                    hyper={"lr": lr, "warmup": 1.0})
    baked = TrainLoop(init_fn, apply_fn, loss_fn, optax.adam(lr), seed=0)
    dev = dyn.plan.put_batch(batch)
    for _ in range(5):
        dyn.state, _ = dyn.program.train_step(dyn.state, dev)
        baked.state, _ = baked.program.train_step(baked.state, dev)
    np.testing.assert_allclose(np.asarray(dyn.params["w"]),
                               np.asarray(baked.params["w"]), rtol=1e-5, atol=1e-6)


def test_traced_dropout_semantics():
    x = jnp.ones((1000,), jnp.float32)
    key = jax.random.PRNGKey(0)
    assert np.allclose(dropout(x, 0.0, key, deterministic=False), x)
    assert np.allclose(dropout(x, 0.7, key, deterministic=True), x)
    out = np.asarray(dropout(x, jnp.float32(0.5), key, deterministic=False))
    kept = out > 0
    assert 0.3 < kept.mean() < 0.7          # ~half survive
    assert np.allclose(out[kept], 2.0)       # inverted scaling
    # traced rate: same compiled fn serves different rates
    f = jax.jit(lambda r: dropout(x, r, key, deterministic=False))
    a, b = f(jnp.float32(0.2)), f(jnp.float32(0.8))
    assert f._cache_size() == 1
    assert (np.asarray(a) > 0).mean() > (np.asarray(b) > 0).mean()
