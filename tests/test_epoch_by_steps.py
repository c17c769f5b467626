"""The serial lane's epoch run step by step (``JaxModel.epoch_program``
false; ``TrainLoop(epoch_program=False)``): the same training as the epoch
program, ONE compiled step whatever the train set's length, every step's
scalars fetched once and reduced on the host, device-side counts summed
over the steps, the ``train.epoch`` span, and a program's first call
compiled once (the profiler's cost capture comes after the call)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rafiki_tpu import telemetry
from rafiki_tpu.model.dataset import dataset_utils
from rafiki_tpu.ops.train import TrainLoop, cross_entropy_loss

TRAIN = "synthetic://images?classes=4&n=300&w=8&h=8&c=1&seed=0"
SHORT = "synthetic://images?classes=4&n=70&w=8&h=8&c=1&seed=2"


def _loop(seed=0, **kw):
    def init_fn(key):
        k1, _ = jax.random.split(key)
        return {"w": jax.random.normal(k1, (64, 4)) * 0.05, "b": jnp.zeros((4,))}

    def apply_fn(params, b):
        x = b["x"].reshape((b["x"].shape[0], -1))
        return x @ params["w"] + params["b"]

    def loss_fn(params, b, rng, hyper):
        loss, acc = cross_entropy_loss(apply_fn(params, b), b["y"])
        return loss, {"acc": acc, "count.rows_seen": jnp.float32(b["y"].shape[0]),
                      "gauge.last_acc": acc}

    return TrainLoop(init_fn, apply_fn, loss_fn, seed=seed,
                     hyper={"lr": 5e-2, "warmup": 1.0}, **kw)


@pytest.fixture
def compiles():
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: seen.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return seen


def test_step_by_step_trains_as_the_epoch_program_does():
    tr = dataset_utils.load(TRAIN)
    scan, steps = _loop(seed=3), _loop(seed=3, epoch_program=False)
    a = scan.run_epoch(tr, batch_size=64, epoch_seed=0)
    b = steps.run_epoch(tr, batch_size=64, epoch_seed=0)
    assert set(a) == set(b)           # sentinels and counts stripped alike
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(scan.params["w"]), np.asarray(steps.params["w"]),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("epoch_program", [True, False], ids=["scan", "steps"])
def test_counts_are_summed_over_the_steps_and_the_epoch_is_a_leaf_span(epoch_program):
    tr = dataset_utils.load(TRAIN)
    loop = _loop(epoch_program=epoch_program)
    before = telemetry.snapshot()["counters"].get("rows_seen", 0.0)
    n0 = len([r for r in telemetry.span_records() if r["name"] == "train.epoch"])
    out = loop.run_epoch(tr, batch_size=64, epoch_seed=0)
    assert "count.rows_seen" not in out and "gauge.last_acc" not in out
    assert telemetry.snapshot()["counters"]["rows_seen"] - before == 4 * 64   # 300 // 64 steps
    assert telemetry.snapshot()["gauges"]["last_acc"] == pytest.approx(out["acc"])
    spans = [r for r in telemetry.span_records() if r["name"] == "train.epoch"][n0:]
    assert len(spans) == 1 and spans[0]["tags"]["steps"] == 4 and spans[0]["tags"]["cold"]


def test_one_step_program_serves_train_sets_of_any_length(compiles):
    loop = _loop(epoch_program=False, program_key="by-steps")
    loop.run_epoch(dataset_utils.load(TRAIN), batch_size=64, epoch_seed=0)
    n = len(compiles)
    # another trial of the same program on a train set of ONE batch (what the
    # language-model cell's first-step trial is): nothing is compiled
    again = _loop(epoch_program=False, seed=1, program_key="by-steps")
    out = again.run_epoch(dataset_utils.load(SHORT), batch_size=64, epoch_seed=0)
    assert again.program is loop.program
    assert np.isfinite(out["loss"]) and len(compiles) == n
    # (the epoch program is compiled anew for every length)
    scan = _loop(program_key="by-scan")
    scan.run_epoch(dataset_utils.load(TRAIN), batch_size=64, epoch_seed=0)
    n = len(compiles)
    _loop(seed=1, program_key="by-scan").run_epoch(
        dataset_utils.load(SHORT), batch_size=64, epoch_seed=0)
    assert len(compiles) > n


def test_the_step_is_built_once_and_the_cost_capture_reads_that_executable(
        compiles, monkeypatch):
    """A loop that runs step by step by choice compiles its step ahead of
    time, once a Program, and the profiler's cost capture reads that
    executable: no second compile (an executable that the persistent cache
    cannot hold was built twice: minutes)."""
    from rafiki_tpu.obs.perf import profiler

    tr = dataset_utils.load(TRAIN)
    counts = {}
    for capture in ("0", "1"):
        jax.clear_caches()                  # (every helper program is built anew, both times)
        del compiles[:]
        monkeypatch.setenv(profiler.ENV_COST_CAPTURE, capture)
        loop = _loop(epoch_program=False, seed=5)
        loop.run_epoch(tr, batch_size=64, epoch_seed=0)
        counts[capture] = len(compiles)
    assert counts["1"] == counts["0"] > 0
    cost = profiler.capture_cost(loop._perf_key, None)     # (idempotent: what was captured)
    assert cost and cost["flops"] > 0
    exe, = loop.program.compiled_steps.values()
    assert exe.as_text().startswith("HloModule jit_train_step")
    n = len(compiles)
    loop.run_epoch(tr, batch_size=64, epoch_seed=1)
    assert len(compiles) == n and len(loop.program.compiled_steps) == 1
