"""A CPU rehearsal of ``run.py`` at a tiny size: the last line's keys, a run
without a chip, and ``correct`` coming out false with the timed path broken
underneath (each fault such a cell can have), and for the control."""

import io
import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, load_cfg, tiny

import run

CELLS = ["vgg16_sweep_packed"]

#: Limits for the tiny size (8x8 images, 16 steps, 144 scored rows: one row
#: is 0.007 of a score), set as the cell's own are: above what a dozen sound
#: tiny runs read, below what the control and the faults read.
TINY_LIMITS = {"first_step_flips": 0.10, "first_loss_gap": 0.005, "score_gap": 0.03,
               "change_gap": 0.4, "val_loss_gap": 0.1}


def tiny_traffic(t):
    # the tiny window is seconds long: trace from its start
    return dict(t, trace={"start_s": 0.2, "seconds": 1.0})


def rehearse(cell, seed=7, trace=0, **overrides):
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    out = io.StringIO()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "3",
                   "--trace", str(trace)], platform="cpu",
                  overrides=dict(overrides, cfg=tiny, out=out,
                                 traffic=tiny_traffic, limits=TINY_LIMITS))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_contracts_keys_and_a_sound_run_is_correct(cell):
    rc, line = rehearse(cell, seed=2**31 + 11)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 8
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for value, limit in line["compared"].values():
        assert value <= limit


def test_traced_run_reports_the_per_layer_metrics_it_can_read():
    # No device plane on the CPU: the trace-borne metric stays out of the
    # line (a reader that finds nothing returns nothing), the rest are there.
    rc, line = rehearse("vgg16_sweep_packed", trace=1)
    assert rc == 0
    assert "traced_idle_share.sweep" not in line["metrics"]
    assert {"advisor_share.sweep", "persist_share.sweep", "train_step_ms.sweep",
            "compiles_in_window.sweep"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == run.EXIT_NO_ACCELERATOR
    assert p.stdout == ""


def test_in_a_directory_with_only_the_benchmark_it_fails(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


# -- the timed path broken underneath -----------------------------------------

def _state_unchanged(mp):
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.ops.train import PackedTrainLoop

    orig = PackedTrainLoop.run_epoch

    def run_epoch(self, dataset, batch_size, epoch_seeds):
        before = jax.tree.map(jnp.copy, self.state)
        rows = orig(self, dataset, batch_size, epoch_seeds)
        self.state = before
        return rows

    mp.setattr(PackedTrainLoop, "run_epoch", run_epoch)


def _half_batch(mp):
    from rafiki_tpu.model.base import JaxModel
    from rafiki_tpu.ops.train import cross_entropy_loss

    def loss(self, params, batch, rng, apply_fn):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        logits = apply_fn(params, half, train=True, rng=rng)
        value, acc = cross_entropy_loss(logits, half["y"])
        return value, {"acc": acc}

    mp.setattr(JaxModel, "loss", loss)


def _score_altered(mp):
    from rafiki_tpu.store import MetaStore

    orig = MetaStore.mark_trial_as_completed

    def mark(self, trial_id, score, params_id):
        return orig(self, trial_id, score + 0.05, params_id)

    mp.setattr(MetaStore, "mark_trial_as_completed", mark)


def _stored_parameters_altered(mp):
    import jax

    from rafiki_tpu.utils import serial

    orig = serial.dump_pytree

    def dump_pytree(tree, cast_f32_to_bf16=True):
        return orig(jax.tree.map(lambda a: a * 1.5, tree), cast_f32_to_bf16)

    mp.setattr(serial, "dump_pytree", dump_pytree)


def _packing_off_in_silence(mp):
    from rafiki_tpu.worker.train import PackedTrialRunner

    mp.setattr(PackedTrialRunner, "eligible", lambda self: False)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "score_altered": _score_altered,
          "stored_parameters_altered": _stored_parameters_altered,
          "packing_off_in_silence": _packing_off_in_silence}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    if fault == "packing_off_in_silence":
        # Already the warm-up round shows it: the run fails, with no result.
        with pytest.raises(RuntimeError, match="packed"):
            rehearse("vgg16_sweep_packed")
        return
    rc, line = rehearse("vgg16_sweep_packed")
    assert rc == 0
    assert line["correct"] is False, (fault, line["compared"])


def test_the_control_and_the_planted_faults_are_not_correct():
    """The reference in float8 put in the program's place (the control),
    and with each fault planted, judged by ``check.compare`` as a run's
    round is, at a size a test run can hold (on the chip at the cell's own
    size: PERF.md). The reference unaltered comes out correct."""
    import control

    row = control.readings(tiny(load_cfg("vgg16_cifar")), 5, TINY_LIMITS)
    assert row["reference_again"]["correct"] is True, row["reference_again"]
    for name in ("fp8", "half_batch", "state_unchanged", "score_altered"):
        assert row[name]["correct"] is False, (name, row[name])
    over = {name: {k for k, v in row[name]["numbers"].items() if v > TINY_LIMITS[k]}
            for name in control.STAND_INS}
    assert "first_step_flips" in over["fp8"] and "first_step_flips" in over["half_batch"]
    assert {"first_step_flips", "change_gap"} <= over["state_unchanged"]
    assert over["score_altered"] == {"score_gap"}


def test_the_programs_readings_on_several_seeds_in_one_process():
    """``control.py --program-seeds``: run.py's whole path per seed, the
    warm-up round only before the first."""
    import control
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    rows = control.program_readings(
        "vgg16_sweep_packed", [21, 22], lambda s: None, platform="cpu",
        overrides=dict(cfg=tiny, traffic=tiny_traffic, limits=TINY_LIMITS))
    assert [r["rc"] for r in rows] == [0, 0]
    assert all(set(r["compared"]) == set(TINY_LIMITS) for r in rows)
