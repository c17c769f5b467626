"""CPU rehearsals, at a tiny size, of the cell PR 27 adds (as
``test_run_cpu.py`` does for the first): the last line's keys, a sound run
correct, the per-layer readers that can read on a CPU, the timed path broken
underneath coming out not correct, and the language-model control."""

import io
import json

import pytest
from conftest import BENCH, load_cfg
from lm_tiny import tiny_lm

import run

LM = "kimi_linear_sweep_8k"

#: Limits at the tiny size (96-token sequences, 384 scored tokens: one token
#: is 0.0026 of a score), set as the cell's own: above what sound tiny runs
#: read, below what the control and the faults read.
TINY_LM_LIMITS = {"first_step_flips": 0.03, "first_loss_gap": 0.003,
                  "score_gap": 0.011, "unmoved_share": 0.5}


def rehearse(cell, seed=7, trace=0, seconds="3", **overrides):
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    out = io.StringIO()
    defaults = dict(cfg=tiny_lm, out=out,
                    traffic=lambda t: dict(t, trace={"start_s": 0.2, "seconds": 1.0}),
                    limits=TINY_LM_LIMITS)
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", seconds,
                   "--trace", str(trace)], platform="cpu",
                  overrides=dict(defaults, **overrides))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def manifest():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_a_sound_run_is_correct_and_prints_the_contracts_keys():
    rc, line = rehearse(LM, seed=2**31 + 27)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {m["name"] for m in manifest()["end_to_end"]}
    for value, limit in line["compared"].values():
        assert value <= limit
    assert len(line["compared"]) == 4


def test_the_language_model_cells_readers_read_what_a_cpu_run_has(monkeypatch):
    import jax
    from drivers import lm_sweep

    # The compiled text is read off the executable the window ran: taking
    # it compiles nothing (at the published size a second compile of the
    # epoch program takes minutes and gigabytes of host memory).
    compiles, took = [], {}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    text_of = lm_sweep.epoch_program_text

    def counted(ctx):
        before = len(compiles)
        text = text_of(ctx)
        took.update(compiles=len(compiles) - before, text=text)
        return text

    monkeypatch.setattr(lm_sweep, "epoch_program_text", counted)
    rc, line = rehearse(LM, trace=1)
    assert rc == 0       # (not correct: a CPU trace holds no device operation)
    assert took["compiles"] == 0 and took["text"].startswith("HloModule jit_train_step")
    got = set(line["metrics"])
    # no device plane and no peak on the CPU: those stay out of the line.
    # One name a layer across cells: what the serial lane shares with the
    # packed one is read by the readers the packed cell has.
    assert got >= {"epoch_program_ms.lm", "evaluate_share.lm", "persist_share.sweep",
                   "health_snapshot_share.lm", "persist_wait_share.sweep",
                   "advisor_share.sweep", "feedback_share.sweep",
                   "persist_fetch_ms.sweep", "persist_write_ms.sweep",
                   "compiles_in_window.sweep", "held_slot_share.lm"}
    assert not got & {"traced_idle_share.sweep", "lm_mfu.lm", "kda_device_share.lm"}
    listed = {m["name"] for m in manifest()["per_layer"] if LM in m["workloads"]}
    assert got <= listed and len(listed) == 17
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0
    assert 0 < line["metrics"]["held_slot_share.lm"]["value"] < 100


def test_every_reader_of_a_new_metric_returns_nothing_where_nothing_is_to_read():
    # (the parent has none of the spans and counters PR 27 adds)
    empty = {"spans": [], "window_s": 1.0, "done": 0, "compiles": {
        "backend_compiles": 0, "cache_misses": 0}, "program_cache_misses": 0}
    new = [m["name"] for m in manifest()["per_layer"] if m["name"].endswith(".lm")]
    assert len(new) == 9
    for name in new:
        assert run.load_reader(name)(dict(empty)) is None, name


def test_scope_seconds_joins_instruction_names_with_scopes():
    from drivers import lm_sweep

    text = '''
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_epoch)/while/body/rafiki.loss/transpose(jvp(_KimiLinear))/layer_2/kda/dot" id=3}
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_epoch)/while/body/rafiki.optimizer/mul"}
  %copy.3 = f32[8]{0} copy(%p), metadata={op_name="jit(train_epoch)/while/body/rafiki.loss/jvp(_KimiLinear)/layer_2/moe/moe.experts/ragged_dot"}
  %fusion.11 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_epoch)/while/body/rafiki.loss/lm.loss/checkpoint/dot"}
'''
    got = lm_sweep.scope_seconds(text, {"fusion.7": 2.0, "fusion.9": 1.0, "copy.3": 0.5,
                                        "fusion.11": 0.25, "unknown.1": 4.0})
    assert got == {"joined": 3.75, "total": 7.75, "kda": 2.0, "other": 1.0,
                   "moe": 0.5, "loss": 0.25}


def test_only_operations_inside_the_epoch_programs_module_are_joined():
    # instruction names are unique within a module only: an evaluation's
    # ``fusion.7`` in the same span is not the epoch program's
    from drivers import lm_sweep

    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_epoch(123)", 100.0, 50.0],
                                           ["jit_eval_counts(7)", 160.0, 30.0],
                                           ["jit_train_epoch(123)", 200.0, 50.0]]},
        {"name": "XLA Ops", "events": [["%while.1 = ...", 100.0, 50.0], ["%fusion.7 = ...", 110.0, 10.0],
                                       ["%fusion.7 = ...", 165.0, 20.0],
                                       ["%fusion.7 = ...", 205.0, 10.0]]}]}
    text = "HloModule jit_train_epoch, is_scheduled=true\n\nENTRY %main {\n}\n"
    got = lm_sweep.epoch_program_events(plane, text)
    assert [(e[0].split(" ")[0], e[1]) for e in got] == [
        ("%while.1", 100.0), ("%fusion.7", 110.0), ("%fusion.7", 205.0)]
    assert lm_sweep.epoch_program_events(plane, "HloModule jit_other\n") == []


# -- the timed path broken underneath -----------------------------------------

def _state_unchanged(mp):
    from rafiki_tpu.ops.train import TrainLoop
    import jax
    import jax.numpy as jnp

    orig = TrainLoop.run_epoch

    def run_epoch(self, dataset, batch_size, epoch_seed, on_metrics=None):
        before = jax.tree.map(jnp.copy, self.state)
        out = orig(self, dataset, batch_size, epoch_seed, on_metrics)
        self.state = before
        return out

    mp.setattr(TrainLoop, "run_epoch", run_epoch)


def _score_altered(mp):
    from rafiki_tpu.store import MetaStore

    orig = MetaStore.mark_trial_as_completed
    mp.setattr(MetaStore, "mark_trial_as_completed",
               lambda self, tid, score, pid: orig(self, tid, score + 0.05, pid))


def _half_batch(mp):
    # (the template runs an epoch step by step: its batches are the data set's)
    from rafiki_tpu.model.dataset import Dataset

    orig = Dataset.batches

    def batches(self, batch_size, shuffle=False, **kw):
        for b in orig(self, batch_size, shuffle=shuffle, **kw):
            yield {k: v[: batch_size // 2] for k, v in b.items()} if shuffle else b

    mp.setattr(Dataset, "batches", batches)


@pytest.mark.parametrize("fault", [_state_unchanged, _score_altered, _half_batch],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_shared_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    rc, line = rehearse(LM)
    assert rc == 0 and line["correct"] is False, line
    assert any(v > limit for v, limit in line["compared"].values())


#: Faults planted in the template itself. The tenant's model file is the
#: template's bytes, so the fault goes into a copy of them.
TEMPLATE_FAULTS = {
    "recurrence_without_decay": ("S = S * dc + _mm(kt, u,", "S = S + _mm(kt, u,"),
    "experts_of_the_wrong_chip": ('first = int(kn["expert_shard"]) * per',
                                  'first = (int(kn["expert_shard"]) + 1) * per'),
    "attention_that_sees_the_future": ("jnp.where(t_pos >= jnp.arange(kb.shape[1])[None, :], s, -1e30)",
                                       "s"),
}


@pytest.mark.parametrize("fault", sorted(TEMPLATE_FAULTS))
def test_a_broken_template_is_not_correct(fault, tmp_path):
    old, new = TEMPLATE_FAULTS[fault]
    source = (BENCH.parent / load_cfg(
        "kimi_linear_48b_a3b_ep32")["template_file"]).read_text()
    assert source.count(old) == 1
    (tmp_path / "template.py").write_text(source.replace(old, new))
    rc, line = rehearse(LM, cfg=lambda c: dict(
        tiny_lm(c), template_file=str(tmp_path / "template.py")))
    assert rc == 0 and line["correct"] is False, line
    assert any(v > limit for v, limit in line["compared"].values())


def test_the_language_model_control_fails_each_stand_in():
    import lm_control
    from lm_tiny import load_lm_cfg

    row = lm_control.readings(tiny_lm(load_lm_cfg()), 2**31 + 5, TINY_LM_LIMITS)
    for name in ("fp8", "half_batch", "state_unchanged", "score_altered"):
        assert row[name]["correct"] is False, (name, row[name])
    assert row["reference_again"]["correct"] is True, row["reference_again"]
    again = row["reference_again"]["numbers"]
    assert max(v for k, v in again.items() if k != "unmoved_share") == 0.0
    assert 0.0 < again["unmoved_share"] < 0.2      # (a share, not a gap)
