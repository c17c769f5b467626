"""The cell ISSUE 31 adds, on the CPU at a tiny size (as
``test_lm_cells_cpu.py`` and ``test_lm_references.py`` do for PR 27's): the
reference's pieces with the tied table against ``jax.value_and_grad`` of its
own ``loss``, a sound rehearsal correct, planted faults not correct, what the
new readers read, and the roofline's count."""

import io
import json

import numpy as np
import pytest
from conftest import BENCH
from lfm2_tiny import load_lfm2_cfg, tiny_lfm2

import run

CELL = "lfm2_moe_sweep_8k"

#: Limits at the tiny size (96-token sequences, 384 scored tokens: one token
#: is 0.0026 of a score), set as the cell's own: above what sound tiny runs
#: read (0.0016-0.004 flipped, 5e-6 to 3e-4 of the loss), below what the
#: control and the faults read (float8 0.06, half a batch 0.2).
TINY_LIMITS = {"first_step_flips": 0.006, "first_loss_gap": 0.003,
               "score_gap": 0.011, "unmoved_share": 0.5}


def rehearse(seed=7, trace=0, seconds="3", **overrides):
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    out = io.StringIO()
    defaults = dict(cfg=tiny_lfm2, out=out,
                    traffic=lambda t: dict(t, trace={"start_s": 0.2, "seconds": 1.0}),
                    limits=TINY_LIMITS)
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", seconds,
                   "--trace", str(trace)], platform="cpu",
                  overrides=dict(defaults, **overrides))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def manifest():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_the_reference_in_pieces_with_the_tied_table_is_the_reference_whole():
    """``lfm2_sweep.TiedReference`` runs the reference a layer at a time with the
    table's gradient summed from the head's piece and the embedding's: the
    loss and every gradient leaf are ``jax.value_and_grad`` of the
    reference's own ``loss``, and a kind of layer is one program."""
    import jax
    import jax.numpy as jnp
    from drivers import lfm2_sweep

    cfg = tiny_lfm2(load_lfm2_cfg())
    ref = lfm2_sweep.TiedReference(cfg, 77, 5)
    ref.opts = dict(ref.opts, seq_block=1)      # a batch of two in two blocks, their sums added
    p = jax.tree.map(jnp.asarray, ref.init_params())
    assert "head" not in p
    X, Y = ref.first_set
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(ref.mod.loss)(p, jnp.asarray(X), jnp.asarray(Y),
                                                       cfg, 0.05)
    loss2, grads2 = ref.loss_and_grads(p, X, Y, 0.05)
    assert {k[0] for k in ref.pieces().exe} == {
        "init", "add", "embed.vjp", "head.vjp", "conv.ffn.fwd", "conv.ffn.vjp",
        "conv.moe.fwd", "conv.moe.vjp", "attn.moe.fwd", "attn.moe.vjp"}
    assert abs(float(loss) - loss2) <= 1e-6 * float(loss)
    assert set(grads2) == set(grads)
    for k in grads:
        scale = float(np.max(np.abs(grads[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(grads2[k]), np.asarray(grads[k]),
                                   rtol=2e-4, atol=2e-5 * scale, err_msg=k)
    acc, nll = ref.evaluate(ref.init_params())
    with jax.default_matmul_precision("highest"):
        ce, hits, n = ref.mod.stats(p, *map(jnp.asarray, ref.val_set), cfg)
    assert acc == float(hits) / n and abs(nll - float(ce) / n) <= 1e-6 * nll
    ref.build()                                  # every piece ``compare`` calls builds


def test_a_sound_run_is_correct_and_prints_the_contracts_keys():
    rc, line = rehearse(seed=2**31 + 31)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {m["name"] for m in manifest()["end_to_end"]}
    assert len(line["compared"]) == 4
    for value, limit in line["compared"].values():
        assert value <= limit


def test_the_cells_readers_read_what_a_cpu_run_has():
    rc, line = rehearse(trace=1)
    assert rc == 0       # (not correct: a CPU trace holds no device operation)
    got = set(line["metrics"])
    assert got >= {"epoch_program_ms.lm", "evaluate_share.lm", "persist_share.sweep",
                   "health_snapshot_share.lm", "persist_wait_share.sweep",
                   "advisor_share.sweep", "feedback_share.sweep",
                   "persist_fetch_ms.sweep", "persist_write_ms.sweep",
                   "compiles_in_window.sweep", "held_slot_share.lm"}
    # no device plane and no peak on the CPU: those stay out of the line
    assert not got & {"traced_idle_share.sweep", "lm_mfu.lm", "conv_device_share.lm",
                      "attn_device_share.lm", "gqa_attention_roofline.lm"}
    listed = {m["name"] for m in manifest()["per_layer"] if CELL in m["workloads"]}
    assert got <= listed and len(listed) == 18
    assert not listed & {"kda_device_share.lm", "mla_device_share.lm"}
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0
    # 4 of 16 experts held: a quarter of the slots under uniform routing
    assert 10 < line["metrics"]["held_slot_share.lm"]["value"] < 50


def test_the_new_readers_return_nothing_where_nothing_is_to_read():
    # (the parent has none of what ISSUE 31 adds to the program)
    empty = {"spans": [], "window_s": 1.0, "done": 0, "compiles": {
        "backend_compiles": 0, "cache_misses": 0}, "program_cache_misses": 0}
    for name in ("conv_device_share.lm", "attn_device_share.lm", "gqa_attention_roofline.lm"):
        assert run.load_reader(name)(dict(empty)) is None, name
        assert run.load_reader(name)(dict(empty, peak={"bf16_flops": 197e12},
                                          attention_kernels=None)) is None, name
    assert [m["name"] for m in manifest()["per_layer"]][-3:] == [
        "conv_device_share.lm", "attn_device_share.lm", "gqa_attention_roofline.lm"]


def test_the_join_keeps_an_instruction_printed_over_several_lines():
    from drivers import lfm2_sweep

    text = '''
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.loss/transpose(jvp(_Lfm2Moe))/layer_2/conv/lfm2.conv/dot" id=3}
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.optimizer/mul"}
  %splash_mha_fwd_residuals.3 = (bf16[32,8192,64]{2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call", backend_config={"kernel_metadata": "a
b"},
    metadata={op_name="jit(train_step)/rafiki.loss/jvp(_Lfm2Moe)/layer_3/attn/lfm2.attn/cond/branch_0_fun/splash_mha_fwd_residuals/pallas_call"}
  %copy.3 = f32[8]{0} copy(%p), metadata={op_name="jit(train_step)/rafiki.loss/jvp(_Lfm2Moe)/layer_4/moe/moe.experts/ragged_dot"}
  %fusion.11 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/lm.loss/checkpoint/dot"}
  %bitcast.1 = f32[8]{0} bitcast(%p)
'''
    scopes = lfm2_sweep.instruction_scopes(text)
    assert "lfm2.attn" in scopes["splash_mha_fwd_residuals.3"] and "bitcast.1" not in scopes
    got = lfm2_sweep.scope_seconds(text, {
        "fusion.7": 2.0, "fusion.9": 1.0, "splash_mha_fwd_residuals.3": 0.75, "copy.3": 0.5,
        "fusion.11": 0.25, "unknown.1": 4.0})
    assert got == {"joined": 4.5, "total": 8.5, "conv": 2.0, "other": 1.0, "attn": 0.75,
                   "moe": 0.5, "loss": 0.25}


def test_the_rooflines_count_is_the_causal_half_and_no_recomputation():
    """``gqa_attention_roofline.lm``: a step's four kernel calls (the forward
    kernel twice, each backward kernel once) need two products forward and
    five backward over the pairs at or under the diagonal; a kernel that ran
    at the chip's peak on exactly that reads 100%."""
    from drivers import lfm2_sweep
    from references import lfm2_moe as R

    cfg = load_lfm2_cfg()
    flops = R.attention_kernel_flops(cfg, batch=2)
    product = 2 * 2 * 32 * (8192 * 8193 // 2) * 64
    assert flops == {"forward": 2 * product, "backward": 5 * product}
    events = [("%splash_mha_fwd_residuals.1 = ...", 0.0, 4e6),
              ("%splash_mha_fwd_residuals.2 = ...", 5e6, 4e6),
              ("%splash_mha_dq_no_residuals.1 = ...", 10e6, 6e6),
              ("%splash_mha_dkv_no_residuals.1 = ...", 17e6, 6e6),
              ("%fusion.3 = ...", 30e6, 9e6)]
    calls = lfm2_sweep.attention_kernel_calls(events)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "splash_mha_fwd": 2, "splash_mha_dq": 1, "splash_mha_dkv": 1}
    assert abs(sum(v["seconds"] for v in calls.values()) - 0.020) < 1e-12
    needed = sum(calls[p]["calls"] * share * flops[which]
                 for p, which, share in lfm2_sweep.ATTENTION_KERNELS)
    assert needed == 7 * product
    read = run.load_reader("gqa_attention_roofline.lm")
    peak = {"bf16_flops": 197e12}
    at_peak = {"attention_kernels": {"seconds": needed / 197e12, "needed_flops": needed},
               "peak": peak}
    assert abs(read(at_peak) - 100.0) < 1e-9
    assert 0 < read({"attention_kernels": {"seconds": 0.020, "needed_flops": needed},
                     "peak": peak}) < 100.0


# -- the timed path broken underneath, and the control ---------------------------

#: Faults planted in the template itself (a copy of its bytes, as a tenant's
#: model file is): a head that is not the table (its gradient never reaches
#: the table), experts of another rank, an attention without positions.
TEMPLATE_FAULTS = {
    "a_head_that_is_not_the_table": ("return h, embed.T, jnp.stack(loads), fused",
                                     "return h, jax.lax.stop_gradient(embed).T, "
                                     "jnp.stack(loads), fused"),
    "experts_of_the_wrong_rank": ('first = int(kn["expert_shard"]) * per',
                                  'first = (int(kn["expert_shard"]) + 1) * per'),
    "attention_without_positions": ("return x * cos + turned * sin", "return x"),
}


@pytest.mark.parametrize("fault", sorted(TEMPLATE_FAULTS))
def test_a_broken_template_is_not_correct(fault, tmp_path):
    old, new = TEMPLATE_FAULTS[fault]
    source = (BENCH.parent / load_lfm2_cfg()["template_file"]).read_text()
    assert source.count(old) == 1
    (tmp_path / "template.py").write_text(source.replace(old, new))
    rc, line = rehearse(cfg=lambda c: dict(
        tiny_lfm2(c), template_file=str(tmp_path / "template.py")))
    assert rc == 0 and line["correct"] is False, line
    assert any(v > limit for v, limit in line["compared"].values())


def test_the_control_fails_each_stand_in_whole_and_by_the_first_step_alone():
    """float8 products, half a batch, a state left unchanged and an altered
    score each come out not correct; the reference against itself correct."""
    import lfm2_control

    cfg = tiny_lfm2(load_lfm2_cfg())
    row = lfm2_control.readings(cfg, 2**31 + 5, TINY_LIMITS)
    for name in ("fp8", "half_batch", "state_unchanged", "score_altered"):
        assert row[name]["correct"] is False, (name, row[name])
    assert row["reference_again"]["correct"] is True, row["reference_again"]
    again = row["reference_again"]["numbers"]
    assert max(v for k, v in again.items() if k != "unmoved_share") == 0.0
    first = lfm2_control.readings(cfg, 2**31 + 5, TINY_LIMITS,
                                  stand_ins=("fp8", "half_batch", "reference_again"),
                                  first_step_only=True)
    for name in ("fp8", "half_batch"):
        assert first[name]["correct"] is False
        assert first[name]["numbers"]["first_step_flips"] == pytest.approx(
            row[name]["numbers"]["first_step_flips"], rel=1e-6)
    assert first["reference_again"]["numbers"] == {"first_step_flips": 0.0, "first_loss_gap": 0.0}
