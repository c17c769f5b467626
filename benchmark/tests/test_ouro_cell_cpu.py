"""The cell ISSUE 33 adds, on the CPU at a tiny size (as
``test_lfm2_cell_cpu.py`` does for PR 31's): the looped reference's pieces
against ``jax.value_and_grad`` of its own ``loss``, a sound rehearsal correct,
planted faults not correct, what the new readers read, and the counts by
hand."""

import io
import json

import numpy as np
import pytest
from conftest import BENCH
from ouro_tiny import load_ouro_cfg, tiny_ouro

import run

CELL = "ouro_loop_sweep_8k"

#: Limits at the tiny size (96-token sequences, 384 scored tokens: one token
#: is 0.0026 of a score), set as the cell's own: above what sound tiny runs
#: read (0 to 0.0004 flipped, 1e-5 to 3e-4 of the loss), below what the
#: control and the faults read (float8 0.04, half a batch 0.2; the loop's
#: faults by the loss: 1.2e-3 and over).
TINY_LIMITS = {"first_step_flips": 0.003, "first_loss_gap": 0.0006,
               "score_gap": 0.011, "unmoved_share": 0.5}


def rehearse(seed=7, trace=0, seconds="3", **overrides):
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    out = io.StringIO()
    defaults = dict(cfg=tiny_ouro, out=out,
                    traffic=lambda t: dict(t, trace={"start_s": 0.2, "seconds": 1.0}),
                    limits=TINY_LIMITS)
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", seconds,
                   "--trace", str(trace)], platform="cpu",
                  overrides=dict(defaults, **overrides))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def manifest():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_the_looped_reference_in_pieces_is_the_reference_whole():
    """``ouro_sweep.LoopedReference`` runs the reference a layer visit at a
    time, the loop's norm, the heads (one a pass, weighted by the exit
    distribution) and the gate as pieces of their own, a layer's gradient
    summed over its four visits: the objective and every gradient leaf are
    ``jax.value_and_grad`` of the reference's own ``loss``, and the one kind of
    layer is one program however often it is visited."""
    import jax
    import jax.numpy as jnp
    from drivers import ouro_sweep

    cfg = tiny_ouro(load_ouro_cfg())
    ref = ouro_sweep.LoopedReference(cfg, 77, 5)
    ref.opts = dict(ref.opts, seq_block=1)      # a batch of two in two blocks, their sums added
    p = jax.tree.map(jnp.asarray, ref.init_params())
    X, Y = ref.first_set
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(ref.mod.loss)(p, jnp.asarray(X), jnp.asarray(Y),
                                                       cfg, 0.05)
    loss2, grads2 = ref.loss_and_grads(p, X, Y, 0.05)
    assert {k[0] for k in ref.pieces().exe} == {
        "init", "add", "embed.vjp", "head.vjp", "norm.fwd", "norm.vjp", "exit.fwd",
        "exit.vjp", "attn.ffn.fwd", "attn.ffn.vjp"}
    assert sum(k[0] == "attn.ffn.fwd" for k in ref.pieces().exe) == 1
    assert abs(float(loss) - loss2) <= 1e-6 * float(loss)
    assert set(grads2) == set(grads)
    for k in grads:
        scale = float(np.max(np.abs(grads[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(grads2[k]), np.asarray(grads[k]),
                                   rtol=2e-4, atol=2e-5 * scale, err_msg=k)
    acc, nll = ref.evaluate(ref.init_params())
    with jax.default_matmul_precision("highest"):
        hs = ref.mod.hidden_states(p, jnp.asarray(ref.val_set[0]), cfg)
        ce, hits = ref.mod.head_stats(p, hs[-1], jnp.asarray(ref.val_set[1]))
    n = ref.val_set[1].size
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
                   "compiles_in_window.sweep", "layer_call_ms.lm"}
    # no device plane and no peak on the CPU: those stay out of the line
    assert not got & {"traced_idle_share.sweep", "lm_mfu.lm", "ffn_device_share.lm",
                      "attn_device_share.lm", "loss_device_share.lm",
                      "gqa_attention_roofline.lm"}
    listed = {m["name"] for m in manifest()["per_layer"] if CELL in m["workloads"]}
    assert got <= listed and len(listed) == 17
    assert not listed & {"kda_device_share.lm", "mla_device_share.lm", "moe_device_share.lm",
                         "conv_device_share.lm", "held_slot_share.lm"}
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0
    # four steps a trial of four passes over the two held layers: a visit is an
    # eighth of a step
    step, visit = (line["metrics"][k]["value"] for k in ("epoch_program_ms.lm",
                                                         "layer_call_ms.lm"))
    assert abs(visit - step / 8) < 1e-6 * step


def test_the_new_readers_return_nothing_where_nothing_is_to_read():
    # (the parent has none of what ISSUE 33 adds to the program)
    empty = {"spans": [], "window_s": 1.0, "done": 0, "compiles": {
        "backend_compiles": 0, "cache_misses": 0}, "program_cache_misses": 0}
    epoch = {"name": "train.epoch", "dur_s": 2.0, "tags": {"steps": 4}}
    for name in ("ffn_device_share.lm", "layer_call_ms.lm"):
        assert run.load_reader(name)(dict(empty)) is None, name
        assert run.load_reader(name)(dict(empty, spans=[epoch], counters={
            "moe.slots_total": 0.0})) is None, name
    assert run.load_reader("layer_call_ms.lm")(dict(
        empty, spans=[epoch], counters={"loop.layer_calls": 96.0})) == pytest.approx(2000 / 96)
    assert run.load_reader("ffn_device_share.lm")(dict(
        empty, scope_seconds={"joined": 4.0, "total": 5.0, "ffn": 1.0})) == 25.0
    assert [m["name"] for m in manifest()["per_layer"]][-2:] == [
        "ffn_device_share.lm", "layer_call_ms.lm"]


def test_the_join_groups_this_templates_scopes():
    from drivers import ouro_sweep

    text = '''
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.loss/transpose(jvp(_Ouro))/while/body/closed_call/_Ouro.one_pass/layer_2/lm.ffn/ffn/dot" id=3}
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.optimizer/mul"}
  %splash_mha_fwd_residuals.3 = (bf16[16,8192,128]{2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call", backend_config={"kernel_metadata": "a
b"},
    metadata={op_name="jit(train_step)/rafiki.loss/jvp(_Ouro)/while/body/closed_call/_Ouro.one_pass/layer_3/ouro.attn/attn/cond/branch_0_fun/splash_mha_fwd_residuals/pallas_call"}
  %fusion.10 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/jvp(_Ouro)/while/body/closed_call/_Ouro.one_pass/checkpoint/ouro.gate/dot_general"}
  %fusion.11 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/lm.loss/while/body/checkpoint/dot"}
  %bitcast.1 = f32[8]{0} bitcast(%p)
'''
    got = ouro_sweep.scope_seconds(text, {
        "fusion.7": 2.0, "fusion.9": 1.0, "splash_mha_fwd_residuals.3": 0.75,
        "fusion.10": 0.5, "fusion.11": 0.25, "unknown.1": 4.0})
    assert got == {"joined": 4.5, "total": 8.5, "ffn": 2.0, "other": 1.0, "attn": 0.75,
                   "gate": 0.5, "loss": 0.25}


def test_the_counts_by_hand():
    """``forward_flops``: R visits of each held layer and R heads;
    ``attention_kernel_flops``: one visit's causal half, which the driver
    multiplies by the kernels' calls: a step's 48 forward calls (24 visits and
    their recomputation) and 24 of each backward kernel need 24 x (2 + 5)
    products, and a kernel at the chip's peak on exactly that reads 100%."""
    from drivers import lfm2_sweep
    from references import ouro as R

    cfg = load_ouro_cfg()
    D, F, V, T = 2048, 5632, 49152, 8192
    visit = 2 * (4 * D * D + 3 * D * F) + 2 * (T + 1) / 2 * 16 * 2 * 128
    assert R.forward_flops(cfg) == pytest.approx(24 * visit + 4 * 2 * (D * V + D), rel=1e-9)
    assert 3 * 2 * T * R.forward_flops(cfg) == pytest.approx(200.6e12, rel=2e-3)
    tiny = tiny_ouro(cfg, layers=3, passes=2)
    assert R.forward_flops(tiny) == pytest.approx(
        6 * (2 * (4 * 64 * 64 + 3 * 64 * 128) + 2 * 97 / 2 * 4 * 2 * 16)
        + 2 * 2 * (64 * 256 + 64), rel=1e-9)
    flops = R.attention_kernel_flops(cfg, batch=2)
    product = 2 * 2 * 16 * (T * (T + 1) // 2) * 128
    assert flops == {"forward": 2 * product, "backward": 5 * product}
    events = ([(f"%splash_mha_fwd_residuals.{i} = ...", 0.0, 4e6) for i in range(48)]
              + [(f"%splash_mha_dq_no_residuals.{i} = ...", 0.0, 6e6) for i in range(24)]
              + [(f"%splash_mha_dkv_no_residuals.{i} = ...", 0.0, 6e6) for i in range(24)])
    calls = lfm2_sweep.attention_kernel_calls(events)
    needed = sum(calls[p]["calls"] * share * flops[which]
                 for p, which, share in lfm2_sweep.ATTENTION_KERNELS)
    assert needed == 24 * 7 * product
    read = run.load_reader("gqa_attention_roofline.lm")
    peak = {"bf16_flops": 197e12}
    assert read({"attention_kernels": {"seconds": needed / 197e12, "needed_flops": needed},
                 "peak": peak}) == pytest.approx(100.0)


# -- the timed path broken underneath, and the control ---------------------------

#: Faults planted in the template itself (a copy of its bytes, as a tenant's
#: model file is), each a list of (old, new).
TEMPLATE_FAULTS = {
    "three_passes_for_four": [('length=c["total_ut_steps"])', 'length=c["total_ut_steps"] - 1)')],
    # (its closing norms' scales half as large again on the second visit)
    "a_second_visit_with_other_weights": [
        ("def one_pass(mdl, h, _):", "def one_pass(mdl, h, t):"),
        ("h, fused = h + added, fused + kernel",
         "h, fused = h + added * jnp.where(t == 1, 1.5, 1.0), fused + kernel"),
        ("astype(F32), None)", 'astype(F32), jnp.arange(c["total_ut_steps"]))')],
    "the_final_norm_left_out_between_passes": [
        ("            h, for_head, gate = jax.checkpoint(",
         "            _normed, for_head, gate = jax.checkpoint(")],
    "uniform_exit_weights": [
        ("p, logp = exit_distribution(gates)",
         "p = jnp.full_like(gates, 1.0 / passes); logp = jnp.log(p)")],
    "the_entropy_term_dropped": [("+ beta * jnp.sum(p * logp, axis=0)",
                                  "+ 0.0 * jnp.sum(p * logp, axis=0)")],
    "the_loss_of_the_last_pass_alone": [
        ("token = jnp.sum(p * ce, axis=0) + beta * jnp.sum(p * logp, axis=0)",
         "token = ce[-1]")],
}


@pytest.mark.parametrize("fault", sorted(TEMPLATE_FAULTS))
def test_a_broken_template_is_not_correct(fault, tmp_path):
    source = (BENCH.parent / load_ouro_cfg()["template_file"]).read_text()
    for old, new in TEMPLATE_FAULTS[fault]:
        assert source.count(old) == 1, old
        source = source.replace(old, new)
    (tmp_path / "template.py").write_text(source)
    rc, line = rehearse(cfg=lambda c: dict(
        tiny_ouro(c), template_file=str(tmp_path / "template.py")))
    assert rc == 0 and line["correct"] is False, line
    assert any(v > limit for v, limit in line["compared"].values())


def test_the_control_fails_each_stand_in_whole_and_by_the_first_step_alone():
    """float8 products, half a batch, a state left unchanged, an altered
    score, three passes for four and exit weights held uniform each come out
    not correct; the reference against itself correct."""
    import ouro_control

    cfg = tiny_ouro(load_ouro_cfg())
    row = ouro_control.readings(cfg, 2**31 + 5, TINY_LIMITS)
    for name in ("fp8", "half_batch", "state_unchanged", "score_altered", "three_passes",
                 "uniform_exit"):
        assert row[name]["correct"] is False, (name, row[name])
    assert row["reference_again"]["correct"] is True, row["reference_again"]
    again = row["reference_again"]["numbers"]
    assert max(v for k, v in again.items() if k != "unmoved_share") == 0.0
    first = ouro_control.readings(
        cfg, 2**31 + 5, TINY_LIMITS, first_step_only=True,
        stand_ins=("fp8", "half_batch", "three_passes", "uniform_exit", "reference_again"))
    for name in ("fp8", "half_batch", "three_passes", "uniform_exit"):
        assert first[name]["correct"] is False
        assert first[name]["numbers"]["first_step_flips"] == pytest.approx(
            row[name]["numbers"]["first_step_flips"], rel=1e-6)
    assert first["reference_again"]["numbers"] == {"first_step_flips": 0.0, "first_loss_gap": 0.0}
