"""The sparse-attention cell on the CPU at a tiny size (as
``test_ouro_cell_cpu.py`` does for the looped one): the reference's pieces against
``jax.value_and_grad`` of its own ``loss``, a sound rehearsal correct,
planted faults not correct, what the new readers read, and the counts by
hand."""

import io
import json

import numpy as np
import pytest
from conftest import BENCH
from keye_tiny import load_keye_cfg, tiny_keye

import run

CELL = "keye_dsa_sweep_32k"

#: Limits at the tiny size (64-token sequences that select 16 keys a query,
#: 128 scored tokens: one token is 0.0078 of a score), set as the cell's own:
#: above what sound tiny runs read (0.8% flipped, 1e-4 of the loss, 0.2% of
#: the selection exchanged), below what the control and the faults read
#: (float8 7.6% flipped, 7.7e-4 of the loss, 3.4% of the selection).
TINY_LIMITS = {"first_step_flips": 0.03, "first_loss_gap": 0.0006, "score_gap": 0.02,
               "unmoved_share": 0.6, "selection_gap": 0.02}


def rehearse(seed=7, trace=0, seconds="3", **overrides):
    from rafiki_tpu.ops.train import clear_program_cache

    clear_program_cache()
    out = io.StringIO()
    defaults = dict(cfg=tiny_keye, out=out,
                    traffic=lambda t: dict(t, trace={"start_s": 0.2, "seconds": 1.0}),
                    limits=TINY_LIMITS)
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", seconds,
                   "--trace", str(trace)], platform="cpu",
                  overrides=dict(defaults, **overrides))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def manifest():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_the_reference_in_pieces_is_the_reference_whole():
    """``keye_sweep.DsaReference`` runs the reference a layer at a time, each
    layer's piece handing back the indexer's loss beside its output and its
    vector-Jacobian product taking a cotangent for each: the objective and
    every gradient leaf are ``jax.value_and_grad`` of the reference's own
    ``loss``, and the one kind of layer is one program."""
    import jax
    import jax.numpy as jnp
    from drivers import keye_sweep

    cfg = tiny_keye(load_keye_cfg())
    ref = keye_sweep.DsaReference(cfg, 77, 5)
    ref.opts = dict(ref.opts, seq_block=1)      # a batch of two in two blocks, their sums added
    p = jax.tree.map(jnp.asarray, ref.init_params())
    X, Y = ref.first_set
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(ref.mod.loss)(p, jnp.asarray(X), jnp.asarray(Y),
                                                       cfg, 0.05)
    loss2, grads2 = ref.loss_and_grads(p, X, Y, 0.05)
    assert {k[0] for k in ref.pieces().exe} == {
        "init", "add", "embed.vjp", "head.vjp", "dsa.moe.fwd", "dsa.moe.vjp"}
    assert sum(k[0] == "dsa.moe.fwd" for k in ref.pieces().exe) == 1
    assert abs(float(loss) - loss2) <= 1e-6 * float(loss)
    assert set(grads2) == set(grads)
    for k in grads:
        scale = float(np.max(np.abs(grads[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(grads2[k]), np.asarray(grads[k]),
                                   rtol=2e-4, atol=2e-5 * scale, err_msg=k)
    # what makes the reference fit (blocks of queries, each quarter of the
    # sequence seeing the keys to its end) changes no number
    h = jax.random.normal(jax.random.PRNGKey(1), (2, int(cfg["seq_len"]), 64))
    np.testing.assert_array_equal(np.asarray(ref.mod.selection(p, 1, h, cfg)),
                                  np.asarray(ref.mod.selection(p, 1, h, cfg, q_block=8)))
    with jax.default_matmul_precision("highest"):
        whole = ref.mod.layer(p, 1, h, cfg)
        fit = ref.mod.layer(p, 1, h, cfg, fit=True, q_block=8)
    for a, b in zip(whole, fit):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    ref.build()                                  # every piece ``compare`` calls builds


def test_a_sound_run_is_correct_and_prints_the_contracts_keys():
    rc, line = rehearse(seed=2**31 + 31)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {m["name"] for m in manifest()["end_to_end"]}
    assert set(line["compared"]) == set(TINY_LIMITS)
    for value, limit in line["compared"].values():
        assert value <= limit


def test_the_cells_readers_read_what_a_cpu_run_has():
    rc, line = rehearse(trace=1)
    assert rc == 0       # (not correct: a CPU trace holds no device operation)
    got = set(line["metrics"])
    assert got >= {"epoch_program_ms.lm", "evaluate_share.lm", "persist_share.sweep",
                   "health_snapshot_share.lm", "persist_wait_share.sweep",
                   "advisor_share.sweep", "feedback_share.sweep", "held_slot_share.lm",
                   "persist_fetch_ms.sweep", "persist_write_ms.sweep",
                   "compiles_in_window.sweep", "init_share.lm"}
    # no device plane and no peak on the CPU: those stay out of the line
    assert not got & {"traced_idle_share.sweep", "lm_mfu.lm", "dsa_device_share.lm",
                      "dsa_attention_roofline.lm", "moe_device_share.lm",
                      "loss_device_share.lm"}
    listed = {m["name"] for m in run.metrics_of_cell(manifest(), CELL, "per_layer")}
    assert got <= listed
    assert {"dsa_device_share.lm", "dsa_attention_roofline.lm", "held_slot_share.lm",
            "moe_device_share.lm"} <= listed
    assert not listed & {"kda_device_share.lm", "mla_device_share.lm", "conv_device_share.lm",
                         "attn_device_share.lm", "gqa_attention_roofline.lm",
                         "ffn_device_share.lm", "layer_call_ms.lm"}
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0


def test_the_new_readers_return_nothing_where_nothing_is_to_read():
    # (a program without the sparse attention has nothing these readers read)
    empty = {"spans": [], "window_s": 1.0, "done": 0, "peak": {"bf16_flops": 197e12,
                                                               "hbm_bytes_per_s": 819e9}}
    for name in ("dsa_device_share.lm", "dsa_attention_roofline.lm"):
        assert run.load_reader(name)(dict(empty)) is None, name
        assert run.load_reader(name)(dict(empty, scope_seconds={"joined": 0.0})) is None, name
    assert run.load_reader("dsa_device_share.lm")(dict(
        empty, scope_seconds={"joined": 4.0, "total": 5.0, "dsa": 3.0})) == 75.0
    # compute-bound: the FLOPs set the bound; bandwidth-bound: the bytes
    read = run.load_reader("dsa_attention_roofline.lm")
    assert read(dict(empty, dsa_kernels={"seconds": 2.0, "needed_flops": 197e12,
                                         "needed_bytes": 1e9})) == pytest.approx(50.0)
    assert read(dict(empty, dsa_kernels={"seconds": 2.0, "needed_flops": 1e12,
                                         "needed_bytes": 819e9})) == pytest.approx(50.0)


def test_the_join_groups_this_templates_scopes():
    from drivers import keye_sweep

    text = '''
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.loss/transpose(jvp(_KeyeVl2))/layer_2/attn/dsa.select/while/body/reduce" id=3}
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/rafiki.optimizer/mul"}
  %dsa_fwd.3 = (bf16[1,32,512,128]{3,2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call", backend_config={"kernel_metadata": "a
b"},
    metadata={op_name="jit(train_step)/rafiki.loss/jvp(_KeyeVl2)/layer_3/attn/jvp(dsa.attn)/jit(dsa_attention)/cond/branch_0_fun/dsa_fwd/pallas_call"}
  %fusion.10 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/jvp(_KeyeVl2)/layer_1/moe/moe.experts/dot_general"}
  %fusion.11 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/lm.loss/checkpoint/dot"}
  %fusion.12 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/rafiki.loss/dsa.kl/reduce_sum"}
  %bitcast.1 = f32[8]{0} bitcast(%p)
'''
    got = keye_sweep.scope_seconds(text, {
        "fusion.7": 2.0, "fusion.9": 1.0, "dsa_fwd.3": 0.75, "fusion.10": 0.5,
        "fusion.11": 0.25, "fusion.12": 0.125, "unknown.1": 4.0})
    assert got == {"joined": 4.625, "total": 8.625, "dsa": 2.875, "other": 1.0, "moe": 0.5,
                   "loss": 0.25}


def test_the_counts_by_hand():
    """``forward_flops``: the projections, the indexer over the mean number of
    keys before a token, the attention over the mean selection, the routed
    experts' expected share, the head; ``sparse_attention_work``: 4 H d pairs
    forward and 2.5 times that backward, the bytes of q, k, v, o (and their
    gradients), which ``keye_sweep`` spreads over the kernels' calls: a step's 8
    forward calls (4 layers and their recomputation) and 4 of each backward
    kernel need 4 forward and 4 backward passes."""
    from drivers import keye_sweep
    from references import keye_vl2 as R

    cfg = load_keye_cfg()
    assert R.parameters(cfg) == 465_391_104
    D, V, T, k = 2048, 18992, 32768, 2048
    pairs = k * (k + 1) / 2 + (T - k) * k
    assert R._selected_keys(T, k) == pairs
    per_layer = (D * 32 * 128 + 2 * D * 4 * 128 + 32 * 128 * D + pairs / T * 32 * 2 * 128
                 + D * (16 * 64 + 64 + 16) + (T + 1) / 2 * 16 * 64
                 + D * 128 + 3 * D * 768 * 8 * 16 / 128)
    assert R.forward_flops(cfg) == pytest.approx(2 * (D * V + 4 * per_layer), rel=1e-9)
    work = R.sparse_attention_work(cfg)
    assert work["forward_flops"] == 4 * 32 * 128 * pairs
    assert work["backward_flops"] == 2.5 * work["forward_flops"]
    q_o, k_v = 2 * T * 32 * 128 * 2, 2 * T * 4 * 128 * 2
    assert work["forward_bytes"] == q_o + k_v and work["backward_bytes"] == 2 * (q_o + k_v)
    events = ([(f"%dsa_fwd.{i} = ...", 0.0, 4e6) for i in range(8)]
              + [(f"%dsa_dq.{i} = ...", 0.0, 6e6) for i in range(4)]
              + [(f"%dsa_dkv.{i} = ...", 0.0, 6e6) for i in range(4)])
    calls = keye_sweep.dsa_kernel_calls(events)
    assert {p: c["calls"] for p, c in calls.items()} == {"dsa_fwd": 8, "dsa_dq": 4, "dsa_dkv": 4}
    needed = sum(calls[p]["calls"] * share * work[f"{which}_flops"]
                 for p, which, share in keye_sweep.DSA_KERNELS)
    assert needed == 4 * 3.5 * work["forward_flops"]
    assert keye_sweep.keys_selected_a_layer(cfg) == pairs


# -- the timed path broken underneath, and the control ---------------------------

#: Faults planted in the template itself (a copy of its bytes, as a tenant's
#: model file is), each a list of (old, new).
TEMPLATE_FAULTS = {
    "every_key_selected": [("    return (above | (tied & (pos <= cut[..., None]))).astype(jnp.int8)",
                            "    return jnp.broadcast_to(valid, u.shape).astype(jnp.int8)")],
    "half_the_keys_selected": [("_select_rows(index_scores(qb, kI, wb), first, topk)",
                                "_select_rows(index_scores(qb, kI, wb), first, topk // 2)")],
    "the_indexers_loss_dropped": [("return ce / n + index_loss, {", "return ce / n, {")],
    "p_not_held_constant": [("p = jax.lax.stop_gradient(jnp.mean(prob, axis=1))",
                             "p = jnp.mean(prob, axis=1)")],
}


@pytest.mark.parametrize("fault", sorted(TEMPLATE_FAULTS))
def test_a_broken_template_is_not_correct(fault, tmp_path):
    source = (BENCH.parent / load_keye_cfg()["template_file"]).read_text()
    for old, new in TEMPLATE_FAULTS[fault]:
        assert source.count(old) == 1, old
        source = source.replace(old, new)
    (tmp_path / "template.py").write_text(source)
    rc, line = rehearse(cfg=lambda c: dict(
        tiny_keye(c), template_file=str(tmp_path / "template.py")))
    assert rc == 0 and line["correct"] is False, line
    assert line["problems"] or any(v > limit for v, limit in line["compared"].values())


def test_the_control_fails_each_stand_in_whole_and_by_the_first_step_alone():
    """float8 products, half a batch, a state left unchanged, an altered
    score, half the keys, every key and the indexer's loss dropped each come
    out not correct; the reference against itself correct."""
    import keye_control

    cfg = tiny_keye(load_keye_cfg())
    row = keye_control.readings(cfg, 2**31 + 9, TINY_LIMITS)
    faults = ("fp8", "half_batch", "state_unchanged", "score_altered", "topk_half", "dense",
              "no_index_loss")
    for name in faults:
        assert row[name]["correct"] is False, (name, row[name])
    assert row["reference_again"]["correct"] is True, row["reference_again"]
    again = row["reference_again"]["numbers"]
    assert max(v for k, v in again.items() if k != "unmoved_share") == 0.0
    assert row["topk_half"]["numbers"]["selection_gap"] > 0.2
    assert row["dense"]["numbers"]["selection_gap"] > 0.5
    first = keye_control.readings(cfg, 2**31 + 9, TINY_LIMITS, first_step_only=True,
                                  stand_ins=("fp8", "half_batch", "topk_half", "dense",
                                             "no_index_loss", "reference_again"))
    for name in ("fp8", "half_batch", "topk_half", "dense", "no_index_loss"):
        assert first[name]["correct"] is False
        assert first[name]["numbers"]["first_step_flips"] == pytest.approx(
            row[name]["numbers"]["first_step_flips"], rel=1e-6)
    assert first["reference_again"]["numbers"] == {
        "first_step_flips": 0.0, "first_loss_gap": 0.0, "selection_gap": 0.0}
