"""The main path's programs compile for the chip — checked without one.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). These tests
hand the jitted epoch programs of the three train lanes and the stacked
serving forward the described v5e devices and real-width shapes
(``jax.eval_shape`` — nothing is placed, nothing runs) and compile them:
what the chip's compiler would refuse is refused here, at no chip time.
A compile that passes is NOT a chip run and is never reported as one —
``chip_smoke.py`` is the chip run.

One file on purpose: the process that describes the topology keeps the
TPU library until it exits, so a second file on another xdist worker
would skip in silence. The topology is described inside a fixture, never
at import (every worker imports this file).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip (the next one warns and compiles
    again), so the cache is off while the topology fixture lives."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """``tree``'s shapes (from eval_shape) as arguments on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _serial_state(fns, init, sharding):
    """The abstract (params, opt_state, step, rng, hyper) a TrainLoop
    carries, for ``fns`` of one JaxModel."""
    rng = jax.random.PRNGKey(0)
    params, opt_state = jax.eval_shape(init, rng)
    hyper = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in fns["hyper"]}
    state = (params, opt_state, jax.ShapeDtypeStruct((), jnp.int32),
             jax.eval_shape(lambda: rng), hyper)
    return _on(sharding, state)


def _peak_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def _vgg16():
    from rafiki_tpu.models.vgg import Vgg

    return Vgg(depth=16, width_mult=1.0, dropout=0.1, learning_rate=1e-3,
               batch_size=256, epochs=1, seed=0)


def test_serial_vgg16_epoch_and_eval_compile_for_v5e(one_chip):
    """The canonical trial (chip_smoke.py): VGG16 depth 16 / width 1.0,
    32x32x3, 50k train / 10k eval device-resident, batch 256."""
    from rafiki_tpu.ops.train import Program, _ShardingPlan

    model = _vgg16()
    fns = model._loop_fns(10, (32, 32, 3))
    prog = Program(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                   fns["optimizer"], _ShardingPlan.build(None))
    state = _serial_state(fns, prog.init, one_chip)
    n_steps = 50_000 // 256
    train = prog.train_epoch.lower(
        state, _spec((50_000, 32, 32, 3), jnp.float32, one_chip),
        _spec((50_000,), jnp.int32, one_chip),
        _spec((n_steps, 256), jnp.int32, one_chip),
        _spec((n_steps,), jnp.float32, one_chip)).compile()
    assert "convolution" in train.as_text()
    assert _peak_bytes(train) < HBM_BYTES
    evaluate = prog.eval_epoch.lower(
        state[0], _spec((10_000, 32, 32, 3), jnp.float32, one_chip),
        _spec((10_000,), jnp.int32, one_chip),
        _spec((10_000 // 256, 256), jnp.int32, one_chip)).compile()
    assert _peak_bytes(evaluate) < HBM_BYTES


def test_packed_k4_feedforward_epoch_compiles_for_v5e(one_chip):
    """The packed lane's program: k=4 FeedForward trials at the
    template's largest shape knobs (3x256, batch 128) vmapped into one
    epoch scan over MNIST-shaped data (60k x 28x28x1)."""
    from rafiki_tpu.models.ff import FeedForward
    from rafiki_tpu.ops.train import PackedProgram

    k, batch = 4, 128
    model = FeedForward(hidden_layers=3, hidden_units=256, learning_rate=1e-3,
                        batch_size=batch, epochs=1, seed=0)
    fns = model._loop_fns(10, (28, 28, 1))
    prog = PackedProgram(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                         fns["optimizer"], k)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(k)])
    params, opt_state = jax.eval_shape(prog.init, rngs)
    hyper = {h: jax.ShapeDtypeStruct((k,), jnp.float32) for h in fns["hyper"]}
    state = _on(one_chip, (params, opt_state,
                           jax.ShapeDtypeStruct((k,), jnp.int32),
                           jax.eval_shape(lambda: rngs), hyper))
    n_steps = 60_000 // batch
    compiled = prog.train_epoch.lower(
        state, _spec((60_000, 28, 28, 1), jnp.float32, one_chip),
        _spec((60_000,), jnp.int32, one_chip),
        _spec((n_steps, k, batch), jnp.int32, one_chip),
        _spec((n_steps, k), jnp.float32, one_chip)).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_a_rounds_stacked_cast_of_16_vgg16s_compiles_for_v5e(one_chip):
    """Persist's one device program a pack round (ISSUE 26): the stacked
    parameters of the benchmark cell's 16 VGG16s cast to the stored
    bfloat16 in one call, the output half the argument's bytes."""
    from rafiki_tpu.ops.train import PackedProgram
    from rafiki_tpu.utils.serial import _cast_tree_bf16

    k = 16
    fns = _vgg16()._loop_fns(10, (32, 32, 3))
    prog = PackedProgram(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                         fns["optimizer"], k)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(k)])
    params, _opt_state = jax.eval_shape(prog.init, rngs)
    assert {a.shape[0] for a in jax.tree.leaves(params)} == {k}
    compiled = _cast_tree_bf16.lower(_on(one_chip, params)).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 16 * 14.9e6 * 4
    # (to a tile's padding: 0.4808 GB out for 0.9616 GB in)
    assert ma.output_size_in_bytes == pytest.approx(
        ma.argument_size_in_bytes / 2, rel=1e-3)
    assert _peak_bytes(compiled) < HBM_BYTES


def test_sharded_width4_transformer_epoch_compiles_for_v5e(topo):
    """The sharded lane's program at width 4: the Transformer template's
    largest setting on a four-device ("shard",) mesh of the described
    chips. The compiled text must hold the lane's collectives
    (all-gather in, no all-reduce needed: compute is replicated) and
    each device's share must fit its HBM."""
    from rafiki_tpu.models.transformer import Transformer
    from rafiki_tpu.shard.loop import _ShardedProgram
    from rafiki_tpu.shard.plan import ShardPlan

    width, batch, n, length = 4, 64, 4096, 16
    model = Transformer(embed_dim=128, num_heads=4, num_layers=2,
                        learning_rate=1e-3, batch_size=batch, epochs=1, seed=0)
    model._dataset_meta = {"vocab": 81}
    fns = model._loop_fns(5, (length,))
    mesh = Mesh(np.asarray(topo.devices[:width]), ("shard",))
    prog = _ShardedProgram(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                           fns["optimizer"], mesh, ShardPlan(width=width),
                           dynamic_lr=True,
                           hyper_keys=tuple(sorted(fns["hyper"])))
    rng = jax.random.PRNGKey(0)
    hyper = {h: jnp.float32(0.0) for h in sorted(fns["hyper"])}
    abs_state = jax.eval_shape(prog.init, rng, rng, hyper)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_state, prog.state_sharding)
    rep = NamedSharding(mesh, P())
    n_steps = n // batch
    compiled = prog.train_epoch.lower(
        state, _spec((n, length), jnp.int32, rep),
        _spec((n,), jnp.int32, rep),
        _spec((n_steps, batch), jnp.int32, rep),
        _spec((n_steps,), jnp.float32, rep)).compile()
    assert "all-gather" in compiled.as_text()
    assert _peak_bytes(compiled) < HBM_BYTES
    sharded = [s for s in jax.tree.leaves(prog.state_sharding)
               if s.spec != P()]
    assert sharded, "width-4 plan sharded no leaf of the transformer state"


def test_stacked_top2_vgg16_serving_forward_compiles_for_v5e(one_chip):
    """The stacked serving route: the top-2 VGG16 trials' params stacked
    on a leading axis, one vmapped forward per 64-query batch."""
    from rafiki_tpu.parallel.ensemble import make_ensemble_forward

    model = _vgg16()
    fns = model._loop_fns(10, (32, 32, 3))
    params = jax.eval_shape(fns["init_fn"], jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((2,) + a.shape, a.dtype,
                                       sharding=one_chip), params)
    fwd = make_ensemble_forward(fns["apply_eval"])
    compiled = fwd.lower(
        stacked, {"x": _spec((64, 32, 32, 3), jnp.float32, one_chip)}).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


# -- the language model's latent attention (ISSUE 28) ---------------------------

SCORES = "f32[2,32,256,8192]"  # a block of 256 queries' scores over 8,192 keys, in HBM


def _pinned_cell(template, config: str, planned_steps: int):
    """(a language-model template pinned to its benchmark cell's published
    widths, vocabulary, sequence length, batch)."""
    import json
    from pathlib import Path

    from rafiki_tpu.model.knobs import FixedKnob

    cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmark" / "configs"
                      / f"{config}.json").read_text())
    pinned = {k: v["fixed"] for k, v in cfg["knobs"].items() if "fixed" in v}
    pinned["seed"] = 0

    class Cell(template):
        @staticmethod
        def get_knob_config():
            base = template.get_knob_config()
            return {k: (FixedKnob(pinned[k], affects_shape=True)
                        if k in pinned and isinstance(base[k], FixedKnob) else base[k])
                    for k in base}

    model = Cell(**pinned, learning_rate=1e-3, label_smoothing=0.05)
    model._planned_steps = planned_steps
    return model, int(cfg["vocab_size"]), int(cfg["seq_len"]), int(pinned["batch_size"])


def _kimi_linear_cell():
    from rafiki_tpu.models.kimi_linear import KimiLinear

    return _pinned_cell(KimiLinear, "kimi_linear_48b_a3b_ep32", 8)


def _kernels(text, prefix):
    """(instruction name, op_name) of the kernel calls in a compiled text
    whose name starts with ``prefix``. (An attention kernel's printed call
    spans three lines: its kernel metadata holds a line break.)"""
    import re

    return [(m.group(1), text[m.end(): text.find("\n  %", m.end())].split('op_name="')[1]
             .split('"')[0])
            for m in re.finditer(rf"^\s*%({prefix}[\w.]*) = ", text, re.M)]


def _attention_kernels(text):
    return _kernels(text, "splash_mha")


def _chunk_kernels(text):
    """The fused chunk rule's calls by kind, each under the ``kda`` scope."""
    kernels = _kernels(text, "kda_chunk_")
    assert all("/kda/" in op for _name, op in kernels), kernels
    assert all("transpose(jvp(" in op for name, op in kernels if "bwd" in name), kernels
    return sorted(name.split(".")[0] for name, _op in kernels)


def _branch_kernels(text):
    """The branch kernels' calls by kind (``kda_branch``: a q, k or v
    branch's convolution, SiLU and l2norm), each under the ``kda`` scope."""
    kernels = _kernels(text, "kda_branch_")
    assert all("/kda/" in op for _name, op in kernels), kernels
    assert all("transpose(jvp(" in op for name, op in kernels if "bwd" in name), kernels
    return sorted(name.split(".")[0] for name, _op in kernels)


def _scan_leftovers(text):
    """Lines of the ``jax.numpy`` chunk rule in a compiled text: the
    triangular inverse's ``jit(diagonal)`` (gathers, scatter-adds and the
    ``while`` loops the compiler makes of them)."""
    return [line for line in text.split("\n") if "jit(diagonal)" in line]


def test_the_latent_attention_layer_is_three_kernel_calls_on_v5e(one_chip):
    """``_Mla``'s value and gradients at the cell's shapes (2 x 8,192
    tokens, 32 heads of 192 / 128): lowered for the described chip,
    ``mla_attention`` takes the fused kernel. One forward, two backward
    calls, each under the ``mla`` scope (``mla_device_share.lm`` reads
    that), and no block of float32 scores in HBM."""
    from rafiki_tpu.models import kimi_linear as K

    model, _vocab, T, B = _kimi_linear_cell()
    c = dict(model.module_config())
    mod = K._Mla(c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                 c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"])
    x = jax.ShapeDtypeStruct((B, T, c["hidden_size"]), jnp.float32)
    params = jax.eval_shape(mod.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        out, fused = mod.apply({"params": params}, x)
        return jnp.sum(out.astype(jnp.float32) ** 2), fused

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)).lower(
        _on(one_chip, params), _on(one_chip, x)).compile()
    text = compiled.as_text()
    kernels = _attention_kernels(text)
    assert sorted(name.split(".")[0] for name, _op in kernels) == [
        "splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals",
        "splash_mha_fwd_residuals"]
    assert all("/mla/" in op for _name, op in kernels), kernels
    assert sum("transpose(jvp(" in op for _name, op in kernels) == 2
    assert text.count("tpu_custom_call") == 3 and SCORES not in text
    # the blocked code's layer, compiled the same way at the parent commit: 3.47 GB (1.42 here)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.47e9


def test_the_delta_rule_layer_is_its_chunk_and_branch_kernels_on_v5e(one_chip):
    """``_Kda``'s value and gradients at the cell's shapes (2 x 8,192
    tokens, 32 heads of 128, chunk 64, 4 taps): lowered for the described
    chip, ``kda_chunked`` takes the fused chunk kernels, one call forward and
    one backward, and each of the q, k, v branches' tails the branch kernels,
    one call forward and one backward (the branch's ``jax.checkpoint``
    recomputes its projection alone: the backward kernel reads nothing else),
    each under the ``kda`` scope (``kda_device_share.lm`` reads that);
    nothing of the scan's triangular inverse is left, and no SiLU of a
    branch runs in XLA's code."""
    from rafiki_tpu.models import kimi_linear as K

    model, _vocab, T, B = _kimi_linear_cell()
    c = dict(model.module_config())
    mod = K._Kda(c["num_heads"], c["kda_head_dim"], c["short_conv_kernel_size"],
                 c["kda_chunk"], c["rms_norm_eps"])
    x = jax.ShapeDtypeStruct((B, T, c["hidden_size"]), jnp.float32)
    params = jax.eval_shape(mod.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        out, fused = mod.apply({"params": params}, x)
        return jnp.sum(out.astype(jnp.float32) ** 2), fused

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)).lower(
        _on(one_chip, params), _on(one_chip, x)).compile()
    text = compiled.as_text()
    assert _chunk_kernels(text) == ["kda_chunk_bwd", "kda_chunk_fwd"]
    assert _branch_kernels(text) == ["kda_branch_bwd"] * 3 + ["kda_branch_fwd"] * 3
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert not _scan_leftovers(text) and "jit(silu)" not in text
    # the scan's layer, compiled the same way at PR 29: 3.40 GB; PR 30's kernels:
    # 3.39 GB; with the branch kernels (PR 36): 2.50 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2.75e9


def test_the_language_models_step_and_evaluation_compile_for_v5e_with_the_kernel(one_chip):
    """The benchmark cell's whole step program (602 M parameters, Adam,
    every layer recomputed) and its evaluation step: the TPU compiler has
    refused in the whole step what it took in every part (PR 27's two
    scatter-adds). The forward kernel twice (the forward pass and the
    layer's ``nn.remat``), the two backward kernels once, nothing else of
    the attention; the evaluation takes the kernel that saves nothing. Of the
    chunk rule: in each of the four KDA layers the forward kernel twice and
    the backward kernel once, the forward kernel once in the evaluation, and
    no line of the scan's triangular inverse. Of the branches' tails: in each
    KDA layer the forward branch kernel twice and the backward once for each
    of q, k and v, the forward once in the evaluation (``count.kda.branch_fused``
    reads 12 a step: four layers of three branches)."""
    from rafiki_tpu.ops.train import Program, _ShardingPlan

    model, vocab, T, B = _kimi_linear_cell()
    fns = model._loop_fns(vocab, (T,))
    prog = Program(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                   fns["optimizer"], _ShardingPlan.build(None),
                   eval_count=fns["eval_count"])
    state = _serial_state(fns, prog.init, one_chip)
    batch = {k: _spec((B, T), jnp.int32, one_chip) for k in ("x", "y")}
    step = prog.train_step.lower(state, batch).compile()
    text = step.as_text()
    kernels = _attention_kernels(text)
    assert sorted(name.split(".")[0] for name, _op in kernels) == [
        "splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals",
        "splash_mha_fwd_residuals", "splash_mha_fwd_residuals"]
    assert all("/mla/" in op for _name, op in kernels), kernels
    assert SCORES not in text and "[2,32,256," not in text
    assert _chunk_kernels(text) == ["kda_chunk_bwd"] * 4 + ["kda_chunk_fwd"] * 8
    assert _branch_kernels(text) == ["kda_branch_bwd"] * 12 + ["kda_branch_fwd"] * 24
    assert not _scan_leftovers(text)
    # the step with the scan, compiled the same way: 4.94 GB of temporaries
    assert step.memory_analysis().temp_size_in_bytes < 4.94e9
    assert _peak_bytes(step) < HBM_BYTES
    evaluate = prog.eval_step.lower(state[0], batch).compile()
    assert [name.split(".")[0] for name, _op in _attention_kernels(evaluate.as_text())] == [
        "splash_mha_fwd_no_residuals"]
    assert _chunk_kernels(evaluate.as_text()) == ["kda_chunk_fwd"] * 4
    assert _branch_kernels(evaluate.as_text()) == ["kda_branch_fwd"] * 12
    assert not _scan_leftovers(evaluate.as_text())
    assert _peak_bytes(evaluate) < HBM_BYTES


# -- the second language model (ISSUE 31) ---------------------------------------

def _lfm2_moe_cell():
    from rafiki_tpu.models.lfm2_moe import Lfm2Moe

    return _pinned_cell(Lfm2Moe, "lfm2_8b_a1b_ep4", 16)


def test_the_second_language_models_step_and_evaluation_compile_for_v5e_with_the_kernel(one_chip):
    """The second cell's whole step program (569 M parameters, Adam, every
    layer recomputed) and its evaluation step at the published widths:
    lowered for the described chip, the grouped-query attention takes the
    fused kernel at 8 key/value heads of 64 for 32 query heads (the forward
    kernel twice, the two backward kernels once), each call under the
    ``lfm2.attn`` scope that ``attn_device_share.lm`` reads, and no float32
    score array of a whole row in HBM; both fit the chip."""
    from rafiki_tpu.ops.train import Program, _ShardingPlan

    model, vocab, T, B = _lfm2_moe_cell()
    fns = model._loop_fns(vocab, (T,))
    prog = Program(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                   fns["optimizer"], _ShardingPlan.build(None),
                   eval_count=fns["eval_count"])
    state = _serial_state(fns, prog.init, one_chip)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state[0])) == 568_647_936
    batch = {k: _spec((B, T), jnp.int32, one_chip) for k in ("x", "y")}
    step = prog.train_step.lower(state, batch).compile()
    text = step.as_text()
    kernels = _attention_kernels(text)
    assert sorted(name.split(".")[0] for name, _op in kernels) == [
        "splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals",
        "splash_mha_fwd_residuals", "splash_mha_fwd_residuals"]
    assert all("/lfm2.attn/" in op for _name, op in kernels), kernels
    assert "f32[2,32,8192,8192]" not in text and "[2,32,256,8192]" not in text
    # as compiled for PR 31: 3.79 GB of temporaries beside 6.82 GB of state
    assert step.memory_analysis().temp_size_in_bytes < 4.2e9
    assert _peak_bytes(step) < HBM_BYTES
    evaluate = prog.eval_step.lower(state[0], batch).compile()
    assert [name.split(".")[0] for name, _op in _attention_kernels(evaluate.as_text())] == [
        "splash_mha_fwd_no_residuals"]
    assert _peak_bytes(evaluate) < HBM_BYTES


# -- the third language model (ISSUE 33) ----------------------------------------

def _ouro_cell():
    from rafiki_tpu.models.ouro import Ouro

    return _pinned_cell(Ouro, "ouro_2_6b_pp8", 4)


def test_the_looped_language_models_step_and_evaluation_compile_for_v5e_with_the_kernel(one_chip):
    """The third cell's whole step program (510 M parameters, Adam, every
    layer visit recomputed) and its evaluation step at the published widths:
    lowered for the described chip, the attention takes the fused kernel at
    16 heads of 128, and the program holds the STACK ONCE: six layers' calls
    inside the loop over the four passes (forward and its ``nn.remat``: twelve
    forward kernels; six of each backward kernel), not twenty-four layers
    unrolled; every call under the ``ouro.attn`` scope that
    ``attn_device_share.lm`` reads; both fit the chip. (24 fused calls a
    step forward is the loop's trip count times these six: on the chip
    ``count.attn.fused`` reads it.)"""
    from rafiki_tpu.ops.train import Program, _ShardingPlan

    model, vocab, T, B = _ouro_cell()
    assert (vocab, T, B) == (49152, 8192, 2)
    fns = model._loop_fns(vocab, (T,))
    prog = Program(fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                   fns["optimizer"], _ShardingPlan.build(None),
                   eval_count=fns["eval_count"])
    state = _serial_state(fns, prog.init, one_chip)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state[0])) == 509_661_185
    batch = {k: _spec((B, T), jnp.int32, one_chip) for k in ("x", "y")}
    step = prog.train_step.lower(state, batch).compile()
    text = step.as_text()
    kernels = _attention_kernels(text)
    assert sorted(name.split(".")[0] for name, _op in kernels) == (
        ["splash_mha_dkv_no_residuals"] * 6 + ["splash_mha_dq_no_residuals"] * 6
        + ["splash_mha_fwd_residuals"] * 12)
    assert all("/ouro.attn/" in op and "/while/body/" in op for _name, op in kernels), kernels
    assert "f32[2,16,8192,8192]" not in text and "[2,16,256,8192]" not in text
    # as compiled for PR 33: 7.85 GB of temporaries beside 6.12 GB of state (11.3
    # with float32 copies of the 24 visits' inputs; 11.5 with the loss unrolled)
    assert step.memory_analysis().temp_size_in_bytes < 8.3e9
    assert _peak_bytes(step) < HBM_BYTES
    evaluate = prog.eval_step.lower(state[0], batch).compile()
    assert [name.split(".")[0] for name, _op in _attention_kernels(evaluate.as_text())] == [
        "splash_mha_fwd_no_residuals"] * 6
    assert _peak_bytes(evaluate) < HBM_BYTES


# -- the fourth language model ----------------------------------------------------

def test_the_sparse_attention_kernels_compile_for_v5e_at_the_cells_widths(one_chip):
    """The sparse attention's value and gradients at the Keye cell's shapes
    (32,768 tokens, 32 query heads on 4 key/value heads of 128, an indexer of
    16 heads of 64) lowered for the described chip: its three kernels, each
    under the ``dsa.attn`` scope that ``dsa_device_share.lm`` reads, and no
    [T, T] float32 array; the selection of every query fits too (its int8
    mask of 1 GiB is the largest array)."""
    from rafiki_tpu.models import keye_vl2 as M

    B, T, H, Hk, d, HI, dI = 1, 32768, 32, 4, 128, 16, 64
    bf = lambda *s: _spec(s, jnp.bfloat16, one_chip)
    q, k, v, qI, kI = bf(B, T, H, d), bf(B, T, Hk, d), bf(B, T, Hk, d), bf(B, T, HI, dI), bf(B, T, dI)
    w = _spec((B, T, HI), jnp.float32, one_chip)
    mask = _spec((B, T, T), jnp.int8, one_chip)
    counts = _spec((B, T // M.DSA_QUERY_TILE, T // M.DSA_KEY_TILE), jnp.int32, one_chip)

    def value_and_grads(q, k, v, mask, counts, qI, kI, w):
        def f(q, k, v, qI, kI, w):
            with jax.named_scope(M.SCOPE_ATTN):
                o, kl, fused = M.dsa_attention(q, k, v, mask, counts, qI, kI, w)
            return jnp.sum(o.astype(jnp.float32)) + jnp.sum(kl), fused
        return jax.grad(f, argnums=tuple(range(6)), has_aux=True)(q, k, v, qI, kI, w)

    compiled = jax.jit(value_and_grads).lower(q, k, v, mask, counts, qI, kI, w).compile()
    text = compiled.as_text()
    kernels = _kernels(text, "dsa_")
    assert sorted(name.split(".")[0] for name, _op in kernels) == ["dsa_dkv", "dsa_dq", "dsa_fwd"]
    assert all("dsa.attn" in op for _name, op in kernels), kernels
    assert f"f32[1,{T},{T}]" not in text and f"f32[{T},{T}]" not in text
    assert _peak_bytes(compiled) < HBM_BYTES
    select = jax.jit(lambda qI, kI, w: M.dsa_select(qI, kI, w, 2048)).lower(qI, kI, w).compile()
    # the mask out, a block of 128 queries' scores and their search inside
    assert select.memory_analysis().temp_size_in_bytes < 0.2e9
    assert _peak_bytes(select) < 1.3e9


def test_the_sparse_attention_forward_kernel_runs_once_a_layer_in_a_step_on_v5e(one_chip):
    """A two-layer stack's value and gradients in training (``nn.remat`` a
    layer, keeping the attention's output and log-sum-exps) at the kernels'
    widths and a short length: ``dsa_fwd`` once a layer, not a second time
    in the backward pass, and the two backward kernels once a layer, all
    under ``dsa.attn``."""
    from rafiki_tpu.models import keye_vl2 as M

    cfg = dict(hidden_size=256, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
               rope_theta=1e7, indexer_num_heads=2, indexer_head_dim=64, topk=256,
               moe_intermediate_size=64, num_experts=4, num_experts_per_tok=2,
               num_hidden_layers=2, rms_norm_eps=1e-6, experts_held=(0, 1, 2, 3))
    module = M._KeyeVl2(cfg=tuple(sorted(cfg.items())), vocab=512)
    x = _spec((1, 1024), jnp.int32, one_chip)
    params = _on(one_chip, jax.eval_shape(lambda x: module.init(jax.random.PRNGKey(0), x), x))

    def loss(params, x):
        h, _head, _loads, extra = module.apply(params, x, train=True, hidden=True)
        return jnp.sum(h.astype(jnp.float32)) + jnp.sum(extra["kl"])

    text = jax.jit(jax.value_and_grad(loss)).lower(params, x).compile().as_text()
    kernels = _kernels(text, "dsa_")
    names = [name.split(".")[0] for name, _op in kernels]
    assert {n: names.count(n) for n in set(names)} == {"dsa_fwd": 2, "dsa_dq": 2, "dsa_dkv": 2}
    assert all("dsa.attn" in op for _name, op in kernels), kernels
