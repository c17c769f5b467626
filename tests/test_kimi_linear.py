"""The language-model template (rafiki_tpu/models/kimi_linear.py) against
its plain reference (benchmark/references/kimi_linear.py) at a small size
on seeded weights, and through the normal path: scheduler -> serial lane ->
train / evaluate / dump -> ParamsStore -> a fresh instance.

Where a comparison is of the arithmetic (chunked against recurrent, sorted
ragged dispatch against a loop over experts, blocked loss against whole
logits) the template's matrix products are switched to float32 so that the
two must agree closely; one test keeps bfloat16 and asks for closeness."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO / "benchmark"), str(REPO / "benchmark" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
from lm_tiny import load_lm_cfg, template_knobs, tiny_lm  # noqa: E402
from references import kimi_linear as R  # noqa: E402

from rafiki_tpu import telemetry  # noqa: E402
from rafiki_tpu.model.dataset import dataset_utils  # noqa: E402
from rafiki_tpu.model.knobs import FixedKnob  # noqa: E402
from rafiki_tpu.models import kimi_linear as K  # noqa: E402

TRAIN = "synthetic://tokens?vocab=256&n=8&len=96&seed=20&follow=0.5"
VAL = "synthetic://tokens?vocab=256&n=4&len=96&seed=21&follow=0.5"


def small_class(cfg, seed=0):
    pinned = {k: v["fixed"] for k, v in cfg["knobs"].items() if "fixed" in v}
    pinned["seed"] = seed

    class Small(K.KimiLinear):
        @staticmethod
        def get_knob_config():
            base = K.KimiLinear.get_knob_config()
            return {k: (FixedKnob(pinned[k], affects_shape=True)
                        if k in pinned and isinstance(base[k], FixedKnob) else base[k])
                    for k in base}

    return Small


@pytest.fixture(params=[16, 64], ids=["chunk16", "chunk64_ragged"])
def cfg(request):
    return tiny_lm(load_lm_cfg(), chunk=request.param)


@pytest.fixture
def f32(monkeypatch):
    """The template's matrix products in float32 at full precision."""
    def mm(a, b, spec, out=jnp.float32):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision="highest")

    monkeypatch.setattr(K, "_mm", mm)
    monkeypatch.setattr(K, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def program_of(cfg, seed=3, **free):
    model = small_class(cfg, seed)(**template_knobs(cfg, seed=seed, **free))
    model._planned_steps = 4
    fns = model._loop_fns(int(cfg["vocab_size"]), (int(cfg["seq_len"]),))
    _step, init_key = check.trial_keys(seed)
    return model, fns, fns["init_fn"](init_key), R.init(init_key, cfg)


def flat(params):
    from flax.traverse_util import flatten_dict

    return {"/".join(k): v for k, v in flatten_dict(params).items()}


def tokens(cfg, n=2, seed=5):
    ds = dataset_utils.load(f"synthetic://tokens?vocab={cfg['vocab_size']}&n={n}"
                            f"&len={cfg['seq_len']}&seed={seed}")
    return jnp.asarray(ds.x), jnp.asarray(ds.y)


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def test_reference_starts_from_the_programs_initial_parameters(cfg):
    _m, _fns, params, ref = program_of(cfg)
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]), err_msg=k)
    assert R.parameters(cfg) == sum(v.size for v in got.values())


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_equals_the_recurrence(chunk, f32):
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    B, T, H, d = 2, 96, 4, 16
    q = K.l2norm(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = K.l2norm(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    a = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    want = R.delta_rule(q, k, v, a, beta)
    assert close(K.kda_chunked(q, k, v, a, beta, chunk), want, 1e-5)
    assert close(R.delta_rule(q[:, :128], k[:, :128], v[:, :128], a[:, :128],
                              beta[:, :128], fit=True), want[:, :128], 1e-6)
    g = jax.grad(lambda a_: K.kda_chunked(q, k, v, a_, beta, chunk).sum())(a)
    assert close(g, jax.grad(lambda a_: R.delta_rule(q, k, v, a_, beta).sum())(a), 1e-4)


def test_unit_lower_inverse_inverts():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 16, 16)), -1)
    eye = jnp.eye(16)
    assert close(jnp.matmul(K.unit_lower_inverse(A), A + eye, precision="highest"),
                 jnp.broadcast_to(eye, A.shape), 1e-4)


@pytest.mark.parametrize("mixer", ["kda", "mla"])
def test_each_mixer_matches_the_reference(cfg, mixer, f32):
    _m, fns, params, ref = program_of(cfg)
    layer = 2 if mixer == "kda" else 4
    x = jax.random.normal(jax.random.PRNGKey(1), (2, int(cfg["seq_len"]), 64))
    c = dict(fns["module"].cfg)
    if mixer == "kda":
        mod = K._Kda(c["num_heads"], c["kda_head_dim"], c["short_conv_kernel_size"],
                     c["kda_chunk"], c["rms_norm_eps"])
        want = R.kda(ref, f"layer_{layer}", x, cfg)
    else:
        mod = K._Mla(c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"])
        want = R.mla(ref, f"layer_{layer}", x, cfg)
        assert close(R.mla(ref, f"layer_{layer}", x, cfg, q_block=32), want, 1e-6)
    got = mod.apply({"params": params[f"layer_{layer}"][mixer]}, x)
    if mixer == "mla":
        got, fused = got
        assert float(fused) == 0.0         # 96 tokens: no kernel block divides them
    assert close(got, want, 2e-5)


def mla_operands(T, dtype=jnp.float32, B=1, H=2):
    """What is particular to MLA: queries and keys 192 wide, values 128."""
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k = (jax.random.normal(ks[i], (B, T, H, 192)).astype(dtype) for i in (0, 1))
    v = jax.random.normal(ks[2], (B, T, H, 128)).astype(dtype)
    return q, k, v, jax.random.normal(ks[3], (B, T, H, 128))


def value_and_grads(fn, q, k, v, ct):
    """fn's result and its three gradients under the cotangent ``ct``."""
    out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v).astype(jnp.float32), q, k, v)
    return (out,) + vjp(ct)


@pytest.fixture
def interpreted():
    """Pallas' interpreter ran in this test. With what it leaves in jax's
    caches, a later test of this file (an eager ``lax.scan`` under the
    ``f32`` fixture) died of a segmentation fault in this jax (0.9.0), every
    time; with the caches cleared it does not."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("against", ["whole_row_softmax", "blocked_path"])
def test_the_fused_kernel_matches(against, f32, monkeypatch, interpreted):
    """The kernel path (Pallas in interpret mode on the CPU) at a length of
    four of its blocks, float32 operands: value and all three gradients
    against the reference's whole-row softmax and against the blocked path."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = mla_operands(4 * 128)
    other = R.attention if against == "whole_row_softmax" else K._blocked_attention
    got = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    for name, a, b in zip(("value", "dq", "dk", "dv"), got,
                          value_and_grads(other, q, k, v, ct)):
        assert a.shape == b.shape and close(a, b, 2e-5), name


def test_the_fused_kernel_in_bfloat16_is_as_near_the_reference_as_the_blocked_path(monkeypatch, interpreted):
    """The contract both paths share: bfloat16 operands and result, float32
    products and softmax. What the kernel changes (q rounded after its
    scaling, an online softmax) must cost no more than bfloat16 itself."""
    monkeypatch.setattr(K, "KERNEL_BLOCK", 128)
    q, k, v, ct = mla_operands(4 * 128, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(R.attention, *(x.astype(jnp.float32) for x in (q, k, v)), ct)
    fused = value_and_grads(lambda *a: K._fused_attention(*a, interpret=True), q, k, v, ct)
    blocked = value_and_grads(K._blocked_attention, q, k, v, ct)
    assert K._fused_attention(q, k, v, interpret=True).dtype == jnp.bfloat16

    def gap(x, w):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32) - w)) / jnp.max(jnp.abs(w)))

    for name, a, b, w in zip(("value", "dq", "dk", "dv"), fused, blocked, want):
        assert a.dtype == b.dtype
        assert gap(b, w) < 0.02 and gap(a, w) < max(1.5 * gap(b, w), 0.01), (name, gap(a, w), gap(b, w))


@pytest.mark.parametrize("T", [4 * K.KERNEL_BLOCK, K.KERNEL_BLOCK + 96],
                         ids=["a_length_of_four_blocks", "a_length_no_block_divides"])
def test_which_attention_runs_is_read_from_the_length_and_the_lowering(T):
    """A length the kernel's block divides: both paths are staged, and the
    platform the program is lowered for takes its own (here the CPU: the
    blocked code, flag 0; ``tests/test_chip_compile.py`` lowers the same
    call for a described TPU). Any other length: the blocked code alone."""
    q, k, v, _ct = mla_operands(T, jnp.bfloat16)
    staged = str(jax.make_jaxpr(K.mla_attention)(q, k, v))
    assert ("platform_index" in staged) == ("pallas_call" in staged) == (T % K.KERNEL_BLOCK == 0)
    lowered = jax.jit(K.mla_attention).lower(q, k, v).as_text()
    assert "tpu_custom_call" not in lowered
    got, fused = jax.jit(K.mla_attention)(q, k, v)
    assert float(fused) == 0.0 and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(K._blocked_attention(q, k, v), np.float32))


def test_the_kernel_is_the_same_text_whatever_model_file_holds_it():
    """A tenant's model file is loaded as a module whose name differs from
    process to process, and the benchmark's differs from seed to seed. jax
    writes the file names of the traceback into a Pallas kernel's serialized
    body, which the persistent compile cache hashes: were the code's file
    name the module's, every process would build the step program anew
    (110 s on the chip). Lowered for a TPU from two such files, the
    attention is one text."""
    from drivers import sweep as sweep_driver
    from rafiki_tpu.model.base import load_model_class

    q, k, v, _ct = mla_operands(K.KERNEL_BLOCK, jnp.bfloat16)
    texts, modules = [], []
    for seed in (1, 2):
        cls = load_model_class(sweep_driver.model_source(REPO, load_lm_cfg(), seed), "BenchModel")
        modules.append(cls.__module__)
        attention = sys.modules[cls.__module__].mla_attention
        texts.append(jax.jit(attention).trace(q, k, v).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True))
    assert modules[0] != modules[1]
    assert "tpu_custom_call" in texts[0] and "rafiki_model.py" in texts[0]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("seq_len", [96, K.KERNEL_BLOCK])
def test_a_step_counts_its_mla_layers_and_none_of_them_fused_on_the_cpu(seq_len):
    cfg = tiny_lm(load_lm_cfg(), seq_len=seq_len)
    _model, fns, params, _ref = program_of(cfg)
    x, y = tokens(cfg)
    _loss, metrics = jax.jit(fns["loss_fn"])(params, {"x": x, "y": y}, None,
                                             {"label_smoothing": jnp.float32(0.0)})
    assert float(metrics["count.mla.layers"]) == 1.0    # layers: dense, KDA, KDA, MLA, KDA
    assert float(metrics["count.mla.fused"]) == 0.0


def test_expert_layer_matches_the_reference_and_counts_its_rows(cfg, f32):
    _m, fns, params, ref = program_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, int(cfg["seq_len"]), 64))
    c = dict(fns["module"].cfg)
    mod = K._Moe(c["num_experts"], c["num_experts_per_token"], tuple(c["experts_held"]),
                 c["moe_intermediate_size"], c["routed_scaling_factor"])
    got, load = mod.apply({"params": params["layer_3"]["moe"]}, x)
    want = (R.routed_part(ref, "layer_3", x, cfg, R.dims(cfg)["held"])
            + R.shared_part(ref, "layer_3", x))
    assert close(got, want, 2e-5)
    ids, _w = R.router(ref, "layer_3", x, cfg)
    assert [int(v) for v in load] == [int((ids == e).sum()) for e in range(4)]


def test_the_shares_add_up_to_the_uncut_layer(cfg, f32):
    """Every chip's routed part (its 4 of the 16 experts, by the program's
    layer, told which it holds) plus the shared expert once is the uncut
    reference's layer with all 16."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2 * int(cfg["seq_len"]), 64))
    full = dict(cfg, experts_held=list(range(16)), num_experts=16)
    ref = R.init(key, full)
    L = "layer_2"
    want = (R.routed_part(ref, L, x, full, list(range(16))) + R.shared_part(ref, L, x))
    ids, w = K.route(x, ref[f"{L}/moe/w_router"], ref[f"{L}/moe/router_bias"], 4, 2.446)
    total = R.shared_part(ref, L, x)
    rows = 0
    for shard in range(4):
        held = tuple(range(4 * shard, 4 * shard + 4))
        part, load = K.expert_layer(
            x, ids, w, held, *(ref[f"{L}/moe/{n}"][4 * shard: 4 * shard + 4]
                               for n in ("w_gate", "w_up", "w_down")))
        total, rows = total + part, rows + int(load.sum())
    assert rows == x.shape[0] * 4          # every slot landed on one chip
    assert close(total, want, 2e-5)


def test_logits_loss_counts_and_every_gradient_leaf(cfg, f32):
    model, fns, params, ref = program_of(cfg, label_smoothing=0.07)
    x, y = tokens(cfg)
    module = fns["module"]
    h, head, _loads, _fused = module.apply({"params": params}, x, hidden=True)
    logits = R.forward(ref, x, cfg)
    assert close(jnp.einsum("btd,dv->btv", h, head, precision="highest"), logits, 5e-5)
    assert close(module.apply({"params": params}, x), logits[:, -1], 5e-5)
    hyper = {"label_smoothing": jnp.float32(0.07)}
    batch = {"x": x, "y": y}
    (loss, metrics), grads = jax.value_and_grad(fns["loss_fn"], has_aux=True)(
        params, batch, None, hyper)
    want, want_g = jax.value_and_grad(R.loss)(ref, x, y, cfg, 0.07)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32, seq_block=1), want, 1e-6)
    R.SEGMENT, segment = 4, R.SEGMENT        # the fitting cuts at this size too
    try:
        assert close(R.loss(ref, x, y, cfg, 0.07, fit=True, q_block=32, seq_block=1), want, 1e-6)
        assert int(R.stats(ref, x, y, cfg, fit=True)[1]) == int(R.stats(ref, x, y, cfg)[1])
    finally:
        R.SEGMENT = segment
    _ce, hits, n = R.stats(ref, x, y, cfg)
    got_hits, got_n = fns["eval_count"](params, batch)
    assert (int(got_hits), int(got_n)) == (int(hits), int(n))
    assert abs(float(metrics["acc"]) - int(hits) / int(n)) < 1e-6
    got_g = flat(grads)
    scale = max(float(jnp.max(jnp.abs(v))) for v in want_g.values())
    for k, g in want_g.items():
        if k.endswith("router_bias"):
            continue  # held at zero: no gradient reaches it
        assert close(got_g[k], g, 2e-4) or \
            float(jnp.max(jnp.abs(got_g[k] - g))) < 1e-6 * scale, k
    assert float(jnp.max(jnp.abs(got_g["layer_2/moe/router_bias"]))) == 0.0
    assert float(metrics["count.moe.slots_total"]) == x.size * 4 * 4
    assert 0 < float(metrics["count.moe.slots_held"]) < x.size * 4 * 4


def test_bfloat16_program_is_near_the_reference(cfg):
    _model, fns, params, ref = program_of(cfg)
    x, y = tokens(cfg)
    loss, _ = fns["loss_fn"](params, {"x": x, "y": y}, None,
                             {"label_smoothing": jnp.float32(0.0)})
    with jax.default_matmul_precision("highest"):
        want = R.loss(ref, x, y, cfg)
    assert abs(float(loss) - float(want)) < 5e-3 * float(want)


def test_forward_flops_count_the_layers_and_parameters_at_the_published_widths():
    cfg = load_lm_cfg()
    assert R.parameters(cfg) == 602_434_432
    per_token = R.forward_flops(cfg)
    # 2 x the parameters a token meets (the routed experts at their expected
    # share, the embedding's rows not multiplied) + attention over the keys
    d = R.dims(cfg)
    expert = 3 * d["D"] * d["moe"]
    met = (R.parameters(cfg) - d["vocab"] * d["D"]
           - 4 * (len(d["held"]) - d["top_k"] * len(d["held"]) / d["experts"]) * expert)
    attention = (8192 + 1) / 2 * d["H"] * (d["nope"] + d["rope"] + d["dv"])
    recurrence = 4 * 3 * d["Hk"] * d["dk"] * d["dk"]  # decay-free: S k, k u^T, S q
    assert abs(per_token - 2 * (met + attention + recurrence)) < 0.001 * per_token
    assert 36e12 < 3 * 16384 * per_token < 39e12
    assert R.forward_flops(cfg, seq_len=4096) < per_token


def test_label_smoothing_and_learning_rate_share_one_program(cfg):
    a, fa, _p, _r = program_of(cfg, label_smoothing=0.0, learning_rate=1e-4)
    b, fb, _p, _r = program_of(cfg, label_smoothing=0.1, learning_rate=1e-3)
    assert fa["program_key"] == fb["program_key"]
    assert fa["hyper"]["label_smoothing"] == 0.0 and fb["hyper"]["label_smoothing"] == 0.1
    assert not small_class(cfg).packable()


def test_synthetic_tokens_is_seeded_and_next_token_labelled():
    import lm_datagen

    ds = dataset_utils.load(TRAIN)
    assert ds.x.shape == ds.y.shape == (8, 96) and ds.classes == 256 and ds.mask is None
    np.testing.assert_array_equal(ds.x[:, 1:], ds.y[:, :-1])
    x, y = lm_datagen.synthetic_tokens(256, 8, 96, 20, 0.5)
    np.testing.assert_array_equal(x, ds.x)
    np.testing.assert_array_equal(y, ds.y)
    other = dataset_utils.load(VAL)
    assert not np.array_equal(other.x[:4], ds.x[:4])
    assert lm_datagen.token_uri({"vocab_size": 256, "seq_len": 96, "follow": 0.5}, 8, 20) == TRAIN


def test_a_trial_trains_scores_counts_and_reloads(cfg):
    """train -> evaluate -> staged dump -> a fresh instance gives the score;
    the epoch is a leaf span with its tags; the expert counts land in
    counters; a staged dump is the unstaged blob byte for byte."""
    telemetry.reset()
    Small = small_class(cfg, 11)
    knobs = template_knobs(cfg, seed=11)
    model = Small(**knobs)
    model.train(TRAIN)
    score = model.evaluate(VAL)
    spans = [s for s in telemetry.span_records() if s["name"] == "train.epoch"]
    assert len(spans) == 1 and spans[0]["leaf"]
    assert spans[0]["tags"]["steps"] == 4 and spans[0]["tags"]["cold"] in (True, False)
    counters = telemetry.snapshot()["counters"]
    assert counters["moe.slots_total"] == 4 * 2 * 96 * 4 * 4
    assert 0 < counters["moe.slots_held"] < counters["moe.slots_total"]
    assert telemetry.get_gauge("moe.held_load_max_over_mean") >= 1.0
    assert (counters["mla.layers"], counters["mla.fused"]) == (4, 0)   # a step each; the CPU
    blob = model.dump_parameters()
    assert counters.get("persist.blob_bytes", 0) == 0 < telemetry.get_counter("persist.blob_bytes")
    model.release_train_state()            # the CPU reports no limit: nothing staged
    assert model._loop.state is not None and model._loop.host_copy is None
    model._loop.release_to_host(True)
    assert model._loop.state is None
    assert model.dump_parameters() == blob
    assert telemetry.get_counter("persist.serial_from_staged_copy") == 1
    fresh = Small(**knobs)
    fresh.load_parameters(blob)
    assert fresh.evaluate(VAL) == pytest.approx(score, abs=0.006)
    probs = np.asarray(fresh.predict([[5, 9, 3] * 32]))
    assert probs.shape == (1, 256) and abs(probs.sum() - 1.0) < 1e-3


def test_a_sweep_through_the_scheduler_stores_what_reproduces_the_score(cfg, tmp_path):
    from drivers import sweep as sweep_driver
    from rafiki_tpu.config import Config, set_config
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore

    set_config(Config(data_dir=tmp_path / "data").ensure_dirs())
    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    source = sweep_driver.model_source(REPO, cfg, 17)
    model = store.create_model("BenchModel", "LANGUAGE_MODELING", None, source, "BenchModel")
    job = store.create_train_job("lm", "LANGUAGE_MODELING", None, TRAIN, VAL,
                                 {"MODEL_TRIAL_COUNT": 2})
    store.create_sub_train_job(job["id"], model["id"])
    before = telemetry.get_counter("worker.packed_trials")
    result = LocalScheduler(store, params).run_train_job(
        job["id"], n_workers=1, advisor_kind="gp", trial_pack=1)
    assert result.status == "COMPLETED" and not result.errors
    assert telemetry.get_counter("worker.packed_trials") == before
    done = [t for t in result.trials if t["status"] == "COMPLETED"]
    assert len(done) == 2
    cls = load_model_class(source, "BenchModel")
    for t in done:
        assert 3e-5 <= t["knobs"]["learning_rate"] <= 1e-3
        fresh = cls(**t["knobs"])
        fresh.load_parameters(params.load(t["params_id"]))
        # (stored in bfloat16: a near-tie among 384 scored tokens may flip)
        assert fresh.evaluate(VAL) == pytest.approx(t["score"], abs=0.006)
    store.close()


def test_template_is_registered_and_passes_the_contract_harness():
    from rafiki_tpu.constants import TaskType
    from rafiki_tpu.model.dev import test_model_class
    from rafiki_tpu.models import get_model_class

    cls = get_model_class("KimiLinear")
    fixed = {k: v.value for k, v in cls.get_knob_config().items()
             if isinstance(v, FixedKnob)}
    score, preds = test_model_class(
        cls, TaskType.LANGUAGE_MODELING.value, TRAIN, VAL, queries=[[5, 9, 3] * 8],
        knobs=dict(fixed, learning_rate=1e-3, label_smoothing=0.05))
    assert 0.0 <= score <= 1.0 and len(preds[0]) == 256


def test_rows_past_a_ragged_products_groups_reach_neither_values_nor_gradients(cfg, f32, monkeypatch):
    """On the TPU a ragged product leaves the rows past its groups as they
    were in memory, in its result and in its left operand's gradient (the
    CPU zero-fills both). Planted here as NaN: the expert layer's result and
    every gradient must stay what they are."""
    real = jax.lax.ragged_dot

    def dead(x, sizes):
        return (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(dead(lhs, sizes), jnp.nan, real(lhs, rhs, sizes,
                                                         preferred_element_type=jnp.float32))

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        _y, vjp = jax.vjp(lambda a, b: real(a, b, sizes, preferred_element_type=jnp.float32),
                          lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(dead(lhs, sizes), 0.0, ct))
        return jnp.where(dead(lhs, sizes), jnp.nan, d_lhs).astype(lhs.dtype), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    _m, fns, params, ref = program_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (192, 64))
    ids, w = K.route(x, ref["layer_2/moe/w_router"], ref["layer_2/moe/router_bias"], 4, 2.446)
    ws = [ref[f"layer_2/moe/{n}"] for n in ("w_gate", "w_up", "w_down")]

    def total(x, w, *ws):
        return jnp.sum(K.expert_layer(x, ids, w, (0, 1, 2, 3), *ws)[0] ** 2)

    want = jax.grad(total, argnums=(0, 1, 2, 3, 4))(x, w, *ws)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, group_sizes, preferred_element_type=None:
                        poisoned(lhs, rhs, group_sizes))
    got = jax.grad(total, argnums=(0, 1, 2, 3, 4))(x, w, *ws)
    assert np.isfinite(float(total(x, w, *ws)))
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a))) and close(a, b, 1e-5)
