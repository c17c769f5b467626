"""Looped dense language-model template: one stack of layers run several
times with shared weights, a loss at every pass weighted by a learned exit
gate.

No reference analog. The block is the one published for Ouro-2.6B
(``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): a dense decoder whose every layer is full
attention and a gated feed-forward part, each between two RMSNorms (the
"sandwich"),

    h = h + N2(attn(N1(h)));  h = h + N4(ffn(N3(h))),

and whose whole stack is a loop: ``h_0 = E[x]``; for t = 1..R:
``h_t = N_f(layers(h_(t-1)))`` with the same parameters at every t. The
normed ``h_t`` goes into pass t + 1, into the head (``z_t = W_head h_t``)
and into the exit gate, one ``Linear(D -> 1)`` shared by all passes:
``lambda_t = sigmoid(w_g . h_t + b_g)`` per token. A token's exit
distribution is ``p_t = lambda_t prod_(j<t) (1 - lambda_j)`` for t < R and
``p_R = prod_(j<R) (1 - lambda_j)``; the training objective is the mean over
tokens of ``sum_t p_t CE_t - beta H(p)`` (the paper's first-stage one: every
pass's loss in every step, the entropy term keeping the gate from
collapsing onto one pass). A trial's score is the accuracy of ``z_R`` (no
token leaves early).

What the template adds to the zoo, by mechanism:

* the loop is in the program ONCE: ``nn.scan`` over the passes with the
  parameters broadcast, so a step program holds the stack once however many
  passes run, a layer's gradient is the sum over its visits (the scan's
  transpose adds them), and recomputation stays per layer visit
  (``nn.remat`` a layer inside the scan: what lives through the backward
  pass is each visit's input, R x layers of them, kept in bfloat16).
* attention with as many key/value heads as query heads, rotary positions
  (``lfm2_moe.rope``, the same positions at every pass) and no per-head
  norm, through ``kimi_linear.mla_attention``: the fused kernel where the
  program is lowered for a TPU at a length its block divides, the blocked
  ``jax.numpy`` code elsewhere. ``count.attn.fused`` of ``count.attn.layers``
  (layer visits: R x layers a step) says which ran.
* the objective takes every pass's cross entropy *per token*
  (``kimi_linear.blocked_logit_stats(..., per_token=True)``: a block of the
  sequence at a time, never a whole ``[tokens, vocab]`` array, R times a
  step under one ``lax.map``), because the weight ``p_t`` has a gradient of
  its own, which reaches the gate and, through ``h_t``, the layers.

TPU notes as ``kimi_linear.py``'s: bfloat16 operands and float32
accumulation in matrix products; parameters, the residual stream, the norms,
rotations, the gate and the softmaxes in float32 (the stream too: it is
normed four times a layer and carried through R x layers visits). No
environment variable or knob chooses a path. A trial at the published widths
fills a chip by itself; what it shares of the ``JaxModel`` contract with the
other language-model templates is ``kimi_linear.BlockedLossLm``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models import kimi_linear as K
from rafiki_tpu.models.lfm2_moe import rope

F32 = jnp.float32

# Named scopes of the block (docs/telemetry.md), beside ``kimi_linear.py``'s
# ``lm.loss``, which covers all R heads and the objective.
SCOPE_ATTN, SCOPE_FFN, SCOPE_GATE = "ouro.attn", "lm.ffn", "ouro.gate"


class _Attn(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    theta: float

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        H, Hkv, d = self.heads, self.kv_heads, self.head_dim
        p = lambda name, shape: self.param(name, K._dense_init(), shape)
        q = K._mm(x, p("w_q", (D, H * d)), "btd,de->bte").reshape(B, T, H, d)
        k = K._mm(x, p("w_k", (D, Hkv * d)), "btd,de->bte").reshape(B, T, Hkv, d)
        v = K._mm(x, p("w_v", (D, Hkv * d)), "btd,de->bte", K.BF16).reshape(B, T, Hkv, d)
        o, fused = K.mla_attention(rope(q, self.theta).astype(K.BF16),
                                   rope(k, self.theta).astype(K.BF16), v)
        return K._mm(o.reshape(B, T, H * d), p("w_o", (H * d, D)), "bte,ed->btd"), fused


class _Layer(nn.Module):
    """One visit of one layer, as what it adds to the stream: the stream
    as the layer reads it [B, T, D], rounded to bfloat16 -> (N2(attn(N1(h))) +
    N4(ffn(N3(h + the first)))) in float32, 1.0 where the fused kernel computed
    the attention). The caller adds it to the float32 stream it keeps, so the
    stream itself is never rounded, and what ``nn.remat`` keeps of a visit
    for the backward pass is the bfloat16 copy: half the bytes (a float32
    copy of each of R x layers inputs did not fit beside the state: PERF.md,
    PR 33). The rounding is of what the norms read, and their results are
    rounded to bfloat16 for the products anyway."""

    cfg: Any            # a hashable tuple of (key, value) pairs

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        eps = c["norm_eps"]
        norm = lambda name: self.param(name, nn.initializers.ones, (x.shape[-1],))
        h = x.astype(F32)
        with jax.named_scope(SCOPE_ATTN):
            m, fused = _Attn(c["num_attention_heads"], c["num_key_value_heads"],
                             c["head_dim"], c["rope_theta"], name="attn")(
                K.rms_norm(h, norm("norm_attn"), eps))
            a = K.rms_norm(m, norm("norm_attn_out"), eps)
        with jax.named_scope(SCOPE_FFN):
            y = K._Dense(c["intermediate_size"], name="ffn")(
                K.rms_norm(h + a, norm("norm_ffn"), eps))
            return a + K.rms_norm(y, norm("norm_ffn_out"), eps), fused


def _close_pass(h, scale, gate_w, gate_b, eps: float):
    """The end of a pass: the stack's output -> (``N_f`` of it, in float32:
    the next pass's input; the same in bfloat16: the head's; the exit gate's
    logit of every token [B, T])."""
    h = K.rms_norm(h, scale, eps)
    with jax.named_scope(SCOPE_GATE):
        gate = jnp.einsum("btd,d->bt", h, gate_w, precision=jax.lax.Precision.HIGHEST) + gate_b
    return h, h.astype(K.BF16), gate


class _Ouro(nn.Module):
    """x [B, T] token ids -> the next token's logits after the last one
    given, from the last pass [B, V]; with ``hidden``, (every pass's hidden
    states after the final norm [R, B, T, D] in bfloat16, the untied head
    [D, V], the exit gate's logit of every pass and token [R, B, T] in
    float32, the layer visits the fused kernel computed)."""

    cfg: Any
    vocab: int

    @nn.compact
    def __call__(self, x, train: bool = False, hidden: bool = False):
        c = dict(self.cfg)
        D, eps = c["hidden_size"], c["norm_eps"]
        embed = self.param("embed", K._dense_init(), (self.vocab, D))
        head = self.param("head", K._dense_init(), (D, self.vocab))
        layer = nn.remat(_Layer) if train else _Layer

        def one_pass(mdl, h, _):
            fused = jnp.float32(0.0)
            for i in range(c["num_hidden_layers"]):
                added, kernel = layer(self.cfg, name=f"layer_{i + 1}")(h.astype(K.BF16))
                h, fused = h + added, fused + kernel
            # (recomputed in the backward pass from the stack's output alone)
            h, for_head, gate = jax.checkpoint(_close_pass, static_argnums=4)(
                h, mdl.param("norm_out", nn.initializers.ones, (D,)),
                mdl.param("gate_w", K._dense_init(), (D,)),
                mdl.param("gate_b", nn.initializers.zeros, ()), eps)
            return h, (for_head, gate, fused)

        loop = nn.scan(one_pass, variable_broadcast="params", split_rngs={"params": False},
                       length=c["total_ut_steps"])
        _h, (hs, gates, fused) = loop(self, jnp.take(embed, x, axis=0).astype(F32), None)
        if hidden:
            return hs, head, gates, fused.sum()
        # Serving: the next token's distribution after the last one given.
        return K._mm(hs[-1][:, -1], head, "bd,dv->bv")


def exit_distribution(gates):
    """The exit gate's logits [R, ...] -> (p [R, ...], log p): ``p_t =
    lambda_t prod_(j<t) (1 - lambda_j)`` for t < R and ``p_R = prod_(j<R)
    (1 - lambda_j)``, taken in logarithms (``log lambda = -softplus(-g)``,
    ``log (1 - lambda) = -softplus(g)``) so that a saturated gate gives a
    small p and not 0 x log 0. The last pass's own gate enters nothing."""
    stay = -jax.nn.softplus(gates[:-1])
    before = jnp.concatenate([jnp.zeros_like(gates[:1]), jnp.cumsum(stay, axis=0)])
    logp = before + jnp.concatenate([-jax.nn.softplus(-gates[:-1]),
                                     jnp.zeros_like(gates[:1])])
    return jnp.exp(logp), logp


class Ouro(K.BlockedLossLm):
    """The template. Shape knobs default to a size a CPU trains in seconds;
    a tenant's model file pins them (the benchmark's configuration pins the
    published widths). ``num_hidden_layers`` layers are held and visited
    ``total_ut_steps`` times a token; ``exit_entropy_beta`` weighs the
    entropy of the exit distribution in the objective."""

    @staticmethod
    def get_knob_config():
        fixed = lambda v: FixedKnob(v, affects_shape=True)
        return {
            "hidden_size": fixed(64), "num_attention_heads": fixed(4),
            "num_key_value_heads": fixed(4), "head_dim": fixed(16),
            "intermediate_size": fixed(128), "num_hidden_layers": fixed(2),
            "total_ut_steps": fixed(4), "rope_theta": fixed(1e6),
            "norm_eps": fixed(1e-6), "exit_entropy_beta": fixed(0.05),
            "learning_rate": FloatKnob(3e-5, 1e-3, is_exp=True),
            "label_smoothing": FloatKnob(0.0, 0.1),
            "batch_size": fixed(2), "epochs": FixedKnob(1), "seed": FixedKnob(0),
        }

    def module_config(self) -> tuple:
        return tuple(sorted((k, self.knobs[k]) for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "num_hidden_layers", "total_ut_steps", "rope_theta",
            "norm_eps")))

    def build_module(self, num_classes, input_shape):
        return _Ouro(cfg=self.module_config(), vocab=int(num_classes))

    def _loss_and_count(self, module):
        c = dict(module.cfg)
        passes, layers = int(c["total_ut_steps"]), int(c["num_hidden_layers"])
        beta = float(self.knobs["exit_entropy_beta"])

        def loss_fn(params, batch, rng, hyper):
            hs, head, gates, fused = module.apply({"params": params}, batch["x"],
                                                  train=True, hidden=True)
            with jax.named_scope(K.SCOPE_LM_LOSS):
                ce, hit, mask = jax.lax.map(lambda h: K.blocked_logit_stats(
                    h, head, batch["y"], hyper["label_smoothing"], per_token=True), hs)
                p, logp = exit_distribution(gates)
                mask = mask[-1]
                n = jnp.maximum(mask.sum(), 1)
                token = jnp.sum(p * ce, axis=0) + beta * jnp.sum(p * logp, axis=0)
                loss = jnp.where(mask, token, 0.0).sum() / n
            mean = lambda v: jnp.where(mask, v, 0.0).sum() / n
            return loss, {
                "acc": (hit[-1] & mask).sum() / n,
                "count.loop.passes": jnp.float32(passes),
                "count.loop.layer_calls": jnp.float32(passes * layers),
                "count.attn.layers": jnp.float32(passes * layers),
                "count.attn.fused": fused,
                "gauge.loop.expected_passes": mean(jnp.tensordot(
                    jnp.arange(1, p.shape[0] + 1, dtype=F32), p, axes=1)),
                "gauge.loop.last_pass_mass": mean(p[-1])}

        def eval_count(params, batch):
            hs, head, _gates, _fused = module.apply({"params": params}, batch["x"],
                                                    train=False, hidden=True)
            with jax.named_scope(K.SCOPE_LM_LOSS):
                _ce, hits, n = K.blocked_logit_stats(hs[-1], head, batch["y"], 0.0)
            return hits, n

        return loss_fn, eval_count


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.ouro`): an explicit CPU
    # request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    _fixed = {k: v.value for k, v in Ouro.get_knob_config().items()
              if isinstance(v, FixedKnob)}
    test_model_class(
        Ouro, "LANGUAGE_MODELING",
        "synthetic://tokens?vocab=256&n=16&len=96&seed=0",
        "synthetic://tokens?vocab=256&n=4&len=96&seed=1",
        queries=[[5, 9, 3] * 8, [17, 2] * 12],
        knobs=dict(_fixed, learning_rate=1e-3, label_smoothing=0.05),
    )
