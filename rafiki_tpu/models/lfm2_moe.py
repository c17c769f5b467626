"""Hybrid short-convolution / grouped-query-attention sparse-expert language
model template.

No reference analog. The block is the one published for LFM2-8B-A1B
(``model_type`` ``lfm2_moe``): pre-norm residual layers,
``h = h + op(RMSNorm(h)); h = h + ffn(RMSNorm(h))``, whose operator is
either a *gated short convolution* or *grouped-query attention* with rotary
positions, and whose feed-forward part is dense in the leading layers and a
sigmoid top-k router over sparse experts, without a shared one, in the rest;
after the last layer one RMSNorm, then logits by the embedding's transpose.

What the template adds to the zoo, by mechanism:

* ``_Conv``: ``[B, C, u] = W_in x``; ``y = C * causal_conv(B * u)`` over
  ``taps`` tokens, depthwise, no bias; ``W_out y``.
* ``_Gqa``: fewer key/value heads than query heads; queries and keys each
  RMS-normed per head with a learned scale, then rotated (``rope``:
  rotate-half over the whole head, positions 0..T-1); causal softmax
  attention through ``kimi_linear.mla_attention``, which both templates
  call: lowered for a TPU at a length its block divides it is the fused
  kernel, which reads a key/value head once for the query heads it serves;
  elsewhere the blocked ``jax.numpy`` code on repeated heads.
  ``count.attn.fused`` of ``count.attn.layers`` says which ran.
* the router selects by score plus ``expert_bias`` and weights by the score
  alone (``kimi_linear.route``, the one router of both templates); the held
  experts' part is ``kimi_linear.expert_layer``, the one expert layer of
  both. ``expert_bias`` is a buffer of the published checkpoint that no
  loss trains: here a parameter drawn once at initialisation (uniform in
  +-``BIAS_RANGE``) that no gradient reaches, so Adam's update of it is
  nought and a trial stores it as it was drawn.
* a head that is the embedding's transpose over the sliced vocabulary,
  through ``kimi_linear.blocked_logit_stats``; the table's gradient is the
  sum of its two uses.

TPU notes as ``kimi_linear.py``'s: bfloat16 operands and float32
accumulation in matrix products; parameters, normalisations, rotations, the
router and the softmaxes in float32; every layer recomputed in the backward
pass (``nn.remat``). Which attention runs is decided when a program is
lowered, by the platform it is lowered for; no environment variable or knob
enters. A trial of this template at the published widths fills a chip by
itself and runs an epoch step by step through one executable built ahead of
time: what it shares of the ``JaxModel`` contract with the first template is
``kimi_linear.SparseExpertLm``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models import kimi_linear as K

F32 = jnp.float32
ROUTER_EPS = 1e-6      # added to the selected scores' sum (``norm_topk_prob``)
BIAS_RANGE = 0.05      # expert_bias ~ U(-0.05, 0.05) (assumed)

# Named scopes of the block (docs/telemetry.md), beside ``kimi_linear.py``'s
# ``moe.route``, ``moe.experts`` and ``lm.loss``, which this template shares.
SCOPE_CONV, SCOPE_ATTN = "lfm2.conv", "lfm2.attn"


def rope(x, theta: float):
    """Rotary positions 0..T-1 on ``x`` [B, T, H, d] in float32, the
    rotate-half convention over the whole head: channel i < d / 2 and
    channel i + d / 2 are one pair, turned by position x theta^(-2i/d)."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(angle)] * 2, -1), F32)[None, :, None, :]
                for f in (np.cos, np.sin))
    x = x.astype(F32)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


class _Conv(nn.Module):
    """The gated short convolution."""

    taps: int

    @nn.compact
    def __call__(self, x):
        D = x.shape[-1]
        p = lambda name, shape, init=K._dense_init(): self.param(name, init, shape)
        with jax.named_scope(SCOPE_CONV):
            bcu = K._mm(x, p("w_in", (D, 3 * D)), "btd,de->bte", K.BF16).astype(F32)
            b, c, u = jnp.split(bcu, 3, axis=-1)
            conv_w = p("conv", (self.taps, D), nn.initializers.normal(1.0 / np.sqrt(self.taps)))
            return K._mm(c * K.causal_conv(b * u, conv_w), p("w_out", (D, D)),
                         "btd,de->bte", K.BF16)


class _Gqa(nn.Module):
    heads: int
    kv_heads: int
    theta: float
    eps: float

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        H, Hkv, d = self.heads, self.kv_heads, x.shape[-1] // self.heads
        p = lambda name, shape, init=K._dense_init(): self.param(name, init, shape)
        with jax.named_scope(SCOPE_ATTN):
            q = K._mm(x, p("w_q", (D, H * d)), "btd,de->bte").reshape(B, T, H, d)
            k = K._mm(x, p("w_k", (D, Hkv * d)), "btd,de->bte").reshape(B, T, Hkv, d)
            v = K._mm(x, p("w_v", (D, Hkv * d)), "btd,de->bte", K.BF16).reshape(B, T, Hkv, d)
            q = rope(K.rms_norm(q, p("q_norm", (d,), nn.initializers.ones), self.eps), self.theta)
            k = rope(K.rms_norm(k, p("k_norm", (d,), nn.initializers.ones), self.eps), self.theta)
            o, fused = K.mla_attention(q.astype(K.BF16), k.astype(K.BF16), v)
            return K._mm(o.reshape(B, T, H * d), p("w_o", (H * d, D)),
                         "bte,ed->btd", K.BF16), fused


class _Moe(nn.Module):
    experts: int            # the router's outputs (published)
    top_k: int
    held: tuple             # expert ids whose weights live here
    width: int
    scaling: float

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        E = len(self.held)
        p = lambda name, shape: self.param(name, K._dense_init(), shape)
        flat = x.reshape(B * T, D)
        with jax.named_scope(K.SCOPE_ROUTE):
            bias = self.param("expert_bias", lambda key, shape: jax.random.uniform(
                key, shape, F32, -BIAS_RANGE, BIAS_RANGE), (self.experts,))
            ids, weights = K.route(flat, p("w_router", (D, self.experts)),
                                   jax.lax.stop_gradient(bias), self.top_k,
                                   self.scaling, ROUTER_EPS)
        with jax.named_scope(K.SCOPE_EXPERTS):
            y, sizes = K.expert_layer(
                flat, ids, weights, self.held, p("w_gate", (E, D, self.width)),
                p("w_up", (E, D, self.width)), p("w_down", (E, self.width, D)))
        return y.reshape(B, T, D), sizes


class _Layer(nn.Module):
    cfg: Any            # a hashable tuple of (key, value) pairs
    op: str             # "conv" | "attn"
    sparse: bool

    @nn.compact
    def __call__(self, h):
        c = dict(self.cfg)
        eps = c["norm_eps"]
        norm = lambda name: self.param(name, nn.initializers.ones, (h.shape[-1],))
        x = K.rms_norm(h, norm("norm_op"), eps)
        if self.op == "conv":
            m, fused = _Conv(c["conv_L_cache"], name="conv")(x), jnp.float32(0.0)
        else:
            m, fused = _Gqa(c["num_attention_heads"], c["num_key_value_heads"],
                            c["rope_theta"], eps, name="attn")(x)
        h = h + m.astype(h.dtype)
        x = K.rms_norm(h, norm("norm_ffn"), eps)
        if self.sparse:
            y, load = _Moe(c["num_experts"], c["num_experts_per_tok"],
                           tuple(c["experts_held"]), c["moe_intermediate_size"],
                           c["routed_scaling_factor"], name="moe")(x)
        else:
            y = K._Dense(c["intermediate_size"], name="ffn")(x)
            load = jnp.zeros((len(c["experts_held"]),), jnp.int32)
        return h + y.astype(h.dtype), load, fused


class _Lfm2Moe(nn.Module):
    """x [B, T] token ids -> the next token's logits after the last one
    given [B, V]; with ``hidden``, (hidden states after the final norm
    [B, T, D] in bfloat16, the head [D, V]: the table transposed, rows each
    held expert took in each layer [layers, E], the attention layers the
    fused kernel computed)."""

    cfg: Any
    vocab: int

    def layer_kinds(self):
        """[(operator, sparse)]; ``layer_<i + 1>`` is the published layer i."""
        c = dict(self.cfg)
        return [(op, i >= c["num_dense_layers"]) for i, op in enumerate(c["layer_ops"])]

    @nn.compact
    def __call__(self, x, train: bool = False, hidden: bool = False):
        c = dict(self.cfg)
        D = c["hidden_size"]
        embed = self.param("embed", K._dense_init(), (self.vocab, D))
        h = jnp.take(embed, x, axis=0).astype(K.BF16)
        loads, fused = [], jnp.float32(0.0)
        layer = nn.remat(_Layer) if train else _Layer
        for i, (op, sparse) in enumerate(self.layer_kinds()):
            h, load, kernel = layer(self.cfg, op, sparse, name=f"layer_{i + 1}")(h)
            loads.append(load)
            fused = fused + kernel
        h = K.rms_norm(h, self.param("norm_out", nn.initializers.ones, (D,)),
                       c["norm_eps"]).astype(K.BF16)
        if hidden:
            return h, embed.T, jnp.stack(loads), fused
        # Serving: the next token's distribution after the last one given.
        return K._mm(h[:, -1], embed, "bd,vd->bv")


class Lfm2Moe(K.SparseExpertLm):
    """The template. Shape knobs default to a size a CPU trains in
    seconds; a tenant's model file pins them (the benchmark's
    configuration pins the published widths). ``expert_shard`` of
    ``expert_shards`` says which experts this chip holds: ids
    ``expert_shard * (num_experts // expert_shards)`` onward.
    ``layer_types`` is the published list, its names joined by commas; the
    first ``num_hidden_layers`` of it are built, the first
    ``num_dense_layers`` of those with a dense feed-forward part."""

    TOP_K_KNOB = "num_experts_per_tok"

    @staticmethod
    def get_knob_config():
        fixed = lambda v: FixedKnob(v, affects_shape=True)
        return {
            "hidden_size": fixed(64), "num_attention_heads": fixed(4),
            "num_key_value_heads": fixed(2), "rope_theta": fixed(1e6),
            "conv_L_cache": fixed(3), "intermediate_size": fixed(128),
            "moe_intermediate_size": fixed(32), "num_experts": fixed(16),
            "num_experts_per_tok": fixed(4), "expert_shards": fixed(4),
            "expert_shard": fixed(0), "routed_scaling_factor": fixed(1.0),
            "num_dense_layers": fixed(2), "num_hidden_layers": fixed(6),
            "layer_types": fixed("conv,conv,full_attention,conv,conv,conv"),
            "norm_eps": fixed(1e-5),
            "learning_rate": FloatKnob(3e-5, 1e-3, is_exp=True),
            "label_smoothing": FloatKnob(0.0, 0.1),
            "batch_size": fixed(2), "epochs": FixedKnob(1), "seed": FixedKnob(0),
        }

    def module_config(self) -> tuple:
        kn = self.knobs
        per = int(kn["num_experts"]) // int(kn["expert_shards"])
        first = int(kn["expert_shard"]) * per
        c = {k: kn[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads", "rope_theta",
            "conv_L_cache", "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "routed_scaling_factor", "num_dense_layers", "norm_eps")}
        c["experts_held"] = tuple(range(first, first + per))
        kinds = str(kn["layer_types"]).split(",")[: int(kn["num_hidden_layers"])]
        if len(kinds) != int(kn["num_hidden_layers"]) or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {kn['layer_types']!r} does not name "
                             f"{kn['num_hidden_layers']} conv / full_attention layers")
        c["layer_ops"] = tuple("attn" if k == "full_attention" else "conv" for k in kinds)
        return tuple(sorted(c.items()))

    def build_module(self, num_classes, input_shape):
        return _Lfm2Moe(cfg=self.module_config(), vocab=int(num_classes))

    def _kernel_counts(self, mixers, fused):
        return {"count.attn.fused": fused,
                "count.attn.layers": jnp.float32(mixers.count("attn")),
                "count.conv.layers": jnp.float32(mixers.count("conv"))}


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.lfm2_moe`): an
    # explicit CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    _fixed = {k: v.value for k, v in Lfm2Moe.get_knob_config().items()
              if isinstance(v, FixedKnob)}
    test_model_class(
        Lfm2Moe, "LANGUAGE_MODELING",
        "synthetic://tokens?vocab=256&n=16&len=96&seed=0",
        "synthetic://tokens?vocab=256&n=4&len=96&seed=1",
        queries=[[5, 9, 3] * 8, [17, 2] * 12],
        knobs=dict(_fixed, learning_rate=1e-3, label_smoothing=0.05),
    )
