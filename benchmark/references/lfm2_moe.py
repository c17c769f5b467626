"""The plain reference of the ``lfm2_moe`` configurations.

LFM2-8B-A1B's block (``config.json`` of LiquidAI/LFM2-8B-A1B, ``model_type``
``lfm2_moe``) in straightforward float32 ``jax.numpy`` at "highest" matmul
precision, independent of ``rafiki_tpu/models/lfm2_moe.py``: every layer is
``h = h + op(RMSNorm(h)); h = h + ffn(RMSNorm(h))``, the operator a gated
three-tap causal convolution (written as three shifted products) or
grouped-query attention (per-head RMS norms on queries and keys, rotary
positions in the rotate-half convention, the key/value heads repeated, a
masked softmax over whole rows of scores); the feed-forward part dense in
the leading layers and, in the rest, a sigmoid router that selects by score
plus a bias and weights by the score alone, over experts that are a loop
over the held ids, every held expert on every token under a mask. After the
last layer one RMSNorm, then logits by the embedding's transpose. No fused
kernel, no sorted dispatch, no cache.

Departures from the published description, each the deployment's cut and
not the mathematics': the router keeps its published width and the experts
listed in ``experts_held`` are computed, what the others would add is left
out; the vocabulary is the slice; ``num_hidden_layers`` of the published
``layer_types`` are present, numbered from 1 as the stored blob numbers
them (``layer_1`` is the published layer 0). ``expert_bias`` is a buffer of
the checkpoint that no loss trains; here it is drawn at initialisation and
no gradient reaches it (selection is by integer ids).

What only makes it *fit* at the published widths, none changing a number
(``fit``, as ``references/kimi_linear.py``): every layer and every expert's
pass is recomputed in the backward pass, attention takes ``q_block`` rows of
queries at a time (each row still a whole softmax), the head ``1024``
positions at a time.

Parameters are a flat dict keyed like the stored blob
(``layer_3/moe/w_gate``); ``init`` derives each parameter's key the way flax
does (``nnref.fold_path``), so a trial of the program and the reference start
from the same values. ``forward_flops(cfg)`` counts as
``references/kimi_linear.py`` does: 2 x multiply-adds, causal attention over
the mean number of keys, the routed experts at their expected share under
uniform routing (top-k x held / experts), nothing for recomputation.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nnref
# The float32 arithmetic both language-model references share, plain
# functions of arrays: a product at "highest" (or the control's rounding),
# RMSNorm, the gated unit, K shifted products, whole-row causal attention,
# a token's cross entropy and hit over whole logits.
from .kimi_linear import _token_stats, attention, causal_conv, dot, rms_norm, swiglu

ROUTER_EPS = 1e-6      # added to the selected scores' sum (``norm_topk_prob``)
BIAS_RANGE = 0.05      # expert_bias ~ U(-0.05, 0.05) (assumed: a buffer of the checkpoint)
HEAD_BLOCK = 1024      # positions whose logits exist at one time under ``fit``


# -- the configuration as the reference reads it -----------------------------

def dims(cfg: dict) -> Dict[str, Any]:
    """Sizes from the configuration's file (the published keys)."""
    H = int(cfg["num_attention_heads"])
    return {
        "D": int(cfg["hidden_size"]), "H": H, "Hkv": int(cfg["num_key_value_heads"]),
        "d": int(cfg["hidden_size"]) // H, "theta": float(cfg["rope_theta"]),
        "taps": int(cfg["conv_L_cache"]),
        "ffn": int(cfg["intermediate_size"]), "moe": int(cfg["moe_intermediate_size"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held": [int(e) for e in cfg["experts_held"]],
        "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "dense_layers": int(cfg["num_dense_layers"]),
        "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["norm_eps"]),
    }


def layer_kinds(cfg: dict) -> List[Tuple[str, bool]]:
    """[(operator, sparse)] for layers 1..n: published layers 0..n-1."""
    d = dims(cfg)
    return [("attn" if kind == "full_attention" else "conv", i >= d["dense_layers"])
            for i, kind in enumerate(cfg["layer_types"][: d["layers"]])]


# -- initial parameters ------------------------------------------------------

def _normal(key, path, shape, std=0.02):
    return jax.nn.initializers.normal(std)(nnref.fold_path(key, path), shape,
                                           jnp.float32)


def init(key, cfg: dict) -> Dict[str, jnp.ndarray]:
    d = dims(cfg)
    D = d["D"]
    p: Dict[str, jnp.ndarray] = {
        "embed": _normal(key, (1,), (d["vocab"], D)),
        "norm_out": jnp.ones((D,), jnp.float32),
    }
    for i, (op, sparse) in enumerate(layer_kinds(cfg), start=1):
        L = f"layer_{i}"
        p[f"{L}/norm_op"] = jnp.ones((D,), jnp.float32)
        p[f"{L}/norm_ffn"] = jnp.ones((D,), jnp.float32)
        if op == "conv":
            path = (L, "conv")
            p[f"{L}/conv/w_in"] = _normal(key, path + (1,), (D, 3 * D))
            p[f"{L}/conv/conv"] = _normal(key, path + (2,), (d["taps"], D),
                                          1.0 / math.sqrt(d["taps"]))
            p[f"{L}/conv/w_out"] = _normal(key, path + (3,), (D, D))
        else:
            path = (L, "attn")
            p[f"{L}/attn/w_q"] = _normal(key, path + (1,), (D, d["H"] * d["d"]))
            p[f"{L}/attn/w_k"] = _normal(key, path + (2,), (D, d["Hkv"] * d["d"]))
            p[f"{L}/attn/w_v"] = _normal(key, path + (3,), (D, d["Hkv"] * d["d"]))
            p[f"{L}/attn/q_norm"] = jnp.ones((d["d"],), jnp.float32)
            p[f"{L}/attn/k_norm"] = jnp.ones((d["d"],), jnp.float32)
            p[f"{L}/attn/w_o"] = _normal(key, path + (6,), (d["H"] * d["d"], D))
        if sparse:
            E, F, path = len(d["held"]), d["moe"], (L, "moe")
            p[f"{L}/moe/expert_bias"] = jax.random.uniform(
                nnref.fold_path(key, path + (1,)), (d["experts"],), jnp.float32,
                -BIAS_RANGE, BIAS_RANGE)
            p[f"{L}/moe/w_router"] = _normal(key, path + (2,), (D, d["experts"]))
            p[f"{L}/moe/w_gate"] = _normal(key, path + (3,), (E, D, F))
            p[f"{L}/moe/w_up"] = _normal(key, path + (4,), (E, D, F))
            p[f"{L}/moe/w_down"] = _normal(key, path + (5,), (E, F, D))
        else:
            F, path = d["ffn"], (L, "ffn")
            p[f"{L}/ffn/w_gate"] = _normal(key, path + (1,), (D, F))
            p[f"{L}/ffn/w_up"] = _normal(key, path + (2,), (D, F))
            p[f"{L}/ffn/w_down"] = _normal(key, path + (3,), (F, D))
    return p


# -- the arithmetic ----------------------------------------------------------

def conv_op(p, L, x, quant=None):
    """[B, C, u] = W_in x; y = C * causal_conv3(B * u); W_out y."""
    b, c, u = jnp.split(dot(x, p[f"{L}/conv/w_in"], quant), 3, axis=-1)
    return dot(c * causal_conv(b * u, p[f"{L}/conv/conv"]), p[f"{L}/conv/w_out"], quant)


def rope(x, theta: float):
    """Rotary positions 0..T-1 on [B, T, H, d], rotate-half over the whole
    head: channel i < d/2 pairs with channel i + d/2, both turned by the
    angle position x theta^(-2i/d)."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def attn_op(p, L, x, cfg, quant=None, q_block=None):
    d = dims(cfg)
    B, T, _ = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["d"]
    q = dot(x, p[f"{L}/attn/w_q"], quant).reshape(B, T, H, hd)
    k = dot(x, p[f"{L}/attn/w_k"], quant).reshape(B, T, Hkv, hd)
    v = dot(x, p[f"{L}/attn/w_v"], quant).reshape(B, T, Hkv, hd)
    q = rope(rms_norm(q, p[f"{L}/attn/q_norm"], d["eps"]), d["theta"])
    k = rope(rms_norm(k, p[f"{L}/attn/k_norm"], d["eps"]), d["theta"])
    # each key/value head serves H / Hkv query heads in a row
    k, v = (jnp.repeat(z, H // Hkv, axis=2) for z in (k, v))
    o = attention(q, k, v, quant, q_block)
    return dot(o.reshape(B, T, H * hd), p[f"{L}/attn/w_o"], quant)


def router(p, L, x, cfg):
    """(selected ids [.., k], their weights [.., k]) over all experts:
    selected by score + bias, weighted by the score alone."""
    d = dims(cfg)
    s = jax.nn.sigmoid(dot(x, p[f"{L}/moe/w_router"]))
    _v, ids = jax.lax.top_k(s + p[f"{L}/moe/expert_bias"], d["top_k"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, d["scaling"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                         + ROUTER_EPS)


def routed_part(p, L, x, cfg, held: Sequence[int], quant=None,
                weights: Optional[Dict[str, jnp.ndarray]] = None, fit: bool = False):
    """Sum over the held experts of w_i E_i(x): every held expert on every
    token, weighted by nought where the token was not routed to it.
    ``weights``: the held experts' stacked matrices, where they are not
    ``p``'s (the share test hands in another rank's)."""
    w = weights or {n: p[f"{L}/moe/{n}"] for n in ("w_gate", "w_up", "w_down")}
    ids, wt = router(p, L, x, cfg)

    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        we = jnp.sum(jnp.where(ids == e, wt, 0.0), axis=-1, keepdims=True)
        return y + we * swiglu(x, w_gate, w_up, w_down, quant), None

    return jax.lax.scan(jax.checkpoint(one) if fit else one, jnp.zeros_like(x),
                        (jnp.asarray(held, jnp.int32), w["w_gate"], w["w_up"], w["w_down"]))[0]


def layer(p, i, h, cfg, op, sparse, quant=None, fit=False, q_block=None):
    d = dims(cfg)
    L = f"layer_{i}"
    x = rms_norm(h, p[f"{L}/norm_op"], d["eps"])
    h = h + (conv_op(p, L, x, quant) if op == "conv"
             else attn_op(p, L, x, cfg, quant, q_block))
    x = rms_norm(h, p[f"{L}/norm_ffn"], d["eps"])
    if sparse:
        y = routed_part(p, L, x, cfg, d["held"], quant, fit=fit)
    else:
        y = swiglu(x, p[f"{L}/ffn/w_gate"], p[f"{L}/ffn/w_up"], p[f"{L}/ffn/w_down"], quant)
    return h + y


def embed(p, x):
    """Token ids [B, T] -> the first layer's input [B, T, D]."""
    return jnp.take(p["embed"], x, axis=0)


def final_norm(p, h, cfg: dict):
    return rms_norm(h, p["norm_out"], dims(cfg)["eps"])


def hidden(p, x, cfg: dict, quant: nnref.Quant = None, fit: bool = False,
           q_block: Optional[int] = None) -> jnp.ndarray:
    """Token ids [B, T] -> the last layer's output after the final norm."""
    h = embed(p, x)
    for i, (op, sparse) in enumerate(layer_kinds(cfg), start=1):
        f = lambda hh, i=i, op=op, sparse=sparse: layer(
            p, i, hh, cfg, op, sparse, quant, fit, q_block)
        h = jax.checkpoint(f)(h) if fit else f(h)
    return final_norm(p, h, cfg)


def logits_of(p, h, quant: nnref.Quant = None):
    """The head is the embedding's transpose (``tie_embedding``)."""
    return dot(h, p["embed"].T, quant)


def forward(p, x, cfg: dict, quant: nnref.Quant = None, fit: bool = False,
            q_block: Optional[int] = None) -> jnp.ndarray:
    """Token ids [B, T] -> logits [B, T, V] over the sliced vocabulary."""
    return logits_of(p, hidden(p, x, cfg, quant, fit, q_block), quant)


def head_stats(p, h, y, smoothing=0.0, quant: nnref.Quant = None, fit: bool = False):
    """The normed last layer's output [B, T, D] -> (summed cross entropy
    with label smoothing over the slice, hits of the argmax) against ``y``,
    the token after each position; ``p`` holds the table. (``fit``:
    ``HEAD_BLOCK`` positions at a time, each token's cross entropy what it is
    over whole logits.)"""
    T = h.shape[1]
    if not fit or T <= HEAD_BLOCK or T % HEAD_BLOCK:
        return _token_stats(logits_of(p, h, quant), y, smoothing)
    cut = lambda a: jnp.moveaxis(
        a.reshape((a.shape[0], T // HEAD_BLOCK, HEAD_BLOCK) + a.shape[2:]), 1, 0)
    one = jax.checkpoint(lambda hb, yb: _token_stats(logits_of(p, hb, quant), yb, smoothing))
    ce, hits = jax.lax.map(lambda b: one(b[0], b[1]), (cut(h), cut(y)))
    return jnp.sum(ce), jnp.sum(hits)


def stats(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
          fit: bool = False, q_block: Optional[int] = None):
    """(summed cross entropy with label smoothing, hits of the argmax,
    positions) of next-token prediction: ``y`` is the token after each of
    ``x``."""
    ce, hits = head_stats(p, hidden(p, x, cfg, quant, fit, q_block), y, smoothing,
                          quant, fit)
    return ce, hits, y.size


def loss(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
         fit: bool = False, q_block: Optional[int] = None):
    """Mean cross entropy over all positions of the batch."""
    ce, _h, n = stats(p, x, y, cfg, smoothing, quant, fit, q_block)
    return ce / n


# -- the counts ----------------------------------------------------------------

def forward_flops(cfg: dict, seq_len: Optional[int] = None) -> float:
    """FLOPs of one token's forward pass (2 x multiply-adds)."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    D, H, Hkv, hd = d["D"], d["H"], d["Hkv"], d["d"]
    conv_macs = D * 3 * D + d["taps"] * D + D * D
    keys = (T + 1) / 2.0
    attn_macs = D * H * hd + 2 * D * Hkv * hd + H * hd * D + keys * H * 2 * hd
    routed = d["top_k"] * len(d["held"]) / d["experts"]
    macs = D * d["vocab"]
    for op, sparse in layer_kinds(cfg):
        macs += conv_macs if op == "conv" else attn_macs
        macs += (D * d["experts"] + 3 * D * d["moe"] * routed) if sparse else 3 * D * d["ffn"]
    return 2.0 * macs


def attention_kernel_flops(cfg: dict, batch: int, seq_len: Optional[int] = None
                           ) -> Dict[str, float]:
    """FLOPs the causal half of one attention layer's products needs for
    ``batch`` sequences, by pass (``gqa_attention_roofline.lm``): a product of
    a head over the T (T + 1) / 2 pairs at or under the diagonal is
    2 x T (T + 1) / 2 x d. The forward pass has two (q k^T, p v). The
    backward pass, from the saved output and log-sum-exp, needs five: the
    scores again (q k^T), dp = do v^T, dv = p^T do, dq = ds k, dk = ds^T q.
    The library's two backward kernels each make the scores and dp again
    (seven products in all); the second making is recomputation and is not
    counted, as ``lm_mfu.lm`` counts none."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    product = 2.0 * batch * d["H"] * (T * (T + 1) / 2.0) * d["d"]
    return {"forward": 2 * product, "backward": 5 * product}


def parameters(cfg: dict) -> int:
    return int(sum(int(np.prod(v.shape)) for v in
                   jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)).values()))
