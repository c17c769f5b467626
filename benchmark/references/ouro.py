"""The plain reference of the ``ouro`` configurations.

Ouro-2.6B's block (``config.json`` of ByteDance/Ouro-2.6B, ``model_type``
``ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) in straightforward float32 ``jax.numpy`` at "highest"
matmul precision, independent of ``rafiki_tpu/models/ouro.py``. A layer has
four RMSNorms, one before and one after each half:

    h = h + N2(attn(N1(h)));  h = h + N4(ffn(N3(h)))

``attn``: q, k, v = W_q u, W_k u, W_v u as heads of ``head_dim``; q and k
rotated (rotate-half over the whole head, positions 0..T-1); a masked softmax
over whole rows of scores scaled by 1/sqrt(head_dim); W_o. ``ffn``:
W_2 (silu(W_1 u) * W_3 u). No bias anywhere in a layer.

The loop, a Python loop over R = ``total_ut_steps`` passes of the held
layers: ``h_0 = E[x]``; ``h_t = N_f(layers(h_(t-1)))`` with the same
parameters at every t; the normed ``h_t`` goes into pass t + 1, into the head
(``z_t = W_head h_t``) and into the exit gate (``lambda_t = sigmoid(w_g . h_t
+ b_g)``, one gate for all passes). Exit distribution of a token:
``p_t = lambda_t prod_(j<t) (1 - lambda_j)`` for t < R, ``p_R = prod_(j<R)
(1 - lambda_j)``. Objective: the mean over tokens of ``sum_t p_t CE_t -
beta H(p)``, ``H(p) = -sum_t p_t log p_t``, ``CE_t`` the token's cross entropy
under ``z_t`` with label smoothing. Score: the accuracy of ``z_R``
(``early_exit_threshold`` 1: no token leaves early). No fused kernel, no
scan, no cache.

Departures from the published description, each the deployment's cut or a
size the config does not fix (the configuration's file lists them under
``assumed``):
* ``num_hidden_layers`` of the published 48 are present (one pipeline
  stage), numbered from 1 as the stored blob numbers them; the whole
  embedding, head, ``N_f`` and gate lie with them so that a trial has a loss.
* the place of the four norms and of ``N_f`` inside the loop follows the
  paper's description and the published modelling code; ``config`` gives only
  ``rms_norm_eps``.
* the gate has a bias, reads the normed ``h_t``, and starts at normal(0.02)
  and 0; ``beta`` is ``exit_entropy_beta`` of the file (0.05).
* the paper's second-stage objective (the gate alone, the model frozen) is
  left out: model and gate train together.
* ``0 log 0`` is taken as 0 in ``H(p)`` (a gate saturated in float32).

What only makes it *fit* at the published widths, none changing a number
(``fit``, as the other references): every layer visit is recomputed in the
backward pass, attention takes ``q_block`` rows of queries at a time (each
row still a whole softmax), the head ``HEAD_BLOCK`` positions at a time.

Parameters are a flat dict keyed like the stored blob
(``layer_3/attn/w_q``); ``init`` derives each parameter's key the way flax
does (``nnref.fold_path``). ``forward_flops(cfg)`` counts 2 x multiply-adds of
one token through R visits of each held layer and R heads, causal attention
over the mean number of keys, nothing for recomputation.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nnref
# The float32 arithmetic the language-model references share, plain functions
# of arrays: a product at "highest" (or the control's rounding), RMSNorm, the
# gated unit, whole-row causal attention; rotary positions.
from .kimi_linear import attention, dot, rms_norm, swiglu
from .lfm2_moe import rope

HEAD_BLOCK = 1024      # positions whose logits exist at one time under ``fit``


# -- the configuration as the reference reads it -----------------------------

def dims(cfg: dict) -> Dict[str, Any]:
    """Sizes from the configuration's file (the published keys)."""
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]), "ffn": int(cfg["intermediate_size"]),
        "layers": int(cfg["num_hidden_layers"]), "passes": int(cfg["total_ut_steps"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "beta": float(cfg["exit_entropy_beta"]),
    }


def layer_kinds(cfg: dict) -> List[Tuple[str, bool]]:
    """[(operator, sparse)] for the held layers 1..n: every one full
    attention and a dense feed-forward part."""
    return [("attn", False)] * dims(cfg)["layers"]


# -- initial parameters ------------------------------------------------------

def _normal(key, path, shape, std=0.02):
    return jax.nn.initializers.normal(std)(nnref.fold_path(key, path), shape,
                                           jnp.float32)


def init(key, cfg: dict) -> Dict[str, jnp.ndarray]:
    d = dims(cfg)
    D = d["D"]
    ones = lambda: jnp.ones((D,), jnp.float32)
    p: Dict[str, jnp.ndarray] = {
        "embed": _normal(key, (1,), (d["vocab"], D)),
        "head": _normal(key, (2,), (D, d["vocab"])),
        "norm_out": ones(),
        "gate_w": _normal(key, (4,), (D,)),
        "gate_b": jnp.zeros((), jnp.float32),
    }
    for i in range(1, d["layers"] + 1):
        L = f"layer_{i}"
        for name in ("norm_attn", "norm_attn_out", "norm_ffn", "norm_ffn_out"):
            p[f"{L}/{name}"] = ones()
        p[f"{L}/attn/w_q"] = _normal(key, (L, "attn", 1), (D, d["H"] * d["d"]))
        p[f"{L}/attn/w_k"] = _normal(key, (L, "attn", 2), (D, d["Hkv"] * d["d"]))
        p[f"{L}/attn/w_v"] = _normal(key, (L, "attn", 3), (D, d["Hkv"] * d["d"]))
        p[f"{L}/attn/w_o"] = _normal(key, (L, "attn", 4), (d["H"] * d["d"], D))
        p[f"{L}/ffn/w_gate"] = _normal(key, (L, "ffn", 1), (D, d["ffn"]))
        p[f"{L}/ffn/w_up"] = _normal(key, (L, "ffn", 2), (D, d["ffn"]))
        p[f"{L}/ffn/w_down"] = _normal(key, (L, "ffn", 3), (d["ffn"], D))
    return p


# -- the arithmetic ----------------------------------------------------------

def attn_op(p, L, x, cfg, quant=None, q_block=None):
    d = dims(cfg)
    B, T, _ = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["d"]
    q = rope(dot(x, p[f"{L}/attn/w_q"], quant).reshape(B, T, H, hd), d["theta"])
    k = rope(dot(x, p[f"{L}/attn/w_k"], quant).reshape(B, T, Hkv, hd), d["theta"])
    v = dot(x, p[f"{L}/attn/w_v"], quant).reshape(B, T, Hkv, hd)
    # each key/value head serves H / Hkv query heads in a row (one, as published)
    k, v = (jnp.repeat(z, H // Hkv, axis=2) for z in (k, v))
    o = attention(q, k, v, quant, q_block)
    return dot(o.reshape(B, T, H * hd), p[f"{L}/attn/w_o"], quant)


def layer(p, i, h, cfg, op="attn", sparse=False, quant=None, fit=False, q_block=None):
    """One visit of layer ``i``. (``op``, ``sparse`` and ``fit``: the other
    references' interface; this model has one kind of layer.)"""
    eps = dims(cfg)["eps"]
    L = f"layer_{i}"
    m = attn_op(p, L, rms_norm(h, p[f"{L}/norm_attn"], eps), cfg, quant, q_block)
    h = h + rms_norm(m, p[f"{L}/norm_attn_out"], eps)
    y = swiglu(rms_norm(h, p[f"{L}/norm_ffn"], eps), p[f"{L}/ffn/w_gate"],
               p[f"{L}/ffn/w_up"], p[f"{L}/ffn/w_down"], quant)
    return h + rms_norm(y, p[f"{L}/norm_ffn_out"], eps)


def embed(p, x):
    """Token ids [B, T] -> the first pass's input [B, T, D]."""
    return jnp.take(p["embed"], x, axis=0)


def final_norm(p, h, cfg: dict):
    """``N_f``: after every pass of the stack."""
    return rms_norm(h, p["norm_out"], dims(cfg)["eps"])


def hidden_states(p, x, cfg: dict, quant: nnref.Quant = None, fit: bool = False,
                  q_block: Optional[int] = None, passes: Optional[int] = None
                  ) -> List[jnp.ndarray]:
    """Token ids [B, T] -> [h_1 .. h_R], each pass's output after ``N_f``."""
    d = dims(cfg)
    h, out = embed(p, x), []
    for _t in range(passes or d["passes"]):
        for i in range(1, d["layers"] + 1):
            f = lambda hh, i=i: layer(p, i, hh, cfg, quant=quant, fit=fit, q_block=q_block)
            h = jax.checkpoint(f)(h) if fit else f(h)
        h = final_norm(p, h, cfg)
        out.append(h)
    return out


def forward(p, x, cfg: dict, quant: nnref.Quant = None, fit: bool = False,
            q_block: Optional[int] = None) -> jnp.ndarray:
    """Token ids [B, T] -> the last pass's logits [B, T, V]."""
    return dot(hidden_states(p, x, cfg, quant, fit, q_block)[-1], p["head"], quant)


def gate_logits(p, hs):
    """[h_1 .. h_R] -> the exit gate's logit of every pass and token [R, B, T]."""
    return jnp.stack([jnp.matmul(h, p["gate_w"], precision=nnref.HIGHEST) + p["gate_b"]
                      for h in hs])


def exit_distribution(gates):
    """The gate's logits [R, B, T] -> p [R, B, T], written out."""
    lam = jax.nn.sigmoid(gates)
    stay, p = jnp.ones_like(lam[0]), []
    for t in range(gates.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p + [stay])


def exit_entropy(p):
    """H(p) = -sum_t p_t log p_t per token, 0 log 0 = 0."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)


def _token_stats(logits, y, smoothing):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return ((1.0 - smoothing) * nll + smoothing * -jnp.mean(logp, axis=-1),
            jnp.argmax(logits, axis=-1) == y)


def head_token_stats(p, h, y, smoothing=0.0, quant: nnref.Quant = None, fit: bool = False):
    """A pass's normed output [B, T, D] -> (each token's cross entropy with
    label smoothing [B, T], whether the argmax hit [B, T]) against ``y``, the
    token after each position. (``fit``: ``HEAD_BLOCK`` positions at a time,
    each token's cross entropy what it is over whole logits.)"""
    T = h.shape[1]
    if not fit or T <= HEAD_BLOCK or T % HEAD_BLOCK:
        return _token_stats(dot(h, p["head"], quant), y, smoothing)
    cut = lambda a: jnp.moveaxis(
        a.reshape((a.shape[0], T // HEAD_BLOCK, HEAD_BLOCK) + a.shape[2:]), 1, 0)
    one = jax.checkpoint(lambda hb, yb: _token_stats(dot(hb, p["head"], quant), yb, smoothing))
    ce, hit = jax.lax.map(lambda b: one(b[0], b[1]), (cut(h), cut(y)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(y.shape)
    return join(ce), join(hit)


def head_stats(p, h, y, smoothing=0.0, quant: nnref.Quant = None, fit: bool = False):
    """(summed cross entropy, hits of the argmax) of one pass's head."""
    ce, hit = head_token_stats(p, h, y, smoothing, quant, fit)
    return jnp.sum(ce), jnp.sum(hit)


def objective(p_exit, ce, beta: float):
    """Per token: sum_t p_t CE_t - beta H(p). ``p_exit``, ``ce``: [R, B, T]."""
    return jnp.sum(p_exit * ce, axis=0) - beta * exit_entropy(p_exit)


def stats(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
          fit: bool = False, q_block: Optional[int] = None):
    """(the summed objective, hits of the last pass's argmax, positions) of
    next-token prediction: ``y`` is the token after each of ``x``."""
    hs = hidden_states(p, x, cfg, quant, fit, q_block)
    per_pass = [head_token_stats(p, h, y, smoothing, quant, fit) for h in hs]
    ce = jnp.stack([c for c, _hit in per_pass])
    total = objective(exit_distribution(gate_logits(p, hs)), ce, dims(cfg)["beta"])
    return jnp.sum(total), jnp.sum(per_pass[-1][1]), y.size


def loss(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
         fit: bool = False, q_block: Optional[int] = None):
    """The training objective: its mean over all positions of the batch."""
    total, _hits, n = stats(p, x, y, cfg, smoothing, quant, fit, q_block)
    return total / n


# -- the counts ----------------------------------------------------------------

def forward_flops(cfg: dict, seq_len: Optional[int] = None) -> float:
    """FLOPs of one token's forward pass (2 x multiply-adds): R visits of
    each held layer, R heads, R gates."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    D, H, Hkv, hd = d["D"], d["H"], d["Hkv"], d["d"]
    keys = (T + 1) / 2.0
    visit = (D * H * hd + 2 * D * Hkv * hd + H * hd * D + keys * H * 2 * hd
             + 3 * D * d["ffn"])
    return 2.0 * d["passes"] * (d["layers"] * visit + D * d["vocab"] + D)


def attention_kernel_flops(cfg: dict, batch: int, seq_len: Optional[int] = None
                           ) -> Dict[str, float]:
    """FLOPs the causal half of one attention layer VISIT's products needs
    for ``batch`` sequences, by pass (``gqa_attention_roofline.lm``; a step
    has R x layers visits, and the driver multiplies by the kernels' calls):
    as ``references/lfm2_moe.py``: a product of a head over the T (T + 1) / 2
    pairs at or under the diagonal is 2 x T (T + 1) / 2 x d; two forward,
    five backward, recomputation not counted."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    product = 2.0 * batch * d["H"] * (T * (T + 1) / 2.0) * d["d"]
    return {"forward": 2 * product, "backward": 5 * product}


def parameters(cfg: dict) -> int:
    return int(sum(int(np.prod(v.shape)) for v in
                   jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)).values()))
