"""The plain reference of the ``kimi_linear`` configurations.

Kimi Linear's block (arXiv:2510.26692; ``config.json`` of
moonshotai/Kimi-Linear-48B-A3B-Instruct) in straightforward float32
``jax.numpy`` at "highest" matmul precision, independent of
``rafiki_tpu/models/kimi_linear.py``: the KDA recurrence runs token by
token in a ``lax.scan``, attention is a masked softmax over whole rows of
scores, the experts are a loop over the held ids, every expert on every
token under a mask. No chunked form, no sorted dispatch, no cache. The
same share of the deployment as the program: the router keeps its
published width, the experts listed in ``experts_held`` are computed, what
the others would add is left out; the vocabulary is the slice.

Three things only make it *fit* beside 2.4 GB of float32 parameters, and
none changes a number: ``seq_block`` sequences are taken at a time and
their sums added; with ``fit`` every layer is recomputed in the backward
pass and the token scan is cut into segments whose insides are recomputed
(``jax.checkpoint``); attention takes ``q_block`` rows of queries at a
time, each row still a whole softmax over all its keys.

Parameters are a flat dict keyed like the stored blob
(``layer_2/moe/w_gate``). ``init`` derives each parameter's key the way
flax does (``nnref.fold_path``), so a trial of the program and the
reference start from the same values; ``benchmark/tests`` pin that.

``forward_flops(cfg)`` is the count of one token's forward pass that
``lm_mfu.lm`` uses: 2 x multiply-adds, causal attention over the mean
number of keys, the routed experts at their expected share under uniform
routing (top-k x held / experts), nothing for recomputation.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nnref

L2_EPS = 1e-6
SEGMENT = 128  # tokens of the KDA scan recomputed together under ``fit``


# -- the configuration as the reference reads it -----------------------------

def dims(cfg: dict) -> Dict[str, Any]:
    """Sizes from the configuration's file (the published keys)."""
    la = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "dk": int(la["head_dim"]), "Hk": int(la["num_heads"]),
        "conv": int(la["short_conv_kernel_size"]),
        "mla_layers": [i for i in la["full_attn_layers"] if i <= layers],
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]), "rank": int(cfg["kv_lora_rank"]),
        "ffn": int(cfg["intermediate_size"]), "moe": int(cfg["moe_intermediate_size"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held": [int(e) for e in cfg["experts_held"]],
        "top_k": int(cfg["num_experts_per_token"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "layers": layers, "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_kinds(cfg: dict) -> List[Tuple[str, bool]]:
    """[(mixer, sparse)] for layers 1..n."""
    d = dims(cfg)
    return [("mla" if i in d["mla_layers"] else "kda", i > d["dense_layers"])
            for i in range(1, d["layers"] + 1)]


# -- initial parameters ------------------------------------------------------

def _normal(key, path, shape, std=0.02):
    return jax.nn.initializers.normal(std)(nnref.fold_path(key, path), shape,
                                           jnp.float32)


def init(key, cfg: dict) -> Dict[str, jnp.ndarray]:
    d = dims(cfg)
    D, V = d["D"], d["vocab"]
    p: Dict[str, jnp.ndarray] = {
        "embed": _normal(key, (1,), (V, D)),
        "head": _normal(key, (2,), (D, V)),
        "norm_out": jnp.ones((D,), jnp.float32),
    }
    for i, (mixer, sparse) in enumerate(layer_kinds(cfg), start=1):
        L = f"layer_{i}"
        p[f"{L}/norm_mixer"] = jnp.ones((D,), jnp.float32)
        p[f"{L}/norm_ffn"] = jnp.ones((D,), jnp.float32)
        if mixer == "kda":
            Hd, dk, H = d["Hk"] * d["dk"], d["dk"], d["Hk"]
            path = (L, "kda")
            n = 0

            def nxt():
                nonlocal n
                n += 1
                return path + (n,)

            for b in ("q", "k", "v"):
                p[f"{L}/kda/w_{b}"] = _normal(key, nxt(), (D, Hd))
                p[f"{L}/kda/conv_{b}"] = _normal(key, nxt(), (d["conv"], Hd),
                                                 1.0 / math.sqrt(d["conv"]))
            p[f"{L}/kda/w_f1"] = _normal(key, nxt(), (D, dk))
            p[f"{L}/kda/w_f2"] = _normal(key, nxt(), (dk, Hd))
            p[f"{L}/kda/A_log"] = jnp.log(jax.random.uniform(
                nnref.fold_path(key, nxt()), (H,), jnp.float32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(
                nnref.fold_path(key, nxt()), (Hd,), jnp.float32,
                np.log(1e-3), np.log(1e-1)))
            p[f"{L}/kda/dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            p[f"{L}/kda/w_beta"] = _normal(key, nxt(), (D, H))
            p[f"{L}/kda/w_g1"] = _normal(key, nxt(), (D, dk))
            p[f"{L}/kda/w_g2"] = _normal(key, nxt(), (dk, Hd))
            nxt()
            p[f"{L}/kda/o_norm"] = jnp.ones((dk,), jnp.float32)
            p[f"{L}/kda/w_o"] = _normal(key, nxt(), (Hd, D))
        else:
            H = d["H"]
            path = (L, "mla")
            p[f"{L}/mla/w_q"] = _normal(key, path + (1,), (D, H * (d["nope"] + d["rope"])))
            p[f"{L}/mla/w_kva"] = _normal(key, path + (2,), (D, d["rank"] + d["rope"]))
            p[f"{L}/mla/kv_norm"] = jnp.ones((d["rank"],), jnp.float32)
            p[f"{L}/mla/w_kvb"] = _normal(key, path + (4,),
                                          (d["rank"], H * (d["nope"] + d["dv"])))
            p[f"{L}/mla/w_o"] = _normal(key, path + (5,), (H * d["dv"], D))
        if sparse:
            E, F, path = len(d["held"]), d["moe"], (L, "moe")
            p[f"{L}/moe/router_bias"] = jnp.zeros((d["experts"],), jnp.float32)
            p[f"{L}/moe/w_router"] = _normal(key, path + (2,), (D, d["experts"]))
            p[f"{L}/moe/w_gate"] = _normal(key, path + (3,), (E, D, F))
            p[f"{L}/moe/w_up"] = _normal(key, path + (4,), (E, D, F))
            p[f"{L}/moe/w_down"] = _normal(key, path + (5,), (E, F, D))
            p[f"{L}/moe/shared_gate"] = _normal(key, path + (6,), (D, F))
            p[f"{L}/moe/shared_up"] = _normal(key, path + (7,), (D, F))
            p[f"{L}/moe/shared_down"] = _normal(key, path + (8,), (F, D))
        else:
            F, path = d["ffn"], (L, "ffn")
            p[f"{L}/ffn/w_gate"] = _normal(key, path + (1,), (D, F))
            p[f"{L}/ffn/w_up"] = _normal(key, path + (2,), (D, F))
            p[f"{L}/ffn/w_down"] = _normal(key, path + (3,), (F, D))
    return p


# -- the arithmetic ----------------------------------------------------------

def dot(a, b, quant: nnref.Quant = None):
    """a [..., n] @ b [n, m], float32 at full precision (or with what goes
    in rounded by ``quant``: the control)."""
    if quant is not None:
        return quant.output(jnp.matmul(quant.inputs(a), quant.inputs(b),
                                       precision=nnref.HIGHEST))
    return jnp.matmul(a, b, precision=nnref.HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def swiglu(x, w_gate, w_up, w_down, quant=None):
    return dot(jax.nn.silu(dot(x, w_gate, quant)) * dot(x, w_up, quant), w_down, quant)


def causal_conv(x, w):
    """x [B, T, C], w [K, C]: y_t = sum_j w_j x_(t-K+1+j)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for j in range(K):
        y = y + xp[:, j: j + T, :] * w[j]
    return y


def delta_rule(q, k, v, a, beta, fit: bool = False):
    """S_t = (I - b_t k_t k_t^T) Diag(e^(a_t)) S_(t-1) + b_t k_t v_t^T;
    o_t = S_t^T q_t; S_0 = 0. All [B, T, H, d]; beta [B, T, H]."""
    B, T, H, dk = q.shape
    hi = nnref.HIGHEST

    def step(S, xs):
        qt, kt, vt, at, bt = xs
        S = jnp.exp(at)[..., None] * S
        kS = jnp.einsum("bhk,bhkv->bhv", kt, S, precision=hi)
        S = S + jnp.einsum("bhk,bhv->bhkv", bt[..., None] * kt, vt - kS, precision=hi)
        return S, jnp.einsum("bhk,bhkv->bhv", qt, S, precision=hi)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    if fit and T > SEGMENT and T % SEGMENT == 0:
        xs = tuple(x.reshape((T // SEGMENT, SEGMENT) + x.shape[1:]) for x in xs)
        seg = jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x))
        _S, o = jax.lax.scan(seg, S0, xs)
        o = o.reshape((T,) + o.shape[2:])
    else:
        _S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1)


def kda(p, L, x, cfg, quant=None, fit=False):
    d = dims(cfg)
    B, T, _ = x.shape
    H, dk = d["Hk"], d["dk"]

    # (``fit``: each wide branch is recomputed in the backward pass)
    ck = jax.checkpoint if fit else (lambda fn: fn)

    def branch(x, w, conv_w):
        return jax.nn.silu(causal_conv(dot(x, w, quant), conv_w)).reshape(B, T, H, dk)

    def decay(x, w1, w2, a_log, dt_bias):
        f = dot(dot(x, w1, quant), w2, quant)
        return (-jnp.exp(a_log)[:, None]
                * jax.nn.softplus(f + dt_bias).reshape(B, T, H, dk))

    def output(o, x, w1, w2, o_norm, w_o):
        gate = jax.nn.sigmoid(dot(dot(x, w1, quant), w2, quant))
        o = rms_norm(o, o_norm, d["eps"]).reshape(B, T, H * dk) * gate
        return dot(o, w_o, quant)

    bq, bk, bv = (ck(branch)(x, p[f"{L}/kda/w_{b}"], p[f"{L}/kda/conv_{b}"])
                  for b in ("q", "k", "v"))
    q = l2norm(bq) / math.sqrt(dk)
    k = l2norm(bk)
    a = ck(decay)(x, p[f"{L}/kda/w_f1"], p[f"{L}/kda/w_f2"], p[f"{L}/kda/A_log"],
                  p[f"{L}/kda/dt_bias"])
    beta = jax.nn.sigmoid(dot(x, p[f"{L}/kda/w_beta"]))
    o = delta_rule(q, k, bv, a, beta, fit)
    return ck(output)(o, x, p[f"{L}/kda/w_g1"], p[f"{L}/kda/w_g2"],
                      p[f"{L}/kda/o_norm"], p[f"{L}/kda/w_o"])


def attention(q, k, v, quant=None, q_block: Optional[int] = None):
    """softmax over each query's keys up to itself. [B, T, H, d]."""
    T = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    qi = quant.inputs if quant is not None else (lambda z: z)
    qo = quant.output if quant is not None else (lambda z: z)

    def rows(qb, first):
        s = qo(jnp.einsum("bthd,bshd->bhts", qi(qb), qi(k),
                          precision=nnref.HIGHEST)) * scale
        t = first + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(t >= jnp.arange(T)[None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return qo(jnp.einsum("bhts,bshd->bthd", qi(pr), qi(v),
                             precision=nnref.HIGHEST))

    if not q_block or q_block >= T or T % q_block:
        return rows(q, 0)
    blocks = jnp.moveaxis(q.reshape((q.shape[0], T // q_block, q_block) + q.shape[2:]), 1, 0)
    out = jax.lax.map(lambda xs: jax.checkpoint(rows)(xs[0], xs[1]),
                      (blocks, jnp.arange(T // q_block) * q_block))
    return jnp.moveaxis(out, 0, 1).reshape((q.shape[0], T) + out.shape[3:])


def mla(p, L, x, cfg, quant=None, q_block=None):
    d = dims(cfg)
    B, T, _ = x.shape
    H, nope, rope, dv, rank = d["H"], d["nope"], d["rope"], d["dv"], d["rank"]
    q = dot(x, p[f"{L}/mla/w_q"], quant).reshape(B, T, H, nope + rope)
    kva = dot(x, p[f"{L}/mla/w_kva"], quant)
    c = rms_norm(kva[..., :rank], p[f"{L}/mla/kv_norm"], d["eps"])
    k_r = kva[..., rank:]
    kvb = dot(c, p[f"{L}/mla/w_kvb"], quant).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, rope))], -1)
    o = attention(q, k, kvb[..., nope:], quant, q_block)
    return dot(o.reshape(B, T, H * dv), p[f"{L}/mla/w_o"], quant)


def router(p, L, x, cfg):
    """(selected ids [.., k], their weights [.., k]) over all experts."""
    d = dims(cfg)
    s = jax.nn.sigmoid(dot(x, p[f"{L}/moe/w_router"]))
    _v, ids = jax.lax.top_k(s + p[f"{L}/moe/router_bias"], d["top_k"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, d["scaling"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routed_part(p, L, x, cfg, held: Sequence[int], quant=None,
                weights: Optional[Dict[str, jnp.ndarray]] = None, fit: bool = False):
    """Sum over the held experts of w_i E_i(x): every held expert on every
    token, weighted by nought where the token was not routed to it.
    ``weights``: the held experts' stacked matrices, where they are not
    ``p``'s (the share test hands in another chip's)."""
    w = weights or {n: p[f"{L}/moe/{n}"] for n in ("w_gate", "w_up", "w_down")}
    ids, wt = router(p, L, x, cfg)

    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        we = jnp.sum(jnp.where(ids == e, wt, 0.0), axis=-1, keepdims=True)
        return y + we * swiglu(x, w_gate, w_up, w_down, quant), None

    # (a loop over the held ids, one after the other: ``lax.scan`` writes its
    # body once where a Python loop writes it for every expert)
    return jax.lax.scan(jax.checkpoint(one) if fit else one, jnp.zeros_like(x),
                        (jnp.asarray(held, jnp.int32), w["w_gate"], w["w_up"], w["w_down"]))[0]


def shared_part(p, L, x, quant=None):
    return swiglu(x, p[f"{L}/moe/shared_gate"], p[f"{L}/moe/shared_up"],
                  p[f"{L}/moe/shared_down"], quant)


def layer(p, i, h, cfg, mixer, sparse, quant=None, fit=False, q_block=None):
    d = dims(cfg)
    L = f"layer_{i}"
    x = rms_norm(h, p[f"{L}/norm_mixer"], d["eps"])
    h = h + (kda(p, L, x, cfg, quant, fit) if mixer == "kda"
             else mla(p, L, x, cfg, quant, q_block))
    x = rms_norm(h, p[f"{L}/norm_ffn"], d["eps"])
    if sparse:
        y = (routed_part(p, L, x, cfg, d["held"], quant, fit=fit)
             + shared_part(p, L, x, quant))
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
    for i, (mixer, sparse) in enumerate(layer_kinds(cfg), start=1):
        f = lambda hh, i=i, mixer=mixer, sparse=sparse: layer(
            p, i, hh, cfg, mixer, sparse, quant, fit, q_block)
        h = jax.checkpoint(f)(h) if fit else f(h)
    return final_norm(p, h, cfg)


def forward(p, x, cfg: dict, quant: nnref.Quant = None, fit: bool = False,
            q_block: Optional[int] = None) -> jnp.ndarray:
    """Token ids [B, T] -> logits [B, T, V] over the sliced vocabulary."""
    return dot(hidden(p, x, cfg, quant, fit, q_block), p["head"], quant)


def _token_stats(logits, y, smoothing):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    ce = (1.0 - smoothing) * nll + smoothing * -jnp.mean(logp, axis=-1)
    return jnp.sum(ce), jnp.sum(jnp.argmax(logits, axis=-1) == y)


def head_stats(p, h, y, smoothing=0.0, quant: nnref.Quant = None, fit: bool = False):
    """The normed last layer's output [B, T, D] -> (summed cross entropy
    with label smoothing, hits of the argmax) against ``y``, the token
    after each position. (``fit``: the head is applied to ``SEGMENT * 8``
    positions at a time, each token's cross entropy what it is over whole
    logits.)"""
    T, block = h.shape[1], SEGMENT * 8
    if not fit or T <= block or T % block:
        return _token_stats(dot(h, p["head"], quant), y, smoothing)
    cut = lambda a: jnp.moveaxis(a.reshape((a.shape[0], T // block, block) + a.shape[2:]), 1, 0)
    one = jax.checkpoint(lambda hb, yb: _token_stats(dot(hb, p["head"], quant), yb, smoothing))
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
         fit: bool = False, q_block: Optional[int] = None, seq_block: int = 0):
    """Mean cross entropy over all positions of the batch, ``seq_block``
    sequences at a time (0: all at once)."""
    B = x.shape[0]
    if not seq_block or seq_block >= B or B % seq_block:
        ce, _h, n = stats(p, x, y, cfg, smoothing, quant, fit, q_block)
        return ce / n
    xs = x.reshape((B // seq_block, seq_block) + x.shape[1:])
    ys = y.reshape(xs.shape)
    one = jax.checkpoint(lambda xb, yb: stats(p, xb, yb, cfg, smoothing, quant,
                                              fit, q_block)[0])
    ce = jax.lax.scan(lambda c, b: (c + one(b[0], b[1]), None),
                      jnp.zeros((), jnp.float32), (xs, ys))[0]
    return ce / y.size


# -- the count -----------------------------------------------------------------

def forward_flops(cfg: dict, seq_len: Optional[int] = None) -> float:
    """FLOPs of one token's forward pass (2 x multiply-adds)."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    D, V = d["D"], d["vocab"]
    Hd = d["Hk"] * d["dk"]
    kda_macs = (3 * D * Hd + 3 * Hd * d["conv"] + 2 * (D * d["dk"] + d["dk"] * Hd)
                + D * d["Hk"] + Hd * D + 3 * d["Hk"] * d["dk"] * d["dk"])
    H = d["H"]
    keys = (T + 1) / 2.0
    mla_macs = (D * H * (d["nope"] + d["rope"]) + D * (d["rank"] + d["rope"])
                + d["rank"] * H * (d["nope"] + d["dv"]) + H * d["dv"] * D
                + keys * H * (d["nope"] + d["rope"] + d["dv"]))
    expert = 3 * D * d["moe"]
    routed = d["top_k"] * len(d["held"]) / d["experts"]
    macs = D * V
    for mixer, sparse in layer_kinds(cfg):
        macs += kda_macs if mixer == "kda" else mla_macs
        macs += (D * d["experts"] + expert * (1 + routed)) if sparse else 3 * D * d["ffn"]
    return 2.0 * macs


def parameters(cfg: dict) -> int:
    return int(sum(int(np.prod(v.shape)) for v in
                   jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)).values()))
