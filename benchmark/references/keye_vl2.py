"""The plain reference of the ``keye_vl2`` configurations.

Keye-VL-2.0-30B-A3B's language model (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type`` ``KeyeVL2``; its ``sa_config``
is DeepSeek-V3.2's sparse attention) in straightforward float32
``jax.numpy`` at "highest" matmul precision, independent of
``rafiki_tpu/models/keye_vl2.py``. Every layer is

    h = h + attn(RMSNorm(h));  h = h + moe(RMSNorm(h))

``attn``, with u the normed input: q = W_q u, k = W_k u, v = W_v u (heads of
``head_dim``; a key/value head serves H / Hk query heads in a row, written as
the heads repeated); q and k RMS-normed per head and rotated (rotate-half,
positions 0..T-1). The indexer on u with no gradient: q^I = W_qI u, k^I =
LayerNorm(W_kI u), both rotated, w = W_w u / sqrt(HI); I[t, s] = sum_j w_j
relu(q^I_j . k^I_s) / sqrt(dI) over whole rows. The selection S_t: the keys
s <= t above the ``topk``-th largest value ``lax.top_k`` returns for the row
(the positions after t at -inf), and of those equal to it the lower positions
first, as many as are left (``lax.top_k``'s own order among equals; a scatter
of its indices into a mask cost seconds a layer at 32k on the chip). The attention: a softmax over
whole rows of scores in which every key outside S_t is -inf, times v; W_o. The
indexer's loss of a token: KL(p || softmax of I over S_t), p the probabilities
averaged over heads, held constant. ``moe``: a softmax over all the router's
outputs, the top k renormalised over themselves; every held expert on every
token, weighted by nought where the token was not routed to it. After the last
layer one RMSNorm and an untied head. The objective: the mean cross entropy
plus the mean over tokens of the indexer's loss, summed over the layers. No
kernel, no threshold search, no sorted dispatch, no cache.

Departures from the published description, each the deployment's cut: the
router keeps its published width and the experts listed in ``experts_held``
are computed, what the others would add is left out; the vocabulary is the
slice; ``num_hidden_layers`` of the published layers are present, numbered
from 1 as the stored blob numbers them. What the configuration does not fix
is the file's ``assumed`` list (the indexer's placement and scales, its loss).

What only makes it *fit* at the published widths, none changing a number
(``fit``): every layer and every expert's pass is recomputed in the backward
pass, attention and the indexer take ``q_block`` rows of queries at a time,
each seeing the keys up to the end of the quarter of the sequence it lies in
(each row still whole: what lies after is masked), the head ``HEAD_BLOCK``
positions at a time.

``fault`` (the control's, never the benchmark's): ``topk_half`` selects half
the keys, ``dense`` attends to every key at or before a token (the selection
ignored), ``no_index_loss`` leaves the indexer's loss out.

Parameters are a flat dict keyed like the stored blob
(``layer_3/attn/w_q``); ``init`` derives each parameter's key the way flax
does (``nnref.fold_path``). ``forward_flops(cfg)`` counts 2 x multiply-adds of
one token: the projections, the indexer's scores over the mean number of keys
before a token, the attention over the mean size of a selection, the routed
experts at their expected share under uniform routing, the head; nothing for
recomputation. ``sparse_attention_work(cfg, tokens)`` gives the FLOPs and
bytes the selected attention needs (``dsa_attention_roofline.lm``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nnref
from .kimi_linear import _token_stats, dot, rms_norm, swiglu
from .lfm2_moe import rope

HEAD_BLOCK = 1024      # positions whose logits exist at one time under ``fit``
SEGMENTS = 4           # parts of the sequence whose queries see keys to the part's end, under ``fit``
LN_EPS = 1e-6          # the indexer key's LayerNorm (assumed)
FAULTS = ("topk_half", "dense", "no_index_loss")


# -- the configuration as the reference reads it -----------------------------

def dims(cfg: dict) -> Dict[str, Any]:
    sa = cfg["sa_config"]
    return {
        "D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]), "HI": int(sa["indexer_num_heads"]),
        "dI": int(sa["indexer_head_dim"]), "topk": int(sa["topk"]),
        "moe": int(cfg["moe_intermediate_size"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held": [int(e) for e in cfg["experts_held"]],
        "top_k": int(cfg["num_experts_per_tok"]),
        "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_kinds(cfg: dict) -> List[Tuple[str, bool]]:
    return [("dsa", True)] * dims(cfg)["layers"]


# -- initial parameters ------------------------------------------------------

def _normal(key, path, shape, std=0.02):
    return jax.nn.initializers.normal(std)(nnref.fold_path(key, path), shape, jnp.float32)


def init(key, cfg: dict) -> Dict[str, jnp.ndarray]:
    d = dims(cfg)
    D, H, Hkv, hd, HI, dI = d["D"], d["H"], d["Hkv"], d["d"], d["HI"], d["dI"]
    p: Dict[str, jnp.ndarray] = {
        "embed": _normal(key, (1,), (d["vocab"], D)),
        "head": _normal(key, (2,), (D, d["vocab"])),
        "norm_out": jnp.ones((D,), jnp.float32),
    }
    for i in range(1, d["layers"] + 1):
        L = f"layer_{i}"
        p[f"{L}/norm_attn"] = jnp.ones((D,), jnp.float32)
        p[f"{L}/norm_ffn"] = jnp.ones((D,), jnp.float32)
        a = (L, "attn")
        p[f"{L}/attn/w_q"] = _normal(key, a + (1,), (D, H * hd))
        p[f"{L}/attn/w_k"] = _normal(key, a + (2,), (D, Hkv * hd))
        p[f"{L}/attn/w_v"] = _normal(key, a + (3,), (D, Hkv * hd))
        p[f"{L}/attn/q_norm"] = jnp.ones((hd,), jnp.float32)
        p[f"{L}/attn/k_norm"] = jnp.ones((hd,), jnp.float32)
        p[f"{L}/attn/index_q"] = _normal(key, a + (6,), (D, HI * dI))
        p[f"{L}/attn/index_k"] = _normal(key, a + (7,), (D, dI))
        p[f"{L}/attn/index_k_norm"] = jnp.ones((dI,), jnp.float32)
        p[f"{L}/attn/index_k_bias"] = jnp.zeros((dI,), jnp.float32)
        p[f"{L}/attn/index_w"] = _normal(key, a + (10,), (D, HI))
        p[f"{L}/attn/w_o"] = _normal(key, a + (11,), (H * hd, D))
        E, F, m = len(d["held"]), d["moe"], (L, "moe")
        p[f"{L}/moe/w_router"] = _normal(key, m + (1,), (D, d["experts"]))
        p[f"{L}/moe/w_gate"] = _normal(key, m + (2,), (E, D, F))
        p[f"{L}/moe/w_up"] = _normal(key, m + (3,), (E, D, F))
        p[f"{L}/moe/w_down"] = _normal(key, m + (4,), (E, F, D))
    return p


# -- the arithmetic ----------------------------------------------------------

def layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def indexer(p, L, x, cfg, quant=None):
    """(q^I [B, T, HI, dI], k^I [B, T, dI], w [B, T, HI] with 1 / sqrt(HI dI)
    in it) of the normed input ``x``, which no gradient leaves through."""
    d = dims(cfg)
    B, T, _ = x.shape
    u = jax.lax.stop_gradient(x)
    qI = rope(dot(u, p[f"{L}/attn/index_q"], quant).reshape(B, T, d["HI"], d["dI"]), d["theta"])
    kI = layer_norm(dot(u, p[f"{L}/attn/index_k"], quant), p[f"{L}/attn/index_k_norm"],
                    p[f"{L}/attn/index_k_bias"])
    kI = rope(kI[:, :, None, :], d["theta"])[:, :, 0]
    w = dot(u, p[f"{L}/attn/index_w"], quant) / math.sqrt(d["HI"] * d["dI"])
    return qI, kI, w


def scores(qI, kI, w, quant=None):
    """I [B, n, T] of a block of queries against every key."""
    qi = quant.inputs if quant is not None else (lambda z: z)
    a = jnp.einsum("bqjd,bsd->bqjs", qi(qI), qi(kI), precision=nnref.HIGHEST)
    return jnp.einsum("bqjs,bqj->bqs", jnp.maximum(a, 0.0), w, precision=nnref.HIGHEST)


def select(I, first, topk: int, fault: Optional[str] = None):
    """The selection of a block of queries ``first``.. from their scores
    [B, n, T] -> bool [B, n, T]: the keys at or before the query whose score
    is above the ``topk``-th largest that ``lax.top_k`` returns for the row
    (the positions after the query at -inf), and of those equal to it as many
    as are left to take, the lower positions first (``lax.top_k``'s order
    among equals); every key at or before the query where there are fewer."""
    B, n, T = I.shape
    valid = jnp.arange(T)[None, None, :] <= (first + jnp.arange(n))[None, :, None]
    if fault == "dense":
        return valid
    k = min(topk // 2 if fault == "topk_half" else topk, T)
    vals, _idx = jax.lax.top_k(jnp.where(valid, I, -jnp.inf), k)
    kth = vals[..., -1:]
    above = valid & (I > kth)
    tied = valid & (I == kth)
    left = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= left))


def _attn_rows(qb, k, v, qIb, kI, wb, first, topk, fault, quant):
    """A block of queries: (o [B, n, H, d], the indexer's loss of each
    token [B, n])."""
    qi = quant.inputs if quant is not None else (lambda z: z)
    qo = quant.output if quant is not None else (lambda z: z)
    I = scores(qIb, kI, wb, quant)
    sel = jax.lax.stop_gradient(select(jax.lax.stop_gradient(I), first, topk, fault))
    s = qo(jnp.einsum("bthd,bshd->bhts", qi(qb), qi(k), precision=nnref.HIGHEST))
    s = jnp.where(sel[:, None], s / math.sqrt(qb.shape[-1]), -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    o = qo(jnp.einsum("bhts,bshd->bthd", qi(prob), qi(v), precision=nnref.HIGHEST))
    p = jax.lax.stop_gradient(jnp.mean(prob, axis=1))
    log_q = jax.nn.log_softmax(jnp.where(sel, I, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0), axis=-1)
    return o, kl


def _in_blocks(rows, keys, queries, q_block, checkpoint=False, width=None):
    """``rows(keys, *a block of queries, first)`` over blocks of ``q_block``
    queries, the results joined along the sequence. What only makes it fit
    (``fit``): the sequence is cut into ``SEGMENTS``, and a segment's blocks
    take the keys up to its end (the keys after it lie after every query of
    it, masked in its rows: a row is still whole); ``width``: a result's last
    axis is the keys', padded back to it. Without ``q_block``, one block."""
    T = queries[0].shape[1]
    if not q_block or q_block >= T or T % (SEGMENTS * q_block):
        return rows(keys, *queries, 0)
    seg, parts = T // SEGMENTS, []
    for i in range(SEGMENTS):
        end = (i + 1) * seg
        cut = lambda a: jnp.moveaxis(
            a[:, i * seg: end].reshape((a.shape[0], seg // q_block, q_block) + a.shape[2:]), 1, 0)
        block = lambda xs, end=end: rows(tuple(z[:, :end] for z in keys), *xs)
        out = jax.lax.map(jax.checkpoint(block) if checkpoint else block,
                          tuple(map(cut, queries)) + (i * seg + jnp.arange(seg // q_block) * q_block,))
        out = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1).reshape(
            (a.shape[1], seg) + a.shape[3:]), out)
        if width:
            out = jnp.pad(out, ((0, 0), (0, 0), (0, width - end)))
        parts.append(out)
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *parts)


def attn_op(p, L, x, cfg, quant=None, q_block=None, fault=None):
    """(W_o o [B, T, D], the indexer's loss of every token [B, T])."""
    d = dims(cfg)
    B, T, _ = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["d"]
    q = dot(x, p[f"{L}/attn/w_q"], quant).reshape(B, T, H, hd)
    k = dot(x, p[f"{L}/attn/w_k"], quant).reshape(B, T, Hkv, hd)
    v = dot(x, p[f"{L}/attn/w_v"], quant).reshape(B, T, Hkv, hd)
    q = rope(rms_norm(q, p[f"{L}/attn/q_norm"], d["eps"]), d["theta"])
    k = rope(rms_norm(k, p[f"{L}/attn/k_norm"], d["eps"]), d["theta"])
    k, v = (jnp.repeat(z, H // Hkv, axis=2) for z in (k, v))
    qI, kI, w = indexer(p, L, x, cfg, quant)

    def rows(keys, qb, qIb, wb, first):
        kb, vb, kIb = keys
        return _attn_rows(qb, kb, vb, qIb, kIb, wb, first, d["topk"], fault, quant)

    o, kl = _in_blocks(rows, (k, v, kI), (q, qI, w), q_block, checkpoint=True)
    if fault == "no_index_loss":
        kl = jnp.zeros_like(kl)
    return dot(o.reshape(B, T, H * hd), p[f"{L}/attn/w_o"], quant), kl


def selection(p, i, h, cfg, q_block=None, fault=None, quant=None):
    """Layer ``i``'s selection of its input ``h`` [B, T, D]: bool [B, T, T]."""
    d = dims(cfg)
    L = f"layer_{i}"
    B, T, _ = h.shape
    qI, kI, w = indexer(p, L, rms_norm(h, p[f"{L}/norm_attn"], d["eps"]), cfg, quant)

    def rows(keys, qIb, wb, first):
        return select(scores(qIb, keys[0], wb, quant), first, d["topk"], fault)

    return _in_blocks(rows, (kI,), (qI, w), q_block, width=T)


def router(p, L, x, cfg):
    """(selected ids [.., k], their weights [.., k]): softmax over every
    output, the top k renormalised over themselves."""
    s = jax.nn.softmax(dot(x, p[f"{L}/moe/w_router"]), axis=-1)
    _v, ids = jax.lax.top_k(s, dims(cfg)["top_k"])
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routed_part(p, L, x, cfg, held, quant=None, weights=None, fit: bool = False):
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


def layer(p, i, h, cfg, op="dsa", sparse=True, quant=None, fit=False, q_block=None,
          fault=None):
    """-> (the layer's output [B, T, D], its indexer's loss summed over tokens)."""
    d = dims(cfg)
    L = f"layer_{i}"
    m, kl = attn_op(p, L, rms_norm(h, p[f"{L}/norm_attn"], d["eps"]), cfg, quant, q_block, fault)
    h = h + m
    x = rms_norm(h, p[f"{L}/norm_ffn"], d["eps"])
    return h + routed_part(p, L, x, cfg, d["held"], quant, fit=fit), jnp.sum(kl)


def embed(p, x):
    return jnp.take(p["embed"], x, axis=0)


def final_norm(p, h, cfg: dict):
    return rms_norm(h, p["norm_out"], dims(cfg)["eps"])


def head_stats(p, h, y, smoothing=0.0, quant: nnref.Quant = None, fit: bool = False):
    """The normed last layer's output [B, T, D] -> (summed cross entropy
    with label smoothing, hits of the argmax) against ``y``."""
    T = h.shape[1]
    if not fit or T <= HEAD_BLOCK or T % HEAD_BLOCK:
        return _token_stats(dot(h, p["head"], quant), y, smoothing)
    cut = lambda a: jnp.moveaxis(a.reshape((a.shape[0], T // HEAD_BLOCK, HEAD_BLOCK) + a.shape[2:]), 1, 0)
    one = jax.checkpoint(lambda hb, yb: _token_stats(dot(hb, p["head"], quant), yb, smoothing))
    ce, hits = jax.lax.map(lambda b: one(b[0], b[1]), (cut(h), cut(y)))
    return jnp.sum(ce), jnp.sum(hits)


def stats(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
          fit: bool = False, q_block: Optional[int] = None, fault=None):
    """(summed cross entropy with label smoothing, the indexer's loss summed
    over tokens and layers, hits of the argmax, positions)."""
    h, index_loss = embed(p, x), 0.0
    for i in range(1, dims(cfg)["layers"] + 1):
        h, kl = layer(p, i, h, cfg, quant=quant, fit=fit, q_block=q_block, fault=fault)
        index_loss = index_loss + kl
    ce, hits = head_stats(p, final_norm(p, h, cfg), y, smoothing, quant, fit)
    return ce, index_loss, hits, y.size


def loss(p, x, y, cfg: dict, smoothing=0.0, quant: nnref.Quant = None,
         fit: bool = False, q_block: Optional[int] = None, fault=None):
    """The objective: mean cross entropy plus the indexer's loss, each a
    mean over all positions of the batch."""
    ce, index_loss, _h, n = stats(p, x, y, cfg, smoothing, quant, fit, q_block, fault)
    return (ce + index_loss) / n


# -- the counts ----------------------------------------------------------------

def _selected_keys(T: int, topk: int) -> float:
    """sum over t of |S_t| = min(t + 1, topk)."""
    k = min(topk, T)
    return k * (k + 1) / 2.0 + (T - k) * k


def forward_flops(cfg: dict, seq_len: Optional[int] = None) -> float:
    """FLOPs of one token's forward pass (2 x multiply-adds)."""
    d = dims(cfg)
    T = int(seq_len or cfg["seq_len"])
    D, H, Hkv, hd, HI, dI = d["D"], d["H"], d["Hkv"], d["d"], d["HI"], d["dI"]
    selected = _selected_keys(T, d["topk"]) / T
    keys = (T + 1) / 2.0
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D + selected * H * 2 * hd
    index = D * (HI * dI + dI + HI) + keys * HI * dI
    routed = d["top_k"] * len(d["held"]) / d["experts"]
    macs = D * d["vocab"] + d["layers"] * (attn + index + D * d["experts"]
                                           + 3 * D * d["moe"] * routed)
    return 2.0 * macs


def sparse_attention_work(cfg: dict, tokens_per_sequence: Optional[int] = None,
                          sequences: int = 1) -> Dict[str, float]:
    """What one layer's sparse attention needs for ``sequences`` of the
    configuration's length, by pass, whatever implements it
    (``dsa_attention_roofline.lm``): FLOPs 4 x H x d x sum_t |S_t| forward
    (q k^T and p v over the selected pairs) and 2.5 times that backward; bytes
    q, k, v, o once forward, and those with do, dq, dk, dv backward, in
    bfloat16."""
    d = dims(cfg)
    T = int(tokens_per_sequence or cfg["seq_len"])
    pairs = sequences * _selected_keys(T, d["topk"])
    fwd = 4.0 * d["H"] * d["d"] * pairs
    q_o = 2 * sequences * T * d["H"] * d["d"] * 2          # q and o, bfloat16
    k_v = 2 * sequences * T * d["Hkv"] * d["d"] * 2        # k and v
    return {"forward_flops": fwd, "backward_flops": 2.5 * fwd,
            "forward_bytes": float(q_o + k_v), "backward_bytes": float(2 * (q_o + k_v))}


def parameters(cfg: dict) -> int:
    return int(sum(int(np.prod(v.shape)) for v in
                   jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0)).values()))
