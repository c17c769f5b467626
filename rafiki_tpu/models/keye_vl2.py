"""Sparse-attention sparse-expert language model template: a learned indexer
picks the keys each query attends to.

No reference analog. The block is the language model published for
Keye-VL-2.0-30B-A3B (``model_type`` ``KeyeVL2``): pre-norm residual layers,
``h = h + attn(RMSNorm(h)); h = h + moe(RMSNorm(h))``, after the last one
RMSNorm and an untied head. With u the normed input of the attention half:

* attention: q = W_q u (H heads of d), k = W_k u and v = W_v u (Hk heads of
  d, a key/value head serving H / Hk query heads in a row); q and k
  RMS-normed per head with a learned scale and rotated (rotate-half, theta
  from the configuration; the three M-RoPE sections carry one position on
  text, which is 1-D rotary);
* the indexer ("lightning indexer", DeepSeek-V3.2's sparse attention), on u
  with no gradient: q^I = W_qI u (HI heads of dI), k^I = LayerNorm(W_kI u)
  (one head), both rotated, w = W_w u / sqrt(HI), and
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) / sqrt(dI)`` for s <= t;
* the selection: S_t, the ``topk`` positions s <= t of the largest I[t, s]
  (all of them while t < topk), ties to the lower position; one selection a
  token, shared by every head, not differentiated;
* the sparse attention: softmax(q . k / sqrt(d)) over S_t alone, times v;
* the indexer's loss: ``KL(p_t || softmax_(S_t) I_t)`` with p_t the main
  attention's probabilities summed over heads and normalised over S_t, held
  constant; its mean over tokens is added to the language model's loss
  (weight 1). The indexer learns from it alone and nothing else learns
  from it;
* the sparse experts: a softmax over all experts, the top ``k`` selected and
  renormalised over themselves (``norm_topk_prob``), through
  ``kimi_linear.route`` (its softmax mode) and ``kimi_linear.expert_layer``;
  no shared expert.

What the template adds to the zoo, by mechanism:

* ``dsa_select``: the indexer's scores a block of ``SELECT_BLOCK`` queries
  at a time (no ``[T, T]`` score array exists), the ``topk``-th largest of a
  row found exactly by a bisection over the bits of its float32 pattern
  (32 counting passes; a sort of every row is what ``lax.top_k`` would do),
  ties cut at a position found by a second bisection only in a block that
  has them; the selection kept as an int8 mask [B, T, T] and the selected
  keys counted a tile of the kernels at a time.
* ``dsa_attention``: softmax attention over the mask, the indexer's scores
  again and the terms of its loss, forward in one pass, then a
  ``custom_vjp`` that takes the forward's output and log-sum-exps for its
  backward rule. Lowered for a TPU, at heads of 128 and 64 and a length
  ``DSA_KEY_TILE`` divides:
  three Pallas kernels of this repo (``dsa_fwd``: per query tile, a first
  sweep of the key tiles for every head's log-sum-exp and the indexer's,
  a second for the output, the head-summed probabilities p and the loss's
  terms; ``dsa_dq`` and ``dsa_dkv``: the backward pass from the saved
  output and log-sum-exps, the indexer's gradient from p beside it). A key
  tile no query of the tile selected, or above the diagonal, is skipped.
  Everywhere else (the CPU, other widths and lengths): plain ``jax.numpy``,
  a block of queries at a time, differentiated by ``jax.vjp``.
  ``count.dsa.fused`` of ``count.dsa.layers`` says which ran;
  ``count.dsa.keys_selected`` sums |S_t| and ``count.dsa.tiles_visited`` of
  ``count.dsa.tiles_total`` the kernels' tiles that hold a selected key.

TPU notes as ``kimi_linear.py``'s: bfloat16 operands and float32
accumulation in matrix products; parameters, norms, rotations, the router,
the indexer's scores and every softmax in float32; every layer recomputed
in the backward pass (``nn.remat``), the selection with it, but for the
sparse attention's output and log-sum-exps, which the layer keeps
(``KEEP_FORWARD``; ``count.dsa.forward_kept`` layers a step), so that a
step runs ``dsa_fwd`` once a layer. Which path runs
is decided when a program is lowered, from the platform it is lowered for;
no environment variable or knob enters. A trial at the published widths
fills a chip by itself; what it shares of the ``JaxModel`` contract with the
other sparse-expert templates is ``kimi_linear.SparseExpertLm``.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models import kimi_linear as K

F32, BF16 = jnp.float32, jnp.bfloat16
NEG = -1e30            # a score no selected key has
SELECT_BLOCK = 128     # queries whose indexer scores exist at one time
DSA_QUERY_TILE = 256   # queries of one tile of the sparse attention kernels
DSA_KEY_TILE = 512     # keys of one tile of the sparse attention kernels
DSA_HEAD_DIM, DSA_INDEX_DIM = 128, 64   # the widths the kernels are written for
LN_EPS = 1e-6          # the indexer key's LayerNorm (assumed)
ROPE_SPLIT = 256       # a position t is ROPE_SPLIT * a + b in the rotary tables' angle sum

# Named scopes of the block (docs/telemetry.md), beside ``kimi_linear.py``'s
# ``moe.route``, ``moe.experts`` and ``lm.loss``, which this template shares.
SCOPE_INDEX, SCOPE_SELECT = "dsa.index", "dsa.select"
SCOPE_ATTN, SCOPE_KL = "dsa.attn", "dsa.kl"
# What a layer's rematerialisation keeps of the sparse attention's forward:
# the output and the log-sum-exps the backward kernels read (``_dsa_fused``).
DSA_KEPT = ("dsa.o", "dsa.lse", "dsa.lse_i")
KEEP_FORWARD = jax.checkpoint_policies.save_only_these_names(*DSA_KEPT)


def rope(x, theta: float):
    """``lfm2_moe.rope`` (rotate-half over the whole head, positions
    0..T-1, float32) with its tables made in the program: the angle of
    position 256a + b is the sum of two angles from tables of 256 and
    T / 256 rows, and cos and sin of the sum are those of the parts
    combined. ``lfm2_moe.rope`` writes its [T, d] tables into the program as
    constants: at 32,768 tokens 16-32 MB for each of a layer's four
    rotations, in every program that holds a layer, and on a TPU v5e those
    programs were compiled anew in every process (PERF.md section 7 (s)).
    Here they are a few kilobytes; the tables differ from the float64-rounded
    ones by at most a few float32 units."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    rows = -(-T // ROPE_SPLIT)
    coarse = (np.arange(rows, dtype=np.float64) * ROPE_SPLIT)[:, None] * freq
    fine = np.arange(ROPE_SPLIT, dtype=np.float64)[:, None] * freq
    (ca, sa), (cb, sb) = ((jnp.asarray(np.cos(a), F32), jnp.asarray(np.sin(a), F32))
                          for a in (coarse, fine))
    whole = lambda t: jnp.concatenate([t.reshape(rows * ROPE_SPLIT, half)[:T]] * 2, -1)[
        None, :, None, :]
    cos = whole(ca[:, None] * cb[None] - sa[:, None] * sb[None])
    sin = whole(sa[:, None] * cb[None] + ca[:, None] * sb[None])
    x = x.astype(F32)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


# -- the indexer's scores and the selection -----------------------------------

def index_scores(qI, kI, w):
    """I [B, n, S] of queries ``qI`` [B, n, HI, dI] against keys ``kI``
    [B, S, dI], weighted by ``w`` [B, n, HI] (the 1 / sqrt(dI) already in
    it): sum_j w_j relu(qI_j . kI), the products in bfloat16 with a float32
    sum, the rest in float32."""
    a = K._mm(qI, kI, "bqjd,bsd->bqjs")
    return jnp.einsum("bqjs,bqj->bqs", jnp.maximum(a, 0.0), w,
                      precision=jax.lax.Precision.HIGHEST)


def _ordered(x):
    """float32 -> uint32 whose order is the floats' (-0.0 taken as 0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | np.uint32(1 << 31), ~bits)


def _select_rows(scores, first, topk: int):
    """The selection of a block of queries: ``scores`` [B, n, S] of queries
    ``first``.. against keys 0..S-1 -> int8 [B, n, S], 1 at the ``topk``
    positions s <= t of the largest score (all of them while t < topk),
    ties to the lower position. The ``topk``-th largest is found exactly,
    bit by bit from the top of its order-preserving pattern: keep a bit
    where at least ``topk`` keys lie at or above the pattern with it set."""
    S = scores.shape[-1]
    pos = jnp.arange(S, dtype=jnp.int32)
    valid = pos[None, None, :] <= (first + jnp.arange(scores.shape[1]))[None, :, None]
    u = jnp.where(valid, _ordered(scores), np.uint32(0))   # 0: below any score

    def bit(i, thr):
        cand = thr | (np.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))
    above = u > thr[..., None]
    tied = valid & (u == thr[..., None])
    need = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)     # ties to take, from the left

    def cut_ties():
        def halve(_i, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            enough = jnp.sum(tied & (pos <= mid[..., None]), axis=-1, dtype=jnp.int32) >= need
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)
        lo = jnp.zeros(need.shape, jnp.int32)
        return jax.lax.fori_loop(0, int(S).bit_length(), halve, (lo, lo + S - 1))[1]

    over = jnp.any(jnp.sum(tied, axis=-1, dtype=jnp.int32) > need)
    cut = jax.lax.cond(over, cut_ties, lambda: jnp.full(need.shape, S - 1, jnp.int32))
    return (above | (tied & (pos <= cut[..., None]))).astype(jnp.int8)


@functools.partial(jax.jit, static_argnums=(3,))
def dsa_select(qI, kI, w, topk: int):
    """The selection of every query: ``qI`` [B, T, HI, dI], ``kI`` [B, T, dI],
    ``w`` [B, T, HI] -> (mask [B, T, T] int8, selected keys of each tile of
    the kernels [B, T / DSA_QUERY_TILE, T / DSA_KEY_TILE] int32; one tile
    where they do not divide the length). A block of ``SELECT_BLOCK`` queries
    at a time (``lax.map``; cut at the diagonal, the blocks' outputs took
    8.6 GB of temporaries where one map over every block takes 0.08,
    compiled for a described v5e at 32,768 tokens)."""
    B, T = qI.shape[:2]
    block = SELECT_BLOCK if T % SELECT_BLOCK == 0 else T

    def one(xs):
        qb, wb, first = xs
        return _select_rows(index_scores(qb, kI, wb), first, topk)

    cut = lambda x: jnp.moveaxis(x.reshape((B, T // block, block) + x.shape[2:]), 1, 0)
    mask = jax.lax.map(one, (cut(qI), cut(w), jnp.arange(T // block, dtype=jnp.int32) * block))
    mask = jnp.moveaxis(mask, 0, 1).reshape(B, T, T)
    tq, tk = _tiles(T)
    counts = jnp.sum(mask.reshape(B, T // tq, tq, T // tk, tk), axis=(2, 4), dtype=jnp.int32)
    return mask, counts


# -- the sparse attention: the blocked path -------------------------------------

def _dsa_rows(q, k, v, sel, qI, kI, w):
    """A block of queries, plainly: ``q`` [B, H, n, d] (scaled), ``k``, ``v``
    [B, Hk, S, d], ``sel`` [B, n, S] bool, ``qI`` [B, HI, n, dI], ``kI``
    [B, S, dI], ``w`` [B, n, HI] -> (o [B, H, n, d] in bfloat16, the loss's
    terms [B, n], every head's log-sum-exp [B, H, n], the indexer's [B, n])."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.where(sel[:, None], K._mm(q, k, "bhtd,bhsd->bhts"), NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(sel[:, None], jnp.exp(s - m), 0.0)
    lse = m + jnp.log(jnp.sum(e, axis=-1, keepdims=True))
    prob = e / jnp.sum(e, axis=-1, keepdims=True)
    o = K._mm(prob, v, "bhts,bhsd->bhtd", BF16)
    p = jax.lax.stop_gradient(jnp.mean(prob, axis=1))
    scores = jnp.where(sel, index_scores(jnp.swapaxes(qI, 1, 2), kI, w), NEG)
    lse_i = jax.nn.logsumexp(scores, axis=-1)
    plogp = jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=-1)
    kl = plogp - jnp.sum(jnp.where(sel, p * scores, 0.0), axis=-1) + lse_i
    return o, kl, lse[..., 0], lse_i


def _dsa_blocked(q, k, v, mask, qI, kI, w):
    """``_dsa_rows`` a block of ``SELECT_BLOCK`` queries at a time, each
    recomputed in the backward pass -> (o, kl, lse [B, H, T], lse_i [B, T])."""
    B, H, T, d = q.shape
    block = SELECT_BLOCK if T % SELECT_BLOCK == 0 else T
    cut = lambda x, ax: jnp.moveaxis(
        x.reshape(x.shape[:ax] + (T // block, block) + x.shape[ax + 1:]), ax, 0)
    one = jax.checkpoint(lambda xs: _dsa_rows(xs[0], k, v, xs[1] != 0, xs[2], kI, xs[3]))
    o, kl, lse, lse_i = jax.lax.map(one, (cut(q, 2), cut(mask, 1), cut(qI, 2), cut(w, 1)))
    join = lambda x, ax: jnp.moveaxis(x, 0, ax).reshape(
        x.shape[1: ax + 1] + (T,) + x.shape[ax + 2:])
    return join(o, 2), join(kl, 1), join(lse, 2), join(lse_i, 1)


# -- the sparse attention: the kernels ---------------------------------------------

def _index_head(j, qI_ref, kI_ref, w_ref):
    """Indexer head ``j`` of a tile: (its weight of each query [tq, 1], its
    products before the ReLU [tq, tk])."""
    wv = w_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, wv.shape, 1)
    w_j = jnp.sum(jnp.where(lane == j, wv, 0.0), axis=1, keepdims=True)
    return w_j, K._kmm(qI_ref[0, j], kI_ref[0], K._NT)


def _over_index_heads(body, init, qI_ref):
    """``body(j, carry)`` over the indexer's heads, a loop on the device (the
    heads unrolled made each kernel's code, and its compile, 16 times longer)."""
    return jax.lax.fori_loop(0, qI_ref.shape[1], body, init)


def _index_tile(qI_ref, kI_ref, w_ref, shape):
    """The indexer's scores of a tile [tq, tk] from its blocks."""
    def head(j, acc):
        w_j, a = _index_head(j, qI_ref, kI_ref, w_ref)
        return acc + w_j * jnp.maximum(a, 0.0)

    return _over_index_heads(head, jnp.zeros(shape, F32), qI_ref)


def _tile_bounds(tq: int, tk: int):
    """(last key tile a query tile sees, first query tile a key tile meets)."""
    return (lambda i: ((i + 1) * tq - 1) // tk), (lambda j: (j * tk) // tq)


def _dsa_fwd_kernel(table, q_ref, k_ref, v_ref, mask_ref, qI_ref, kI_ref, w_ref,
                    o_ref, lse_ref, kl_ref, lse_i_ref, m_s, l_s, acc_s, mi_s, li_s,
                    plogp_s, pi_s, *, tq, tk, nk):
    """A grid step (b, query tile i, sweep, key tile j) of ``dsa_fwd``. The
    first sweep keeps a running maximum and sum for every head and for the
    indexer's scores; the second, with the log-sum-exps known, adds the
    normalised probabilities times v, and the head-summed p into the loss's
    terms. Key tiles above the diagonal or with no selected key are skipped."""
    b, i, sweep, j = (pl.program_id(n) for n in range(4))
    H, group = q_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]
    last, _first = _tile_bounds(tq, tk)
    live = (j <= last(i)) & (table[(b * pl.num_programs(1) + i) * nk + j] > 0)

    @pl.when((sweep == 0) & (j == 0))
    def _():
        m_s[...] = jnp.full(m_s.shape, NEG, F32)
        l_s[...] = jnp.zeros(l_s.shape, F32)
        mi_s[...] = jnp.full(mi_s.shape, NEG, F32)
        li_s[...] = jnp.zeros(li_s.shape, F32)

    @pl.when((sweep == 1) & (j == 0))
    def _():
        lse_ref[0] = m_s[...] + jnp.log(l_s[...])
        lse_i_ref[0] = mi_s[...] + jnp.log(li_s[...])
        acc_s[...] = jnp.zeros(acc_s.shape, F32)
        plogp_s[...] = jnp.zeros(plogp_s.shape, F32)
        pi_s[...] = jnp.zeros(pi_s.shape, F32)

    def online(m_ref, l_ref, s, sel):
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(jnp.where(sel, s, NEG), axis=1, keepdims=True))
        e = jnp.where(sel, jnp.exp(s - m_new[:, :1]), 0.0)
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(e, axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when((sweep == 0) & live)
    def _():
        sel = mask_ref[0].astype(jnp.int32) != 0

        def head(h, carry):
            s = K._kmm(q_ref[0, h], k_ref[0, h // group], K._NT)
            online(m_s.at[h], l_s.at[h], s, sel)
            return carry

        jax.lax.fori_loop(0, H, head, 0)
        online(mi_s, li_s, _index_tile(qI_ref, kI_ref, w_ref, sel.shape), sel)

    @pl.when((sweep == 1) & live)
    def _():
        sel = mask_ref[0].astype(jnp.int32) != 0

        def head(h, psum):
            g = h // group
            s = K._kmm(q_ref[0, h], k_ref[0, g], K._NT)
            prob = jnp.where(sel, jnp.exp(s - lse_ref[0, h][:, :1]), 0.0)
            acc_s[h] = acc_s[h] + K._kmm(prob, v_ref[0, g])
            return psum + prob

        p = jax.lax.fori_loop(0, H, head, jnp.zeros(sel.shape, F32)) * (1.0 / H)
        scores = _index_tile(qI_ref, kI_ref, w_ref, sel.shape)
        safe = jnp.where(p > 0, p, 1.0)
        plogp_s[...] += jnp.sum(jnp.where(p > 0, p * jnp.log(safe), 0.0), axis=1, keepdims=True)
        pi_s[...] += jnp.sum(jnp.where(sel, p * scores, 0.0), axis=1, keepdims=True)

    @pl.when((sweep == 1) & (j == nk - 1))
    def _():
        o_ref[0] = acc_s[...].astype(o_ref.dtype)
        kl_ref[0] = plogp_s[...] - pi_s[...] + lse_i_ref[0]


def _backward_terms(h, group, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sel):
    """A head's tile in the backward pass: (probabilities, ds = p (dp - D))."""
    g = h // group
    s = K._kmm(q_ref[0, h], k_ref[0, g], K._NT)
    prob = jnp.where(sel, jnp.exp(s - lse_ref[0, h][:, :1]), 0.0)
    dp = K._kmm(do_ref[0, h], v_ref[0, g], K._NT)
    dsum = jnp.sum(do_ref[0, h].astype(F32) * o_ref[0, h].astype(F32), axis=1, keepdims=True)
    return prob, prob * (dp - dsum)


def _index_cotangent(psum, H, sel, qI_ref, kI_ref, w_ref, lse_i_ref, dkl_ref):
    """d loss / d I of a tile: dkl (softmax over the selection of I - p)."""
    scores = _index_tile(qI_ref, kI_ref, w_ref, sel.shape)
    soft = jnp.where(sel, jnp.exp(scores - lse_i_ref[0][:, :1]), 0.0)
    return dkl_ref[0][:, :1] * (soft - psum * (1.0 / H))


def _dsa_dq_kernel(table, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, mask_ref, qI_ref,
                   kI_ref, w_ref, lse_i_ref, dkl_ref, dq_ref, dqi_ref, dw_ref,
                   dq_s, dqi_s, dw_s, *, tq, tk, nk):
    """A grid step (b, query tile i, key tile j) of ``dsa_dq``: dq, and the
    indexer's dq and dw, summed over the key tiles of a query tile."""
    b, i, j = (pl.program_id(n) for n in range(3))
    H, group = q_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]
    last, _first = _tile_bounds(tq, tk)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, F32)
        dqi_s[...] = jnp.zeros(dqi_s.shape, F32)
        dw_s[...] = jnp.zeros(dw_s.shape, F32)

    @pl.when((j <= last(i)) & (table[(b * pl.num_programs(1) + i) * nk + j] > 0))
    def _():
        sel = mask_ref[0].astype(jnp.int32) != 0

        def head(h, psum):
            prob, ds = _backward_terms(h, group, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sel)
            dq_s[h] = dq_s[h] + K._kmm(ds, k_ref[0, h // group])
            return psum + prob

        psum = jax.lax.fori_loop(0, H, head, jnp.zeros(sel.shape, F32))
        di = _index_cotangent(psum, H, sel, qI_ref, kI_ref, w_ref, lse_i_ref, dkl_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_s.shape, 1)

        def index_head(jj, carry):
            w_j, a = _index_head(jj, qI_ref, kI_ref, w_ref)
            dqi_s[jj] = dqi_s[jj] + K._kmm(jnp.where(a > 0, di * w_j, 0.0), kI_ref[0])
            dw_s[...] += jnp.where(lane == jj, jnp.sum(di * jnp.maximum(a, 0.0), axis=1,
                                                       keepdims=True), 0.0)
            return carry

        _over_index_heads(index_head, 0, qI_ref)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)
        dqi_ref[0] = dqi_s[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_s[...]


def _dsa_dkv_kernel(table, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, mask_ref, qI_ref,
                    kI_ref, w_ref, lse_i_ref, dkl_ref, dk_ref, dv_ref, dki_ref,
                    dk_s, dv_s, dki_s, *, tq, tk, nq, nk):
    """A grid step (b, key tile j, query tile i) of ``dsa_dkv``: dk, dv and
    the indexer's dk, summed over the query tiles that meet a key tile."""
    b, j, i = (pl.program_id(n) for n in range(3))
    H, group = q_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]
    _last, first = _tile_bounds(tq, tk)

    @pl.when(i == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, F32)
        dv_s[...] = jnp.zeros(dv_s.shape, F32)
        dki_s[...] = jnp.zeros(dki_s.shape, F32)

    @pl.when((i >= first(j)) & (table[(b * nq + i) * nk + j] > 0))
    def _():
        sel = mask_ref[0].astype(jnp.int32) != 0

        def head(h, psum):
            g = h // group
            prob, ds = _backward_terms(h, group, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, sel)
            dv_s[g] = dv_s[g] + K._kmm(prob, do_ref[0, h], K._TN)
            dk_s[g] = dk_s[g] + K._kmm(ds, q_ref[0, h], K._TN)
            return psum + prob

        psum = jax.lax.fori_loop(0, H, head, jnp.zeros(sel.shape, F32))
        di = _index_cotangent(psum, H, sel, qI_ref, kI_ref, w_ref, lse_i_ref, dkl_ref)
        def index_head(jj, carry):
            w_j, a = _index_head(jj, qI_ref, kI_ref, w_ref)
            dki_s[...] += K._kmm(jnp.where(a > 0, di * w_j, 0.0), qI_ref[0, jj], K._TN)
            return carry

        _over_index_heads(index_head, 0, qI_ref)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)
        dki_ref[0] = dki_s[...].astype(dki_ref.dtype)


_DSA_VMEM = 100 * 1024 * 1024


def _lanes(x):
    """[B, n...] -> [B, n..., 128]: a row's value in every lane, the kernels'
    layout of a query's scalar."""
    return jnp.broadcast_to(x[..., None], x.shape + (128,)).astype(F32)


def _tiles(T: int):
    return (DSA_QUERY_TILE, DSA_KEY_TILE) if T % DSA_KEY_TILE == 0 else (T, T)


def _dsa_kernel_forward(q, k, v, mask, table, qI, kI, w, interpret: bool = False):
    """-> (o [B, H, T, d] bfloat16, kl [B, T], lse [B, H, T, 128], lse_i
    [B, T, 128]) by ``dsa_fwd``."""
    B, H, T, d = q.shape
    Hk, HI, dI = k.shape[1], qI.shape[1], qI.shape[-1]
    tq, tk = _tiles(T)
    nq, nk = T // tq, T // tk
    last, _first = _tile_bounds(tq, tk)
    kj = lambda i, j: jnp.minimum(j, last(i))
    spec = lambda shape, f: pl.BlockSpec(shape, f)
    in_specs = [
        spec((1, H, tq, d), lambda b, i, s, j, t: (b, 0, i, 0)),
        spec((1, Hk, tk, d), lambda b, i, s, j, t: (b, 0, kj(i, j), 0)),
        spec((1, Hk, tk, d), lambda b, i, s, j, t: (b, 0, kj(i, j), 0)),
        spec((1, tq, tk), lambda b, i, s, j, t: (b, i, kj(i, j))),
        spec((1, HI, tq, dI), lambda b, i, s, j, t: (b, 0, i, 0)),
        spec((1, tk, dI), lambda b, i, s, j, t: (b, kj(i, j), 0)),
        spec((1, tq, HI), lambda b, i, s, j, t: (b, i, 0))]
    row = lambda b, i, s, j, t: (b, i, 0)
    out_specs = [spec((1, H, tq, d), lambda b, i, s, j, t: (b, 0, i, 0)),
                 spec((1, H, tq, 128), lambda b, i, s, j, t: (b, 0, i, 0)),
                 spec((1, tq, 128), row), spec((1, tq, 128), row)]
    o, lse, kl, lse_i = pl.pallas_call(
        functools.partial(_dsa_fwd_kernel, tq=tq, tk=tk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, 2, nk), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((H, tq, 128), F32), pltpu.VMEM((H, tq, 128), F32),
                            pltpu.VMEM((H, tq, d), F32), pltpu.VMEM((tq, 128), F32),
                            pltpu.VMEM((tq, 128), F32), pltpu.VMEM((tq, 128), F32),
                            pltpu.VMEM((tq, 128), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, T, d), BF16),
                   jax.ShapeDtypeStruct((B, H, T, 128), F32),
                   jax.ShapeDtypeStruct((B, T, 128), F32),
                   jax.ShapeDtypeStruct((B, T, 128), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_DSA_VMEM),
        name="dsa_fwd", interpret=interpret,
    )(table.reshape(-1), q, k, v, mask, qI, kI, w)
    return o, kl[..., 0], lse, lse_i


def _dsa_kernel_backward(q, k, v, mask, table, qI, kI, w, o, lse, lse_i, do, dkl,
                         interpret: bool = False):
    """-> (dq, dk, dv, dqI, dkI, dw) by ``dsa_dq`` and ``dsa_dkv``."""
    B, H, T, d = q.shape
    Hk, HI, dI = k.shape[1], qI.shape[1], qI.shape[-1]
    tq, tk = _tiles(T)
    nq, nk = T // tq, T // tk
    last, first = _tile_bounds(tq, tk)
    dkl = _lanes(dkl)
    args = (table.reshape(-1), q, k, v, o, do, lse, mask, qI, kI, w, lse_i, dkl)
    params = lambda sem: pltpu.CompilerParams(dimension_semantics=sem,
                                              vmem_limit_bytes=_DSA_VMEM)

    def specs(qi, kj):
        """The inputs' blocks, given the query and key tiles of a step."""
        return [
            pl.BlockSpec((1, H, tq, d), lambda b, x, y, t: (b, 0, qi(x, y), 0)),
            pl.BlockSpec((1, Hk, tk, d), lambda b, x, y, t: (b, 0, kj(x, y), 0)),
            pl.BlockSpec((1, Hk, tk, d), lambda b, x, y, t: (b, 0, kj(x, y), 0)),
            pl.BlockSpec((1, H, tq, d), lambda b, x, y, t: (b, 0, qi(x, y), 0)),
            pl.BlockSpec((1, H, tq, d), lambda b, x, y, t: (b, 0, qi(x, y), 0)),
            pl.BlockSpec((1, H, tq, 128), lambda b, x, y, t: (b, 0, qi(x, y), 0)),
            pl.BlockSpec((1, tq, tk), lambda b, x, y, t: (b, qi(x, y), kj(x, y))),
            pl.BlockSpec((1, HI, tq, dI), lambda b, x, y, t: (b, 0, qi(x, y), 0)),
            pl.BlockSpec((1, tk, dI), lambda b, x, y, t: (b, kj(x, y), 0)),
            pl.BlockSpec((1, tq, HI), lambda b, x, y, t: (b, qi(x, y), 0)),
            pl.BlockSpec((1, tq, 128), lambda b, x, y, t: (b, qi(x, y), 0)),
            pl.BlockSpec((1, tq, 128), lambda b, x, y, t: (b, qi(x, y), 0))]

    # grid (b, query tile, key tile): the key tile held at the diagonal's
    qi, kj = (lambda i, j: i), (lambda i, j: jnp.minimum(j, last(i)))
    dq, dqI, dw = pl.pallas_call(
        functools.partial(_dsa_dq_kernel, tq=tq, tk=tk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, nk), in_specs=specs(qi, kj),
            out_specs=[pl.BlockSpec((1, H, tq, d), lambda b, i, j, t: (b, 0, i, 0)),
                       pl.BlockSpec((1, HI, tq, dI), lambda b, i, j, t: (b, 0, i, 0)),
                       pl.BlockSpec((1, tq, HI), lambda b, i, j, t: (b, i, 0))],
            scratch_shapes=[pltpu.VMEM((H, tq, d), F32), pltpu.VMEM((HI, tq, dI), F32),
                            pltpu.VMEM((tq, HI), F32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(qI.shape, qI.dtype),
                   jax.ShapeDtypeStruct(w.shape, F32)],
        compiler_params=params(("parallel", "parallel", "arbitrary")),
        name="dsa_dq", interpret=interpret,
    )(*args)
    # grid (b, key tile, query tile): the query tile held at the diagonal's
    qi, kj = (lambda j, i: jnp.maximum(i, first(j))), (lambda j, i: j)
    dk, dv, dkI = pl.pallas_call(
        functools.partial(_dsa_dkv_kernel, tq=tq, tk=tk, nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nk, nq), in_specs=specs(qi, kj),
            out_specs=[pl.BlockSpec((1, Hk, tk, d), lambda b, j, i, t: (b, 0, j, 0)),
                       pl.BlockSpec((1, Hk, tk, d), lambda b, j, i, t: (b, 0, j, 0)),
                       pl.BlockSpec((1, tk, dI), lambda b, j, i, t: (b, j, 0))],
            scratch_shapes=[pltpu.VMEM((Hk, tk, d), F32), pltpu.VMEM((Hk, tk, d), F32),
                            pltpu.VMEM((tk, dI), F32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(kI.shape, kI.dtype)],
        compiler_params=params(("parallel", "parallel", "arbitrary")),
        name="dsa_dkv", interpret=interpret,
    )(*args)
    return dq, dk, dv, dqI, dkI, dw


def _dsa_forward(q, k, v, mask, table, qI, kI, w, interpret: bool):
    """(o, kl, lse [B, H, T], lse_i [B, T], 1.0 where the kernel ran): the
    kernel where the program is lowered for a TPU, the blocked path
    elsewhere. The kernel writes a log-sum-exp in every lane; lane 0 is
    kept."""
    def fused(*xs):
        o, kl, lse, lse_i = _dsa_kernel_forward(*xs, interpret=interpret)
        return o, kl, lse[..., 0], lse_i[..., 0], jnp.float32(1.0)

    if interpret:
        return fused(q, k, v, mask, table, qI, kI, w)

    def blocked(q, k, v, mask, _table, qI, kI, w):
        return _dsa_blocked(q, k, v, mask, qI, kI, w) + (jnp.float32(0.0),)

    return jax.lax.platform_dependent(q, k, v, mask, table, qI, kI, w,
                                      tpu=fused, default=blocked)


@functools.partial(jax.custom_vjp, nondiff_argnums=(12,))
def _dsa_attend(q, k, v, mask, table, qI, kI, w, o, kl, lse, lse_i, interpret=False):
    """(o, kl) that ``_dsa_forward`` computed from these inputs, with their
    gradients: the two backward kernels from o and the log-sum-exps, or
    ``jax.vjp`` of the blocked path. No gradient reaches the forward's
    outputs themselves."""
    return o, kl


def _dsa_attend_fwd(q, k, v, mask, table, qI, kI, w, o, kl, lse, lse_i, interpret):
    return (o, kl), (q, k, v, mask, table, qI, kI, w, o, lse, lse_i)


def _dsa_attend_bwd(interpret, saved, cts):
    do, dkl = cts
    q, k, v, mask, table, qI, kI, w, o, lse, lse_i = saved

    def fused(q, k, v, mask, table, qI, kI, w, o, lse, lse_i, do, dkl):
        return _dsa_kernel_backward(q, k, v, mask, table, qI, kI, w, o, _lanes(lse),
                                    _lanes(lse_i), do, dkl, interpret=interpret)

    def blocked(q, k, v, mask, _table, qI, kI, w, _o, _lse, _lse_i, do, dkl):
        run = lambda q, k, v, qI, kI, w: _dsa_blocked(q, k, v, mask, qI, kI, w)[:2]
        return jax.vjp(run, q, k, v, qI, kI, w)[1]((do, dkl))

    args = (q, k, v, mask, table, qI, kI, w, o, lse, lse_i, do, dkl)
    if interpret:
        grads = fused(*args)
    else:
        grads = jax.lax.platform_dependent(*args, tpu=fused, default=blocked)
    dq, dk, dv, dqI, dkI, dw = grads
    return (dq, dk, dv, None, None, dqI, dkI, dw.astype(w.dtype)) + (None,) * 4


_dsa_attend.defvjp(_dsa_attend_fwd, _dsa_attend_bwd)


def _dsa_fused(q, k, v, mask, table, qI, kI, w, interpret=False):
    """(o, kl, 1.0 where the kernel ran) at the kernels' widths. The forward
    runs by itself on inputs that carry no gradient, and its o and
    log-sum-exps are named (``DSA_KEPT``) before ``_dsa_attend`` takes them
    for the backward pass: a layer rematerialised under ``KEEP_FORWARD``
    keeps them and does not run the forward again."""
    o, kl, lse, lse_i, fused = _dsa_forward(
        *(jax.lax.stop_gradient(x) for x in (q, k, v, mask, table, qI, kI, w)), interpret)
    o, lse, lse_i = (checkpoint_name(x, name) for x, name in zip((o, lse, lse_i), DSA_KEPT))
    o, kl = _dsa_attend(q, k, v, mask, table, qI, kI, w, o, kl, lse, lse_i, interpret)
    return o, kl, fused


def kernel_path(T: int, d: int, dI: int) -> bool:
    """Whether ``dsa_attention`` takes ``_dsa_fused`` at a length ``T``,
    heads ``d`` wide and indexer heads ``dI`` wide (the kernels where the
    program is lowered for a TPU)."""
    return T % DSA_KEY_TILE == 0 and d == DSA_HEAD_DIM and dI == DSA_INDEX_DIM


@jax.jit
def dsa_attention(q, k, v, mask, counts, qI, kI, w):
    """Softmax attention over each query's selected keys, and the indexer's
    loss. ``q`` [B, T, H, d], ``k``, ``v`` [B, T, Hk, d] (Hk divides H),
    ``mask`` [B, T, T] and ``counts`` from ``dsa_select``, ``qI`` [B, T, HI,
    dI], ``kI`` [B, T, dI], ``w`` [B, T, HI] (the 1 / sqrt(dI) in it).
    Returns (o [B, T, H, d] bfloat16; the indexer's loss of each token
    [B, T]: KL(p || softmax over the selection of I), p the head-averaged
    probabilities held constant, whose gradient reaches ``qI``, ``kI`` and
    ``w`` alone; 1.0 where the kernels computed it and 0.0 where the blocked
    code did). The kernels run where the program is lowered for a TPU, at
    heads ``DSA_HEAD_DIM`` and ``DSA_INDEX_DIM`` wide and a length
    ``DSA_KEY_TILE`` divides; decided as ``kimi_linear.mla_attention``
    decides."""
    _B, T, _H, d = q.shape
    _B, _T, _HI, dI = qI.shape
    q = (q.astype(F32) * (1.0 / np.sqrt(d))).astype(BF16)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)
    args = (heads_major(q), heads_major(k.astype(BF16)), heads_major(v.astype(BF16)), mask,
            (counts > 0).astype(jnp.int32), heads_major(qI.astype(BF16)), kI.astype(BF16),
            w.astype(F32))
    if kernel_path(T, d, dI):
        o, kl, fused = _dsa_fused(*args)
    else:
        o, kl, _lse, _lse_i = _dsa_blocked(*args[:4], *args[5:])
        fused = jnp.float32(0.0)
    return heads_major(o), kl, fused


# -- the module ----------------------------------------------------------------

def layer_norm(x, scale, bias, eps: float = LN_EPS):
    x = x.astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def index_inputs(x, w_q, w_k, k_norm, k_bias, w_w, heads: int, theta: float):
    """The indexer's queries [B, T, HI, dI] and key [B, T, dI] (bfloat16,
    rotated) and weights [B, T, HI] (float32, the 1 / sqrt(HI dI) in them)
    from the attention half's normed input ``x``, which no gradient leaves
    through here."""
    B, T, _D = x.shape
    u = jax.lax.stop_gradient(x)
    dI = w_k.shape[-1]
    qI = rope(K._mm(u, w_q, "btd,de->bte").reshape(B, T, heads, dI), theta)
    kI = layer_norm(K._mm(u, w_k, "btd,de->bte"), k_norm, k_bias)
    kI = rope(kI[:, :, None, :], theta)[:, :, 0]
    w = jnp.einsum("btd,dj->btj", u, w_w,
                   precision=jax.lax.Precision.HIGHEST) * (1.0 / np.sqrt(heads * dI))
    return qI.astype(BF16), kI.astype(BF16), w


class _Attn(nn.Module):
    """The attention half: projections, norms, rotations, the indexer, the
    selection, the sparse attention. Returns (W_o o [B, T, D], the indexer's
    loss of every token [B, T], 1.0 where the kernels ran, [selected keys,
    tiles visited, tiles])."""

    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    index_heads: int
    index_dim: int
    topk: int

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        H, Hk, d, HI, dI = self.heads, self.kv_heads, self.head_dim, self.index_heads, self.index_dim
        p = lambda name, shape, init=K._dense_init(): self.param(name, init, shape)
        with jax.named_scope(SCOPE_ATTN):
            q = K._mm(x, p("w_q", (D, H * d)), "btd,de->bte").reshape(B, T, H, d)
            k = K._mm(x, p("w_k", (D, Hk * d)), "btd,de->bte").reshape(B, T, Hk, d)
            v = K._mm(x, p("w_v", (D, Hk * d)), "btd,de->bte", BF16).reshape(B, T, Hk, d)
            q = rope(K.rms_norm(q, p("q_norm", (d,), nn.initializers.ones), self.eps), self.theta)
            k = rope(K.rms_norm(k, p("k_norm", (d,), nn.initializers.ones), self.eps), self.theta)
        with jax.named_scope(SCOPE_INDEX):
            qI, kI, w = index_inputs(
                x, p("index_q", (D, HI * dI)), p("index_k", (D, dI)),
                p("index_k_norm", (dI,), nn.initializers.ones),
                p("index_k_bias", (dI,), nn.initializers.zeros), p("index_w", (D, HI)),
                HI, self.theta)
        with jax.named_scope(SCOPE_SELECT):
            mask, counts = dsa_select(*(jax.lax.stop_gradient(a) for a in (qI, kI, w)), self.topk)
        with jax.named_scope(SCOPE_ATTN):
            o, kl, fused = dsa_attention(q.astype(BF16), k.astype(BF16), v, mask, counts,
                                         qI, kI, w)
            out = K._mm(o.reshape(B, T, H * d), p("w_o", (H * d, D)), "bte,ed->btd", BF16)
        stats = jnp.stack([jnp.sum(counts).astype(F32), jnp.sum(counts > 0).astype(F32),
                           jnp.float32(counts.size)])
        return out, kl, fused, stats


class _Moe(nn.Module):
    experts: int            # the router's outputs (published)
    top_k: int
    held: tuple             # expert ids whose weights live here
    width: int

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        E = len(self.held)
        p = lambda name, shape: self.param(name, K._dense_init(), shape)
        flat = x.reshape(B * T, D)
        with jax.named_scope(K.SCOPE_ROUTE):
            ids, weights = K.route(flat, p("w_router", (D, self.experts)), 0.0, self.top_k,
                                   1.0, score_function=jax.nn.softmax)
        with jax.named_scope(K.SCOPE_EXPERTS):
            y, sizes = K.expert_layer(
                flat, ids, weights, self.held, p("w_gate", (E, D, self.width)),
                p("w_up", (E, D, self.width)), p("w_down", (E, self.width, D)))
        return y.reshape(B, T, D), sizes


class _Layer(nn.Module):
    cfg: Any            # a hashable tuple of (key, value) pairs

    @nn.compact
    def __call__(self, h):
        c = dict(self.cfg)
        eps = c["rms_norm_eps"]
        norm = lambda name: self.param(name, nn.initializers.ones, (h.shape[-1],))
        m, kl, fused, stats = _Attn(
            c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["rope_theta"],
            eps, c["indexer_num_heads"], c["indexer_head_dim"], c["topk"], name="attn")(
            K.rms_norm(h, norm("norm_attn"), eps))
        h = h + m.astype(h.dtype)
        y, load = _Moe(c["num_experts"], c["num_experts_per_tok"], tuple(c["experts_held"]),
                       c["moe_intermediate_size"], name="moe")(K.rms_norm(h, norm("norm_ffn"), eps))
        return h + y.astype(h.dtype), load, kl, fused, stats


class _KeyeVl2(nn.Module):
    """x [B, T] token ids -> the next token's logits after the last one
    given [B, V]; with ``hidden``, (hidden states after the final norm
    [B, T, D] in bfloat16, the untied head [D, V], rows each held expert
    took in each layer [layers, E], {"kl": the indexer's loss of every token
    summed over the layers [B, T], "fused": layers the kernels computed,
    "stats": selected keys, tiles visited, tiles, summed over the layers,
    "kept": layers whose attention output and log-sum-exps the backward pass
    keeps, ``KEEP_FORWARD``: all of them in training at the kernels' widths
    and length, fixed when the program is traced})."""

    cfg: Any
    vocab: int

    def layer_kinds(self):
        return [("dsa", True)] * dict(self.cfg)["num_hidden_layers"]

    @nn.compact
    def __call__(self, x, train: bool = False, hidden: bool = False):
        c = dict(self.cfg)
        D = c["hidden_size"]
        embed = self.param("embed", K._dense_init(), (self.vocab, D))
        head = self.param("head", K._dense_init(), (D, self.vocab))
        h = jnp.take(embed, x, axis=0).astype(F32)
        loads, kl, fused, stats = [], 0.0, jnp.float32(0.0), jnp.zeros((3,), F32)
        layer = nn.remat(_Layer, policy=KEEP_FORWARD) if train else _Layer
        kept = train and kernel_path(x.shape[1], c["head_dim"], c["indexer_head_dim"])
        for i in range(c["num_hidden_layers"]):
            h, load, kl_i, fused_i, stats_i = layer(self.cfg, name=f"layer_{i + 1}")(h)
            loads.append(load)
            kl, fused, stats = kl + kl_i, fused + fused_i, stats + stats_i
        h = K.rms_norm(h, self.param("norm_out", nn.initializers.ones, (D,)),
                       c["rms_norm_eps"]).astype(BF16)
        if hidden:
            return h, head, jnp.stack(loads), {
                "kl": kl, "fused": fused, "stats": stats,
                "kept": jnp.float32(c["num_hidden_layers"] if kept else 0)}
        # Serving: the next token's distribution after the last one given.
        return K._mm(h[:, -1], head, "bd,dv->bv")


class KeyeVl2(K.SparseExpertLm):
    """The template. Shape knobs default to a size a CPU trains in seconds
    (``topk`` small enough that the selection bites); a tenant's model file
    pins them (the benchmark's configuration pins the published widths).
    ``expert_shard`` of ``expert_shards`` says which experts this chip holds:
    ids ``expert_shard * (num_experts // expert_shards)`` onward."""

    TOP_K_KNOB = "num_experts_per_tok"

    @staticmethod
    def get_knob_config():
        fixed = lambda v: FixedKnob(v, affects_shape=True)
        return {
            "hidden_size": fixed(64), "num_attention_heads": fixed(4),
            "num_key_value_heads": fixed(2), "head_dim": fixed(16), "rope_theta": fixed(1e7),
            "indexer_num_heads": fixed(4), "indexer_head_dim": fixed(8), "topk": fixed(16),
            "moe_intermediate_size": fixed(32), "num_experts": fixed(16),
            "num_experts_per_tok": fixed(4), "expert_shards": fixed(4), "expert_shard": fixed(0),
            "num_hidden_layers": fixed(2), "rms_norm_eps": fixed(1e-6),
            "learning_rate": FloatKnob(3e-5, 1e-3, is_exp=True),
            "label_smoothing": FloatKnob(0.0, 0.1),
            "batch_size": fixed(2), "epochs": FixedKnob(1), "seed": FixedKnob(0),
        }

    def module_config(self) -> tuple:
        kn = self.knobs
        per = int(kn["num_experts"]) // int(kn["expert_shards"])
        first = int(kn["expert_shard"]) * per
        c = {k: kn[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "indexer_num_heads", "indexer_head_dim", "topk",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "num_hidden_layers", "rms_norm_eps")}
        c["experts_held"] = tuple(range(first, first + per))
        return tuple(sorted(c.items()))

    def build_module(self, num_classes, input_shape):
        return _KeyeVl2(cfg=self.module_config(), vocab=int(num_classes))

    def _kernel_counts(self, mixers, fused):
        stats = fused["stats"]
        return {"count.dsa.layers": jnp.float32(len(mixers)),
                "count.dsa.fused": fused["fused"],
                "count.dsa.forward_kept": fused["kept"],
                "count.dsa.keys_selected": stats[0],
                "count.dsa.tiles_visited": stats[1],
                "count.dsa.tiles_total": stats[2]}

    def _loss_and_count(self, module):
        """``SparseExpertLm``'s loss and counts, with the indexer's loss (the
        mean over tokens of its KL, summed over the layers) added under
        ``dsa.kl`` and logged as ``index_loss``."""
        sparse_layers = len(module.layer_kinds())
        mixers = [mixer for mixer, _sp in module.layer_kinds()]
        top_k, width = int(self.knobs[self.TOP_K_KNOB]), int(self.knobs["moe_intermediate_size"])

        def stats(params, batch, train, smoothing):
            h, head, loads, extra = module.apply({"params": params}, batch["x"],
                                                 train=train, hidden=True)
            with jax.named_scope(K.SCOPE_LM_LOSS):
                # a block after the other (``lax.map``): 32 blocks of 1,024 of a
                # 32k sequence unrolled made the step program's code 468 MB
                ce, hits, n = (jnp.sum(a, dtype=d) for a, d in zip(
                    K.blocked_logit_stats(h, head, batch["y"], smoothing, per_token=True),
                    (F32, jnp.int32, jnp.int32)))
            return ce, hits, n, loads, extra

        def loss_fn(params, batch, rng, hyper):
            ce, hits, n, loads, extra = stats(params, batch, True, hyper["label_smoothing"])
            n = jnp.maximum(n, 1)
            with jax.named_scope(SCOPE_KL):
                index_loss = jnp.mean(extra["kl"])
            loads = loads.astype(F32)
            skew = jnp.max(loads, axis=-1) / jnp.maximum(jnp.mean(loads, axis=-1), 1.0)
            slots = top_k * batch["x"].size
            slab = K._slab_rows(slots, loads.shape[-1], width)
            return ce / n + index_loss, {
                "acc": hits / n, "index_loss": index_loss,
                "count.moe.slots_held": loads.sum(),
                "count.moe.rows_room": (jnp.ceil(loads.sum(axis=-1) / slab) * slab).sum(),
                "count.moe.slots_total": jnp.float32(slots * sparse_layers),
                "gauge.moe.held_load_max_over_mean": skew.mean(),
                **self._kernel_counts(mixers, extra)}

        def eval_count(params, batch):
            _ce, hits, n, _loads, _extra = stats(params, batch, False, 0.0)
            return hits, n

        return loss_fn, eval_count


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.keye_vl2`): an explicit CPU
    # request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    _fixed = {k: v.value for k, v in KeyeVl2.get_knob_config().items()
              if isinstance(v, FixedKnob)}
    test_model_class(
        KeyeVl2, "LANGUAGE_MODELING",
        "synthetic://tokens?vocab=256&n=16&len=96&seed=0",
        "synthetic://tokens?vocab=256&n=4&len=96&seed=1",
        queries=[[5, 9, 3] * 8, [17, 2] * 12],
        knobs=dict(_fixed, learning_rate=1e-3, label_smoothing=0.05),
    )
