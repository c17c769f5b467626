"""Hybrid linear-attention sparse-expert language model template.

No reference analog: the reference zoo stops at CNNs and a BiLSTM
tagger. The block is the one published for Kimi Linear
(arXiv:2510.26692; ``model_type`` ``kimi_linear``): pre-norm residual
layers whose token mixer is either *KDA* — a gated delta-rule linear
attention with a per-channel decay — or *MLA* — multi-head latent
attention without positions — and whose feed-forward part is dense in
the leading layers and a sigmoid top-k router over sparse experts, plus
one shared expert, in the rest.

What the template adds to the zoo, by mechanism:

* ``kda_chunked``: the delta rule in its chunked (WY / UT-transform)
  form. Within a chunk the rank-one updates are folded into one unit
  lower-triangular system, inverted by block substitution in log depth
  (matrix products only); across chunks the ``[d_k, d_v]`` state is
  carried. Lowered for a TPU, at chunk 64, heads 128 wide and a length
  two chunks divide: one Pallas kernel a pass (``kda_chunk_fwd``, and
  ``kda_chunk_bwd`` for the five gradients), a head's chunks one after
  the other, two a grid step, with the state in VMEM, so that only q, k,
  v, a, beta, the result and the state each chunk started from touch HBM
  (two chunks a step because the float32 solve is bound by MXU passes,
  and two chunks' [64, 64] tiles side by side fill one). Everywhere
  else (the CPU, chunk 16, other widths, a ragged length): plain
  ``jax.numpy``, a ``lax.scan`` over groups of chunks. ``count.kda.fused``
  of ``count.kda.layers`` says which ran. (The recurrence token by token
  is the benchmark's reference, ``benchmark/references/kimi_linear.py``;
  tests compare all three.)
* ``mla_attention``: causal softmax attention over latent-projected
  keys and values (queries and keys 192 wide, values 128). Lowered for a
  TPU, at a length ``KERNEL_BLOCK`` divides: one fused kernel a pass (the
  library's Pallas splash attention: online softmax, the score tile in
  VMEM, nothing above the diagonal visited, the backward pass from the
  saved log-sum-exp). Everywhere else (the CPU, any other length): plain
  ``jax.numpy``, a block of queries at a time so that no ``[T, T]`` score
  array outlives its block. ``count.mla.fused`` of ``count.mla.layers``
  says which ran.
* ``expert_layer``: the router keeps every output and its experts per
  token; the layer is *told which expert ids it holds* and computes
  their part of the result for the tokens routed to them, without a
  capacity limit: all tokens x k token-choices are sorted once by the
  expert they name, the live ones first, and those are taken a slab of
  rows at a time, only while there are live ones (gather, three ragged
  products, a scatter-add by token), so the layer moves the rows its
  experts hold and not every token once a choice. What absent experts
  would add is left out. ``count.moe.slots_held`` of
  ``count.moe.rows_room`` is the live share of the rows the slabs moved.
* the loss and the score are taken a block of the sequence at a time, so
  the ``[tokens, vocab]`` logits never exist whole; every layer is
  recomputed in the backward pass (``nn.remat``).

TPU notes: matrix products take bfloat16 operands and accumulate in
float32; parameters, the recurrent state, decays, normalisations, the
router and the softmaxes stay float32 (in the attention kernel: the
running maximum, sum and accumulator; in the chunk kernels: the running
sum of the log-decay, its factors, the triangular solve at full precision
and the state). Which attention and which chunk rule run is decided when a
program is lowered, by the platform it is lowered for
(``lax.platform_dependent``), so a compile on a CPU host for a described
chip holds the kernels; no environment variable or knob enters. The scan
on a TPU writes fifteen times its inputs to HBM as intermediates, in some
sixty small programs a group (0.93 s of a 1.9 s step at 2 x 8,192 tokens:
PERF.md, PR 30). The blocked attention on a TPU writes each block's float32
scores to HBM three times a pass (2.2 s of a 3.9 s step at 2 x 8,192 tokens
against 0.07 s in the kernel's four calls: PERF.md, PR 28). Sequences are fixed length, one
document a sequence (`synthetic://tokens`). A trial of this template
fills a chip by itself, so it is not packable; it runs in the serial
lane.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)

from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob

F32 = jnp.float32
L2_EPS = 1e-6        # l2norm's epsilon (assumed; FLA's kernels use 1e-6)
EXP_CLIP = 80.0      # |log-decay| a chunk half may span before float32 overflows
LOSS_BLOCK = 1024    # tokens of a sequence whose logits exist at one time
ATTN_BLOCK = 256     # queries whose scores exist at one time (the blocked path)
KERNEL_BLOCK = 1024  # queries and keys of one tile of the fused attention kernel
KDA_GROUP = 16       # chunks whose insides exist at one time
KDA_KERNEL_CHUNK = 64    # the chunk and the head width (keys and values alike)
KDA_KERNEL_WIDTH = 128   # that the fused chunk kernel is written for
KDA_BRANCH_BLOCK = 1024  # tokens of a head's block in the branch kernels
KDA_BRANCH_HALO = 16     # rows read on either side of it: a bfloat16 tile
BF16 = jnp.bfloat16

# Named scopes of the block (docs/telemetry.md), beside ops/train.py's
# ``rafiki.*`` ones. Metadata only.
SCOPE_KDA, SCOPE_MLA = "kda", "mla"
SCOPE_ROUTE, SCOPE_EXPERTS, SCOPE_SHARED = "moe.route", "moe.experts", "moe.shared"
SCOPE_LM_LOSS = "lm.loss"


def _mm(a, b, spec: str, out=F32):
    """A matrix product with bfloat16 operands, accumulated in float32;
    ``out``: the dtype it is kept in (bfloat16 for the wide activations
    that live until the backward pass)."""
    return jnp.einsum(spec, a.astype(BF16), b.astype(BF16),
                      preferred_element_type=F32).astype(out)


def rms_norm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def l2norm(x):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# -- KDA: the gated delta rule -------------------------------------------------

def unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower-triangular ``A`` [..., C, C], C a
    power of two: block forward substitution in log2(C) levels. A level
    pairs the inverted diagonal blocks of the one before,
    [[X, 0], [M, Y]]^-1 = [[X^-1, 0], [-Y^-1 M X^-1, Y^-1]], so the whole
    is matrix products (float32 at full precision) and no loop over rows."""
    C = A.shape[-1]
    lead = A.shape[:-2]
    hi = jax.lax.Precision.HIGHEST
    inv = jnp.ones(lead + (C, 1, 1), F32)           # 1x1 blocks of the unit diagonal
    b = 1
    while b < C:
        nb = C // (2 * b)
        blocks = jnp.moveaxis(jnp.diagonal(
            A.reshape(lead + (nb, 2 * b, nb, 2 * b)), axis1=-4, axis2=-2), -1, -3)
        m21 = blocks[..., b:, :b]
        pair = inv.reshape(lead + (nb, 2, b, b))
        x, y = pair[..., 0, :, :], pair[..., 1, :, :]
        low = -jnp.matmul(jnp.matmul(y, m21, precision=hi), x, precision=hi)
        top = jnp.concatenate([x, jnp.zeros_like(x)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([low, y], axis=-1)], axis=-2)
        b *= 2
    return inv.reshape(lead + (C, C))


def _kda_group_size(n: int) -> int:
    return next(x for x in range(min(KDA_GROUP, n), 0, -1) if n % x == 0)


def _kda_grouped(x, C: int):
    """[B, T, H, d] (or [B, T, H]) -> [n / G, G, B, H, C, d] (d = 1)."""
    if x.ndim == 3:
        x = x[..., None]
    B, T, H, d = x.shape
    n = T // C
    x = jnp.transpose(x.reshape(B, n, C, H, d), (1, 0, 3, 2, 4))
    return x.reshape((n // _kda_group_size(n), -1) + x.shape[1:])


def _kda_ungrouped(x):
    """[n / G, G, B, H, C, d] -> [B, T, H, d]."""
    _ng, _G, B, H, C, d = x.shape
    return jnp.transpose(x.reshape((-1,) + x.shape[2:]), (1, 0, 3, 2, 4)).reshape(B, -1, H, d)


def _kda_group(C: int):
    """One group's pass as a function (S, (q, k, v, a, beta) of the group's
    chunks) -> (S after them, (o, the state each chunk started from)): what
    is parallel over chunks for all of them at once, then the state through
    them one by one."""
    row, col = np.arange(C)[:, None], np.arange(C)[None, :]

    def step(S, xs):
        w, u0, aqk, qe, kt, dc = xs
        start = S
        u = u0 - _mm(w, S, "bhtk,bhkv->bhtv")
        o = _mm(qe, S, "bhtk,bhkv->bhtv") + _mm(aqk, u, "bhts,bhsv->bhtv")
        S = S * dc + _mm(kt, u, "bhtk,bhtv->bhkv")
        return S, (o, start)

    def group(S, xs):
        q, k, v, a, beta = (x.astype(F32) for x in xs)
        g = jnp.cumsum(a, axis=-2)                         # <= 0, falling
        mid = g[..., C // 2 - 1: C // 2, :]
        up = jnp.exp(jnp.minimum(g - mid, EXP_CLIP))       # e^(g_t - g_mid)
        down = jnp.exp(jnp.minimum(mid - g, EXP_CLIP))     # e^(g_mid - g_s)
        eg = jnp.exp(g)
        k_down = k * down
        A = jnp.where(row > col, _mm(beta * k * up, k_down, "...td,...sd->...ts"), 0.0)
        Tm = unit_lower_inverse(A)
        W = _mm(Tm, beta * k * eg, "...ts,...sd->...td")   # [G, B, H, C, dk]
        U0 = _mm(Tm, beta * v, "...ts,...sd->...td")       # [G, B, H, C, dv]
        Aqk = jnp.where(row >= col, _mm(q * up, k_down, "...td,...sd->...ts"), 0.0)
        g_last = g[..., -1:, :]
        k_tail = k * jnp.exp(g_last - g)                   # e^(g_C - g_s) <= 1
        decay = jnp.swapaxes(jnp.exp(g_last), -1, -2)      # [G, B, H, dk, 1]
        return jax.lax.scan(step, S, (W, U0, Aqk, q * eg, k_tail, decay))

    return group


def _kda_scan(q, k, v, a, beta, C: int):
    """The chunked rule in ``jax.numpy``, a length ``C`` divides: a scan
    over groups of ``KDA_GROUP`` chunks, each recomputed in the backward
    pass, so that only the state at group boundaries and the layer's q, k,
    v, a, beta outlive a group. Returns (o [B, T, H, dv] in float32, the
    state each chunk started from [n / G, G, B, H, dk, dv])."""
    B, _T, H, dk = q.shape
    _S, (o, starts) = jax.lax.scan(
        jax.checkpoint(_kda_group(C)), jnp.zeros((B, H, dk, v.shape[-1]), F32),
        tuple(_kda_grouped(x, C) for x in (q, k, v, a, beta)))
    return _kda_ungrouped(o), starts


def _kda_scan_backward(q, k, v, a, beta, starts, ct, C: int):
    """The five gradients under ``ct`` from the states the groups started
    from [n / G, B, H, dk, dv]: the groups in reverse, each recomputed and
    differentiated, which is what ``_kda_scan``'s own backward pass does."""
    group = _kda_group(C)

    def back(dS, xs):
        S, inputs, d_o = xs
        (_after, (_o, inside)), vjp = jax.vjp(group, S, inputs)
        return vjp((dS, (d_o, jnp.zeros_like(inside))))

    _dS, grads = jax.lax.scan(
        back, jnp.zeros(starts.shape[1:], F32),
        (starts, tuple(_kda_grouped(x, C) for x in (q, k, v, a, beta)),
         _kda_grouped(ct.astype(F32), C)), reverse=True)
    dq, dk, dv, da, dbeta = (_kda_ungrouped(x) for x in grads)
    return dq, dk, dv, da, dbeta[..., 0]


_NN, _NT, _TN = ((((1,), (0,)), ((), ())), (((1,), (1,)), ((), ())),
                 (((0,), (0,)), ((), ())))    # x y, x y^T, x^T y


def _kmm(x, y, dims=_NN):
    """``_mm`` on a kernel's tiles: bfloat16 operands, a float32 sum."""
    return jax.lax.dot_general(x.astype(BF16), y.astype(BF16), dims,
                               preferred_element_type=F32)


def _kmm32(x, y, dims=_NN):
    """A float32 product at full precision on a kernel's tiles."""
    return jax.lax.dot_general(x, y, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _side_by_side(x):
    """A block-diagonal [2C, 2C] tile's two blocks as one [C, 2C] tile."""
    C = KDA_KERNEL_CHUNK
    left = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1) < C
    return jnp.where(left, x[:C], x[C:])


def _block_diagonal(x):
    """``_side_by_side``'s inverse: no lane moves either way."""
    C = KDA_KERNEL_CHUNK
    left = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1) < C
    return jnp.concatenate([jnp.where(left, x, 0.0), jnp.where(left, 0.0, x)], axis=0)


def _kda_block_terms(q, k, v, a, beta):
    """``_kda_group``'s lines before its scan for one head's block of two
    chunks, on [2C, d] tiles in VMEM (``beta`` [2C, 1]): a product over the
    block's tokens holds both chunks' [C, C] results as its diagonal blocks
    (what pairs tokens of different chunks is masked away), and the
    triangular solve runs on the two side by side in one [C, 2C] tile, so
    that an MXU pass serves two chunks. Everything either kernel needs of
    the block, by name."""
    C = KDA_KERNEL_CHUNK
    N, bits = 2 * C, C.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
    same = (row >> bits) == (col >> bits)                  # tokens of one chunk
    at = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    g = _kmm32((same & (row >= col)).astype(F32), a)       # a chunk's running sum of a
    of_chunk = lambda r: jnp.where(at < C, g[r: r + 1], g[C + r: C + r + 1])
    mid, g_last = of_chunk(C // 2 - 1), of_chunk(C - 1)
    up = jnp.exp(jnp.minimum(g - mid, EXP_CLIP))
    down = jnp.exp(jnp.minimum(mid - g, EXP_CLIP))
    eg, tail = jnp.exp(g), jnp.exp(g_last - g)
    kb = beta * k
    ku, kd, ke, vb = kb * up, k * down, kb * eg, beta * v
    A = _side_by_side(jnp.where(same & (row > col), _kmm(ku, kd, _NT), 0.0))
    # (I + A)^-1 by ``unit_lower_inverse``'s levels on whole tiles: a level's
    # inverse is block diagonal, so inv M inv, with M the blocks of A below
    # the diagonal that the level pairs, is every pair's Y^-1 M X^-1.
    r = jax.lax.broadcasted_iota(jnp.int32, (C, N), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (C, N), 1) & (C - 1)
    inv = jnp.where(r == c, 1.0, 0.0) - jnp.where((r == c + 1) & ((r & 1) == 1), A, 0.0)
    for level in range(1, bits):                           # blocks of 2, 4, ... C / 2
        b = 1 << level
        below = ((r >> (level + 1)) == (c >> (level + 1))) & ((r & b) != 0) & ((c & b) == 0)
        inv = inv - _kmm32(_kmm32(inv, _block_diagonal(jnp.where(below, A, 0.0))),
                           _block_diagonal(inv))
    T = _block_diagonal(inv)
    qu, qe, kt = q * up, q * eg, k * tail
    Aqk = jnp.where(same & (row >= col), _kmm(qu, kd, _NT), 0.0)
    return dict(row=row, col=col, same=same, at=at, g=g, mid=mid, up=up, down=down, eg=eg,
                tail=tail, decay=[jnp.exp(g[C - 1: C]), jnp.exp(g[N - 1:])], kb=kb, ku=ku,
                kd=kd, ke=ke, vb=vb, T=T, W=_kmm(T, ke), U0=_kmm(T, vb), qu=qu, qe=qe,
                kt=kt, Aqk=Aqk)


def _head_beta(beta_ref):
    """The grid step's head's column [2C, 1] of the block's [2C, H] tile of beta."""
    betas = beta_ref[0].astype(F32)
    head = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    return jnp.sum(jnp.where(head == pl.program_id(1), betas, 0.0), axis=1, keepdims=True)


def _kda_forward_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, o_ref, *rest):
    """A grid step of ``_kda_kernel_forward``: a head's block of two chunks.
    ``rest``: (``start_ref``,) ``state``: the head's state (transposed:
    [dv, dk], so that the decay is a row), carried from a block to the
    head's next; ``start_ref``, where the call keeps them, takes it as each
    chunk finds it."""
    C = KDA_KERNEL_CHUNK
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, v, a = (x[0].astype(F32) for x in (q_ref, k_ref, v_ref, a_ref))
    t = _kda_block_terms(q, k, v, a, _head_beta(beta_ref))
    St, us = state[...], []
    for i in range(2):                                     # ``_kda_group``'s step
        rows = slice(i * C, (i + 1) * C)
        for start_ref in rest[:-1]:
            start_ref[0, 0, i] = St
        us.append(t["U0"][rows] - _kmm(t["W"][rows], St, _NT))
        o_ref[0, rows] = (_kmm(t["qe"][rows], St, _NT) + _kmm(
            t["Aqk"][rows, : (i + 1) * C], jnp.concatenate(us, axis=0)))
        St = St * t["decay"][i] + _kmm(us[i], t["kt"][rows], _TN)
    state[...] = St


def _kda_backward_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, ct_ref, start_ref,
                         dq_ref, dk_ref, dv_ref, da_ref, db_ref, d_state):
    """A grid step of ``_kda_kernel_backward``, the blocks of a head from
    the last to the first: the block's terms again from q, k, v, a, beta
    and the states its chunks started from, then every line of the forward
    pass transposed. ``d_state``: the gradient of the state the block
    leaves (transposed), carried to the block before. ``db_ref`` takes
    beta's gradient before its sum over a head's channels."""
    C = KDA_KERNEL_CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    q, k, v, a, d_o = (x[0].astype(F32) for x in (q_ref, k_ref, v_ref, a_ref, ct_ref))
    beta = _head_beta(beta_ref)
    t = _kda_block_terms(q, k, v, a, beta)
    row, col, same, at, T, up, down, eg, tail = (t[n] for n in (
        "row", "col", "same", "at", "T", "up", "down", "eg", "tail"))
    halves = [slice(0, C), slice(C, 2 * C)]
    starts = [start_ref[0, 0, i] for i in range(2)]
    u = jnp.concatenate([t["U0"][rows] - _kmm(t["W"][rows], St, _NT)
                         for rows, St in zip(halves, starts)], axis=0)
    # the step, the second chunk first: o = qe S + Aqk u;
    # S' = decay S + kt^T u;  u = U0 - W S
    d_u_of_o = _kmm(t["Aqk"], d_o, _TN)
    dSt = d_state[...]
    d_u, d_W, d_qe, d_kt, d_last = ([None, None] for _ in range(5))
    for i in (1, 0):
        rows, St, decay = halves[i], starts[i], t["decay"][i]
        d_u[i] = d_u_of_o[rows] + _kmm(t["kt"][rows], dSt, _NT)
        d_W[i], d_qe[i], d_kt[i] = -_kmm(d_u[i], St), _kmm(d_o[rows], St), _kmm(u[rows], dSt)
        d_last[i] = jnp.sum(St * dSt, axis=0, keepdims=True) * decay
        dSt = (dSt * decay + _kmm(d_o[rows], t["qe"][rows], _TN)
               - _kmm(d_u[i], t["W"][rows], _TN))
    d_state[...] = dSt
    d_u, d_W, d_qe, d_kt = (jnp.concatenate(x, axis=0) for x in (d_u, d_W, d_qe, d_kt))
    # W = T ke, U0 = T vb;  T = (I + A)^-1: dA = -T^T dT T^T below the diagonal
    d_Aqk = jnp.where(same & (row >= col), _kmm(d_o, u, _NT), 0.0)
    d_T = jnp.where(same, _kmm(d_W, t["ke"], _NT) + _kmm(d_u, t["vb"], _NT), 0.0)
    d_ke, d_vb = _kmm(T, d_W, _TN), _kmm(T, d_u, _TN)
    d_A = jnp.where(same & (row > col), -_kmm32(_kmm32(T, d_T, _TN), T, _NT), 0.0)
    d_ku = _kmm(d_A, t["kd"])
    d_kd = _kmm(d_A, t["ku"], _TN) + _kmm(d_Aqk, t["qu"], _TN)
    d_qu = _kmm(d_Aqk, t["kd"])
    dq_ref[0] = (d_qu * up + d_qe * eg).astype(dq_ref.dtype)
    dk_ref[0] = (beta * (d_ku * up + d_ke * eg) + d_kd * down
                 + d_kt * tail).astype(dk_ref.dtype)
    dv_ref[0] = (d_vb * beta).astype(dv_ref.dtype)
    db_ref[0] = k * (d_ku * up + d_ke * eg) + d_vb * v
    # the factors: up and down about a chunk's middle row, eg, tail and decay
    # about its last; g a chunk's running sum of a, so da the sum of dg from
    # the row to the chunk's end
    x_up = jnp.where(t["g"] - t["mid"] < EXP_CLIP, (d_ku * t["kb"] + d_qu * q) * up, 0.0)
    x_down = jnp.where(t["mid"] - t["g"] < EXP_CLIP, d_kd * k * down, 0.0)
    x_tail = d_kt * k * tail
    d_g = x_up - x_down + (d_ke * t["kb"] + d_qe * q) * eg - x_tail
    for i, rows in enumerate(halves):
        d_mid = jnp.sum((x_down - x_up)[rows], axis=0, keepdims=True)
        d_g = (d_g + jnp.where(at == i * C + C // 2 - 1, d_mid, 0.0)
               + jnp.where(at == (i + 1) * C - 1, d_last[i] + jnp.sum(
                   x_tail[rows], axis=0, keepdims=True), 0.0))
    da_ref[0] = _kmm32((same & (col >= row)).astype(F32), d_g)


def _kda_kernel_grid(shape, back: bool):
    """What both kernels share: (the grid (batch, heads, blocks of two
    chunks), a head's blocks one after the other (``back``: from the last);
    the tile of a [B, T, H x d] array, a head's block; of beta [B, T, H], a
    block; of the chunks' states [B, H, n, d, d], a block's two)."""
    B, T, H, d = shape
    N = 2 * KDA_KERNEL_CHUNK
    at = (lambda c: T // N - 1 - c) if back else (lambda c: c)
    return ((B, H, T // N),
            pl.BlockSpec((1, N, d), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((1, N, H), lambda b, h, c: (b, at(c), 0)),
            pl.BlockSpec((1, 1, 2, d, d), lambda b, h, c: (b, h, at(c), 0, 0)))


_KDA_KERNEL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kda_kernel_forward(q, k, v, a, beta, keep: bool = True, interpret: bool = False):
    """``_kda_scan`` as one Pallas kernel: the chunks of a head one after
    the other, two a grid step, its state in VMEM all the while; q, k, v,
    a, beta are read a block of a head at a time straight from
    [B, T, H x d], and nothing but o and, with ``keep``, the state each
    chunk started from (as the kernels keep it: [B, H, n, dv, dk]) is
    written. ``interpret``: the tests' way to run it on the CPU."""
    B, T, H, d = q.shape
    grid, wide, narrow, states = _kda_kernel_grid(q.shape, back=False)
    o, *starts = pl.pallas_call(
        _kda_forward_kernel, grid=grid,
        in_specs=[wide] * 4 + [narrow], out_specs=[wide] + [states] * keep,
        out_shape=[jax.ShapeDtypeStruct((B, T, H * d), F32)]
        + [jax.ShapeDtypeStruct((B, H, T // KDA_KERNEL_CHUNK, d, d), F32)] * keep,
        scratch_shapes=[pltpu.VMEM((d, d), F32)],
        compiler_params=_KDA_KERNEL_PARAMS, name="kda_chunk_fwd", interpret=interpret,
    )(*(x.reshape(B, T, H * d) for x in (q, k, v, a)), beta)
    return (o.reshape(B, T, H, d), *starts)


def _kda_kernel_backward(q, k, v, a, beta, starts, ct, interpret: bool = False):
    """The five gradients under ``ct`` as one Pallas kernel: a head's
    chunks from the last to the first, two a grid step, the state's
    gradient in VMEM all the while, each chunk's insides made again in VMEM
    from what the forward kernel read and the state it saved."""
    B, T, H, d = q.shape
    grid, wide, narrow, states = _kda_kernel_grid(q.shape, back=True)
    flat = lambda x: x.reshape(B, T, H * d)
    dq, dk, dv, da, db = pl.pallas_call(
        _kda_backward_kernel, grid=grid,
        in_specs=[wide] * 4 + [narrow, wide, states], out_specs=[wide] * 5,
        out_shape=[jax.ShapeDtypeStruct((B, T, H * d), dt)
                   for dt in (q.dtype, k.dtype, v.dtype, a.dtype, F32)],
        scratch_shapes=[pltpu.VMEM((d, d), F32)],
        compiler_params=_KDA_KERNEL_PARAMS, name="kda_chunk_bwd", interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(a), beta, flat(ct.astype(F32)), starts)
    dq, dk, dv, da, db = (x.reshape(B, T, H, d) for x in (dq, dk, dv, da, db))
    return dq, dk, dv, da, db.sum(-1).astype(beta.dtype)


def _kda_forward(q, k, v, a, beta, keep: bool, interpret: bool):
    """(o, with ``keep`` the state each chunk started from as the kernels
    keep it, 1.0 where the kernel ran): the kernel where the program is
    lowered for a TPU, the scan elsewhere."""
    fused = lambda *xs: _kda_kernel_forward(*xs, keep, interpret) + (jnp.float32(1.0),)
    if interpret:
        return fused(q, k, v, a, beta)

    def scan(*xs):
        o, starts = _kda_scan(*xs, KDA_KERNEL_CHUNK)
        starts = starts.reshape((-1,) + starts.shape[2:])      # [n, B, H, dk, dv]
        return (o,) + (jnp.transpose(starts, (1, 2, 0, 4, 3)),) * keep + (jnp.float32(0.0),)

    return jax.lax.platform_dependent(q, k, v, a, beta, tpu=fused, default=scan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_fused(q, k, v, a, beta, interpret=False):
    """The chunked rule at the kernels' shapes: (o, 1.0 where the kernel
    ran). Differentiated, the forward pass keeps q, k, v, a, beta and the
    state each chunk started from (0.54 GB a layer at 2 x 8,192 tokens and
    32 heads), and the backward pass is the second kernel, or the scan's
    own from the states its groups started from."""
    return _kda_forward(q, k, v, a, beta, False, interpret)


def _kda_fused_fwd(q, k, v, a, beta, interpret):
    o, starts, fused = _kda_forward(q, k, v, a, beta, True, interpret)
    return (o, fused), (q, k, v, a, beta, starts)


def _kda_fused_bwd(interpret, saved, cts):
    fused = lambda *xs: _kda_kernel_backward(*xs, interpret=interpret)
    if interpret:
        return fused(*saved, cts[0])

    def scan(q, k, v, a, beta, starts, ct):
        G = _kda_group_size(starts.shape[2])
        firsts = jnp.transpose(starts[:, :, ::G], (2, 0, 1, 4, 3))
        return _kda_scan_backward(q, k, v, a, beta, firsts, ct, KDA_KERNEL_CHUNK)

    return jax.lax.platform_dependent(*saved, cts[0], tpu=fused, default=scan)


_kda_fused.defvjp(_kda_fused_fwd, _kda_fused_bwd)


def kda_chunked(q, k, v, a, beta, chunk: int = 64):
    """S_t = (I - b_t k_t k_t^T) Diag(exp a_t) S_(t-1) + b_t k_t v_t^T,
    o_t = S_t^T q_t (``q``, ``k``, ``a``: [B, T, H, dk]; ``v``: [B, T, H, dv];
    ``beta``: [B, T, H]) in chunks of ``chunk`` tokens (a power of two). Within a chunk, with g the running sum of ``a``: the updates
    u_t = b_t (v_t - S_(t-1)^T Diag(alpha_t) k_t) solve (I + A) U = b V -
    (b K e^g) S_0, where A_ts = b_t sum_d k_td k_sd e^(g_td - g_sd) for
    s < t; then o = (Q e^g) S_0 + tril((Q e^g)(K e^-g)^T) U and
    S_C = e^(g_C) S_0 + (K e^(g_C - g))^T U. The factors e^g and e^-g are
    taken about the chunk's middle token and clipped at e^80, which is
    exact while half a chunk's summed log-decay stays above -80 (2.5 a
    token at chunk 64; the initial range ends at 1.6). A ragged tail is
    padded with tokens that leave the state as it is (b = 0, a = 0).

    Returns (o [B, T, H, dv] in float32, 1.0 where the fused chunk kernel
    computed it and 0.0 where the ``jax.numpy`` scan did). The kernel runs
    where the program is lowered for a TPU, at the shapes it is written for
    (chunk ``KDA_KERNEL_CHUNK``, keys and values ``KDA_KERNEL_WIDTH`` wide,
    a length two chunks divide); decided when the program is lowered, as
    ``mla_attention`` is, and from nothing else. Every other shape, and
    every other platform, runs the scan."""
    T, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    C = int(chunk)
    if C == KDA_KERNEL_CHUNK and dk == dv == KDA_KERNEL_WIDTH and T % (2 * C) == 0:
        return _kda_fused(q, k, v, a, beta)
    pad = (-T) % C
    if pad:
        q, k, v, a = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, a))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    return _kda_scan(q, k, v, a, beta, C)[0][:, :T], jnp.float32(0.0)


def causal_conv(x, w):
    """Depthwise causal convolution: ``x`` [B, T, D], ``w`` [K, D]; tap
    K-1 is the current token's."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    T = x.shape[1]
    return sum(xp[:, j: j + T, :] * w[j] for j in range(K))


# -- MLA: latent attention, no positions ------------------------------------------

def _blocked_attention(q, k, v, block: int = ATTN_BLOCK, segments: int = 4):
    """``mla_attention`` in plain ``jax.numpy``. The sequence is cut into
    ``segments``; a segment's queries see the keys up to the segment's end,
    a block of ``block`` queries at a time, one after the other
    (``lax.map``), each block recomputed in the backward pass: one block's
    scores exist at a time."""
    B, T, H, d = q.shape
    scale = 1.0 / np.sqrt(d)

    @jax.checkpoint
    def one(qb, kb, vb, first):
        s = _mm(qb, kb, "bthd,bshd->bhts") * scale
        t_pos = first + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(t_pos >= jnp.arange(kb.shape[1])[None, :], s, -1e30)
        return _mm(jax.nn.softmax(s, axis=-1), vb, "bhts,bshd->bthd", BF16)

    if T % (segments * block):
        segments, block = 1, T      # a size no segment divides: one block
    seg = T // segments
    outs = []
    for i in range(segments):
        end = (i + 1) * seg
        kb, vb = k[:, :end], v[:, :end]
        qs = q[:, i * seg: end].reshape(B, seg // block, block, H, d)
        firsts = i * seg + jnp.arange(seg // block) * block
        o = jax.lax.map(lambda xs: one(xs[0], kb, vb, xs[1]),
                        (jnp.moveaxis(qs, 1, 0), firsts))
        outs.append(jnp.moveaxis(o, 0, 1).reshape(B, seg, H, v.shape[-1]))
    return jnp.concatenate(outs, axis=1)


def _fused_attention(q, k, v, interpret: bool = False):
    """``mla_attention`` as one kernel a pass (the library's Pallas splash
    attention): online softmax over ``KERNEL_BLOCK`` keys at a time, the
    score tile in VMEM, key blocks above the diagonal never visited,
    float32 maximum, sum and accumulator, bfloat16 operands; the backward
    pass from the saved output and log-sum-exp (its own ``custom_vjp``).
    The kernel scales nothing, so q is scaled here, in float32, before it
    is rounded; it takes one sequence heads-major, so the batch is mapped.
    ``interpret``: the tests' way to run it on the CPU."""
    _B, T, H, d = q.shape
    b = KERNEL_BLOCK
    kernel = splash.make_splash_mha(
        splash_mask.MultiHeadMask([splash_mask.CausalMask((T, T))] * H),
        block_sizes=splash.BlockSizes(
            block_q=b, block_kv=b, block_kv_compute=b,
            block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
            block_q_dq=b, block_kv_dq=b),
        head_shards=1, q_seq_shards=1, interpret=interpret)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)
    q = (q.astype(F32) * (1.0 / np.sqrt(d))).astype(BF16)
    o = jax.vmap(kernel)(heads_major(q), heads_major(k.astype(BF16)),
                         heads_major(v.astype(BF16)))
    return heads_major(o)


def mla_attention(q, k, v):
    """Causal softmax(q k^T / sqrt(d)) v: bfloat16 operands, float32
    products and softmax, every query sees every key at or before it.
    ``q``: [B, T, H, d]; ``k``: [B, T, Hk, d]; ``v``: [B, T, Hk, dv], where
    Hk is H or divides it (grouped queries: a key/value head serves H / Hk
    query heads in a row; the kernel reads it so, the blocked code repeats
    it). Returns (the result [B, T, H, dv] in bfloat16, 1.0 where the
    fused kernel computed it and 0.0 where the blocked code did). The
    kernel runs where the program is lowered for a TPU and ``KERNEL_BLOCK``
    divides the length; which of the two is decided when the program is
    lowered, from the platform it is lowered for (so a compile here for a
    described chip takes the chip's path), and from nothing else. Both
    language-model templates' attention."""
    group = q.shape[2] // k.shape[2]

    def blocked(q, k, v):
        if group > 1:
            k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return _blocked_attention(q, k, v), jnp.float32(0.0)

    if q.shape[1] % KERNEL_BLOCK:
        return blocked(q, k, v)
    fused = lambda q, k, v: (_fused_attention(q, k, v), jnp.float32(1.0))
    return jax.lax.platform_dependent(q, k, v, tpu=fused, default=blocked)


# -- sparse experts ------------------------------------------------------------

def route(x, w_router, bias, top_k: int, scaling: float, eps=0.0, score_function=jax.nn.sigmoid):
    """Scores over every expert (``score_function``: sigmoid, or a softmax over
    all of them, ``jax.nn.softmax``); the top ``top_k`` of score + bias selected,
    and weighted by their scores alone, renormalised over the selected set
    (``eps`` added to their sum, where a published router has one), times
    ``scaling``. Returns (ids [N, k], weights [N, k]) in float32. The
    language-model templates' one router (``lfm2_moe.py``: a bias not zero)."""
    s = score_function(jnp.matmul(x.astype(F32), w_router.astype(F32),
                                  precision=jax.lax.Precision.HIGHEST))
    _vals, ids = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, ids, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    return ids, scaling * picked / (total + eps if eps else total)


def _slab_rows(pairs: int, experts: int, width: int) -> int:
    """Rows of one slab of the sorted token-choices. A slab reads its
    experts' ``experts x width`` columns of weights whatever it holds, so its
    rows are of that order (half of it: 4,096 at 8 experts 1,024 wide) and
    the layer costs what its live rows do plus at most one slab."""
    return max(1, min(pairs, experts * width // 2))


def _slab(rows, w, sizes, wg, wu, wd):
    """One slab: ``rows`` [S, D] of tokens in expert order, their router
    weights ``w`` [S], the rows each expert holds of them ``sizes`` [E].
    A ragged product leaves the rows past its groups as they were in
    memory (zero on the CPU, anything on the TPU), in its result and in
    the gradient it hands back for its left operand alike. Every such
    operand and result is therefore selected by ``live`` (a select, not
    a product: it stops a NaN), so that neither a value nor a gradient
    of a token-choice that names an absent expert comes from there."""
    rd = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                           preferred_element_type=F32)
    live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
    xs = jnp.where(live, rows, 0)
    h = jnp.where(live, jax.nn.silu(rd(xs, wg)) * rd(xs, wu), 0.0).astype(BF16)
    return jnp.where(live, rd(h, wd), 0.0) * w[:, None]


def _slabs(x, weights, order, sizes, w_gate, w_up, w_down):
    """What both passes over the live slabs share: how many there are (a
    device value), the bfloat16 operands, and ``take(s)`` -> the flat
    token-choices of slab ``s``, their tokens, rows and router weights and
    the slab's own share of the groups."""
    k = weights.shape[1]
    S = _slab_rows(order.shape[0], *w_gate.shape[::2])
    order = jnp.pad(order, (0, -order.shape[0] % S))
    ends = jnp.cumsum(sizes)
    xb, wflat = x.astype(BF16), weights.reshape(-1)

    def take(s):
        pairs = jax.lax.dynamic_slice(order, (s * S,), (S,))
        tokens = pairs // k
        cut = lambda edge: jnp.clip(edge - s * S, 0, S)
        return (pairs, tokens, jnp.take(xb, tokens, axis=0), jnp.take(wflat, pairs),
                cut(ends) - cut(ends - sizes))

    return (ends[-1] + S - 1) // S, take, tuple(w.astype(BF16) for w in (w_gate, w_up, w_down))


@jax.custom_vjp
def _held_rows(x, weights, order, sizes, w_gate, w_up, w_down):
    """The routed result [N, D] of the sorted token-choices ``order`` [N k],
    of which the first ``sum(sizes)`` are live, grouped by expert: a slab of
    them at a time while there are live ones (a ``while`` on the device: no
    slab without a live row runs, in either pass, and none is cut). The
    backward pass is written out because a loop of that kind has no
    transpose, and so that a slab's rows are recomputed there and never
    kept: what lives between the passes is the arguments."""
    n, take, ws = _slabs(x, weights, order, sizes, w_gate, w_up, w_down)

    def one(s, y):
        _pairs, tokens, rows, w, held = take(s)
        # a token's k choices name distinct experts: they simply sum
        return y.at[tokens].add(_slab(rows, w, held, *ws))

    return jax.lax.fori_loop(0, n, one, jnp.zeros(x.shape, F32))


def _held_rows_fwd(*args):
    return _held_rows(*args), args


def _held_rows_bwd(args, dy):
    x, weights, _order, _sizes, *experts = args
    n, take, ws = _slabs(*args)

    def one(s, grads):
        dx, dw, *dws = grads
        pairs, tokens, rows, w, held = take(s)
        _out, vjp = jax.vjp(lambda rows, w, *ws: _slab(rows, w, held, *ws), rows, w, *ws)
        d_rows, d_w, *d_ws = vjp(jnp.take(dy, tokens, axis=0))
        return (dx.at[tokens].add(d_rows.astype(F32)), dw.at[pairs].add(d_w),
                *(a + d.astype(F32) for a, d in zip(dws, d_ws)))

    zeros = lambda a: jnp.zeros(a.shape, F32)
    dx, dw, *dws = jax.lax.fori_loop(
        0, n, one, (zeros(x), jnp.zeros((weights.size,), F32), *map(zeros, experts)))
    return (dx.astype(x.dtype), dw.reshape(weights.shape).astype(weights.dtype), None, None,
            *(d.astype(w.dtype) for d, w in zip(dws, experts)))


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def expert_layer(x, ids, weights, held: Sequence[int], w_gate, w_up, w_down):
    """The held experts' part of the routed result. ``x`` [N, D]; ``ids``
    and ``weights`` [N, k] over all experts; ``held`` the expert ids whose
    weights ``w_gate``/``w_up`` [E, D, F] and ``w_down`` [E, F, D] are here,
    in that order. All N k token-choices are sorted ONCE by the local
    expert each names, those that name an absent one last and in no group;
    the live ones come first, grouped by expert. They are then taken a slab
    of ``_slab_rows`` at a time, only while there are live ones: a slab
    gathers its tokens' rows and router weights, runs the three products
    ragged over its share of the groups, and adds the weighted rows into the
    result by token. So the layer moves the rows its experts hold (and at
    most a slab beyond them), not every token once a choice; there is no
    capacity and no dropped token at any routing, skewed or full routing
    runs more slabs. Returns (y [N, D] float32, rows a held expert took [E])."""
    E = len(held)
    top = int(max(held)) + 1
    local = np.full((top + 1,), E, np.int32)               # E: absent
    local[list(held)] = np.arange(E)
    expert = jnp.take(jnp.asarray(local), jnp.minimum(ids, top)).reshape(-1)
    order = jnp.argsort(expert)                            # stable: by expert, then by token
    sizes = jnp.sum(expert[:, None] == jnp.arange(E)[None, :], axis=0, dtype=jnp.int32)
    return _held_rows(x, weights, order, sizes, w_gate, w_up, w_down), sizes


def swiglu(x, w_gate, w_up, w_down):
    gate = _mm(x, w_gate, "...d,df->...f", BF16).astype(F32)
    h = jax.nn.silu(gate) * _mm(x, w_up, "...d,df->...f", BF16).astype(F32)
    return _mm(h, w_down, "...f,fd->...d")


# -- KDA: a branch's tail (convolution, SiLU, l2norm) ------------------------------

def _branch_tail(y, w, heads: int, normed: bool, scale: float):
    """The ``jax.numpy`` tail of a KDA branch: ``y`` [B, T, heads x d] (the
    projection), ``w`` [K, heads x d] (the taps) -> [B, T, heads, d] in
    bfloat16, each head l2-normed and scaled where ``normed``."""
    B, T, E = y.shape
    u = jax.nn.silu(causal_conv(y.astype(F32), w)).reshape(B, T, heads, E // heads)
    return ((l2norm(u) * scale) if normed else u).astype(BF16)


def _branch_taps(win, w, rows: int, first: int):
    """``causal_conv`` on ``rows`` rows of a VMEM window whose row ``first``
    holds the first row's own token (the rows before it its history): the
    taps in ``causal_conv``'s order, the oldest first."""
    K = w.shape[0]
    u = win[pl.ds(first - K + 1, rows), :] * w[0: 1]
    for j in range(1, K):
        u = u + win[pl.ds(first - K + 1 + j, rows), :] * w[j: j + 1]
    return u


def _branch_forward_kernel(y_ref, before_ref, w_ref, o_ref, win, *, normed, scale):
    """A grid step of ``_branch_kernel_forward``: a head's block of tokens,
    with the ``KDA_BRANCH_HALO`` rows before it as its history (none in a
    sequence's first block)."""
    N, halo = y_ref.shape[1], KDA_BRANCH_HALO
    win[pl.ds(0, halo), :] = before_ref[0].astype(F32)
    win[pl.ds(halo, N), :] = y_ref[0].astype(F32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        win[pl.ds(0, halo), :] = jnp.zeros((halo, win.shape[1]), F32)

    s = jax.nn.silu(_branch_taps(win, w_ref[...], N, halo))
    if normed:
        s = s * jax.lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + L2_EPS) * scale
    o_ref[0] = s.astype(o_ref.dtype)


def _branch_backward_kernel(y_ref, before_ref, after_ref, ct_ref, ct_after_ref, w_ref,
                            dy_ref, dw_ref, win, d_win, *, normed, scale):
    """A grid step of ``_branch_kernel_backward``: the block's forward values
    again, and those of the ``KDA_BRANCH_HALO`` rows after it (whose
    gradients reach back into the block through the taps), then the tail
    transposed: the projection's gradient, and the taps' summed over the
    block's rows."""
    N, halo, K = y_ref.shape[1], KDA_BRANCH_HALO, w_ref.shape[0]
    rows = N + halo
    win[pl.ds(0, halo), :] = before_ref[0].astype(F32)
    win[pl.ds(halo, N), :] = y_ref[0].astype(F32)
    win[pl.ds(halo + N, halo), :] = after_ref[0].astype(F32)
    d_win[pl.ds(0, N), :] = ct_ref[0].astype(F32)
    d_win[pl.ds(N, halo), :] = ct_after_ref[0].astype(F32)
    zeros = jnp.zeros((halo, win.shape[1]), F32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        win[pl.ds(0, halo), :] = zeros

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        d_win[pl.ds(N, halo), :] = zeros

    w = w_ref[...]
    u = _branch_taps(win, w, rows, halo)
    g = d_win[...]
    sig = jax.nn.sigmoid(u)
    if normed:                        # o = s r scale, r = (sum s^2 + eps)^-1/2
        s = u * sig
        z = jnp.sum(s * s, axis=-1, keepdims=True) + L2_EPS
        r, g = jax.lax.rsqrt(z), g * scale
        g = g * r - s * (jnp.sum(g * s, axis=-1, keepdims=True) * r / z)
    d_win[...] = g * sig * (1.0 + u * (1.0 - sig))          # through SiLU: du
    dy = d_win[pl.ds(K - 1, N), :] * w[0: 1]
    for j in range(1, K):
        dy = dy + d_win[pl.ds(K - 1 - j, N), :] * w[j: j + 1]
    dy_ref[0] = dy.astype(dy_ref.dtype)
    du = d_win[pl.ds(0, N), :]
    for j in range(K):
        dw_ref[0, 0, j: j + 1, :] = jnp.sum(
            win[pl.ds(halo - K + 1 + j, N), :] * du, axis=0, keepdims=True)


def _branch_kernel_grid(shape, K: int, N: int):
    """What both kernels share: (the grid (batch, heads, blocks of ``N``
    tokens), every step on its own; the tile of a [B, T, H x d] array, a
    head's block; the ``KDA_BRANCH_HALO`` rows before it and after it (at a
    sequence's ends its own, and ignored); the taps of a head [K, d])."""
    B, T, E = shape
    halo, d = KDA_BRANCH_HALO, KDA_KERNEL_WIDTH
    per, last = N // halo, T // halo - 1
    return ((B, E // d, T // N),
            pl.BlockSpec((1, N, d), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, halo, d), lambda b, h, c: (b, jnp.maximum(c * per - 1, 0), h)),
            pl.BlockSpec((1, halo, d), lambda b, h, c: (b, jnp.minimum((c + 1) * per, last), h)),
            pl.BlockSpec((K, d), lambda b, h, c: (0, h)))


_BRANCH_KERNEL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


# Both kernels are jitted: a step calls them 36 times at three settings, each
# traced and lowered once (``kda_branch`` below).
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _branch_kernel_forward(y, w, normed: bool, scale: float, N: int, interpret: bool = False):
    """``_branch_tail`` as one Pallas kernel (``kda_branch_fwd``) in blocks of
    ``N`` tokens: ``y`` read a head's block at a time straight from
    [B, T, H x d], the arithmetic in float32 in VMEM, nothing but the result
    [B, T, H x d] written, in ``y``'s dtype."""
    B, T, E = y.shape
    grid, block, before, _after, taps = _branch_kernel_grid(y.shape, w.shape[0], N)
    return pl.pallas_call(
        functools.partial(_branch_forward_kernel, normed=normed, scale=scale), grid=grid,
        in_specs=[block, before, taps], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, T, E), y.dtype),
        scratch_shapes=[pltpu.VMEM((N + KDA_BRANCH_HALO, KDA_KERNEL_WIDTH), F32)],
        compiler_params=_BRANCH_KERNEL_PARAMS, name="kda_branch_fwd", interpret=interpret,
    )(y, y, w)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _branch_kernel_backward(y, w, ct, normed: bool, scale: float, N: int,
                            interpret: bool = False):
    """The tail's gradients under ``ct`` [B, T, H x d] as one Pallas kernel
    (``kda_branch_bwd``): the projection's in its own dtype, the taps' as a
    float32 sum a block ([B, blocks, K, H x d]) that XLA adds up."""
    B, T, E = y.shape
    K = w.shape[0]
    grid, block, before, after, taps = _branch_kernel_grid(y.shape, K, N)
    halo, d = KDA_BRANCH_HALO, KDA_KERNEL_WIDTH
    dy, dw = pl.pallas_call(
        functools.partial(_branch_backward_kernel, normed=normed, scale=scale), grid=grid,
        in_specs=[block, before, after, block, after, taps],
        out_specs=[block, pl.BlockSpec((1, 1, K, d), lambda b, h, c: (b, c, 0, h))],
        out_shape=[jax.ShapeDtypeStruct((B, T, E), y.dtype),
                   jax.ShapeDtypeStruct((B, T // N, K, E), F32)],
        scratch_shapes=[pltpu.VMEM((N + 2 * halo, d), F32), pltpu.VMEM((N + halo, d), F32)],
        compiler_params=_BRANCH_KERNEL_PARAMS, name="kda_branch_bwd", interpret=interpret,
    )(y, y, y, ct, ct, w)
    return dy, dw.sum((0, 1)).astype(w.dtype)


def _branch_forward(y, w, normed: bool, scale: float, interpret: bool):
    """(the tail [B, T, H, d], 1.0 where the kernel computed it): the kernel
    where the program is lowered for a TPU, ``_branch_tail`` elsewhere."""
    B, T, E = y.shape
    heads = E // KDA_KERNEL_WIDTH
    fused = lambda y, w: (_branch_kernel_forward(y, w, normed, scale, KDA_BRANCH_BLOCK, interpret)
                          .reshape(B, T, heads, KDA_KERNEL_WIDTH).astype(BF16), jnp.float32(1.0))
    if interpret:
        return fused(y, w)
    plain = lambda y, w: (_branch_tail(y, w, heads, normed, scale), jnp.float32(0.0))
    return jax.lax.platform_dependent(y, w, tpu=fused, default=plain)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _branch_fused(y, w, normed: bool, scale: float, interpret: bool = False):
    """The tail at the kernels' shapes: (the tail, 1.0 where the kernel ran).
    Differentiated, it keeps ``y`` and the taps and nothing else; the
    backward pass is the second kernel, or ``jax.vjp`` of ``_branch_tail``."""
    return _branch_forward(y, w, normed, scale, interpret)


def _branch_fused_fwd(y, w, normed, scale, interpret):
    return _branch_forward(y, w, normed, scale, interpret), (y, w)


def _branch_fused_bwd(normed, scale, interpret, saved, cts):
    fused = lambda y, w, ct: _branch_kernel_backward(y, w, ct.reshape(y.shape), normed, scale,
                                                     KDA_BRANCH_BLOCK, interpret)
    if interpret:
        return fused(*saved, cts[0])

    def plain(y, w, ct):                                   # ct [B, T, H, d]
        tail = lambda y, w: _branch_tail(y, w, ct.shape[2], normed, scale)
        return jax.vjp(tail, y, w)[1](ct)

    return jax.lax.platform_dependent(*saved, cts[0], tpu=fused, default=plain)


_branch_fused.defvjp(_branch_fused_fwd, _branch_fused_bwd)


# Jitted, as the kernels are: a step's twelve branches trace and lower three
# settings once each, where each call traced both paths and both rules anew
# (+7 s of the cell's ``setup_s`` on the chip's host: PERF.md, PR 36).
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def kda_branch(y, w, heads: int, normed: bool, scale: float):
    """A KDA branch's tail: ``causal_conv`` of the projection ``y``
    [B, T, heads x d] with the taps ``w`` [K, heads x d] in float32, SiLU,
    and with ``normed`` each head's l2norm times ``scale``. Returns (the
    tail [B, T, heads, d] in bfloat16, 1.0 where the branch kernels computed
    it and 0.0 where ``_branch_tail`` did). The kernels run where the
    program is lowered for a TPU, at the shapes they are written for (heads
    ``KDA_KERNEL_WIDTH`` wide, a length ``KDA_BRANCH_BLOCK`` divides, at most
    ``KDA_BRANCH_HALO`` + 1 taps); decided as ``kda_chunked`` decides. The
    forward kernel reads ``y`` once and writes the tail once, where the
    ``jax.numpy`` code makes a float32 copy of ``y``, four shifted ones and
    as many more in its gradient (PERF.md, PR 36)."""
    _B, T, E = y.shape
    if (E == heads * KDA_KERNEL_WIDTH and T % KDA_BRANCH_BLOCK == 0
            and w.shape[0] <= KDA_BRANCH_HALO + 1):
        return _branch_fused(y, w, normed, scale)
    return _branch_tail(y, w, heads, normed, scale), jnp.float32(0.0)


# -- the module ----------------------------------------------------------------

def _dense_init(scale: float = 0.02):
    return nn.initializers.normal(scale)


class _Kda(nn.Module):
    """The KDA mixer. Each wide branch (q, k, v, the decay, the gate, the
    output) is a function of its own that is recomputed in the backward
    pass, so that what lives through the layer's backward is the core's
    five inputs and not every [tokens, heads x d] intermediate. Returns
    (the mixed [B, T, D], [1.0 where the chunk kernels ran, the branches
    of q, k, v whose tail a kernel computed])."""

    heads: int
    head_dim: int
    conv: int
    chunk: int
    eps: float

    @nn.compact
    def __call__(self, x):
        D, H, d = x.shape[-1], self.heads, self.head_dim
        B, T, _ = x.shape
        eps = self.eps
        p = lambda name, shape, init=_dense_init(): self.param(name, init, shape)

        @functools.partial(jax.checkpoint, static_argnums=(3, 4))
        def branch(x, w, conv_w, normed: bool, scale: float):
            return kda_branch(_mm(x, w, "btd,de->bte", BF16), conv_w, H, normed, scale)

        @jax.checkpoint
        def decay(x, w1, w2, a_log, dt_bias):
            f = _mm(_mm(x, w1, "btd,de->bte", BF16), w2, "bte,ef->btf")
            return (-jnp.exp(a_log)[:, None]
                    * jax.nn.softplus(f + dt_bias).reshape(B, T, H, d))

        @jax.checkpoint
        def output(o, x, w1, w2, o_norm, w_o):
            gate = jax.nn.sigmoid(_mm(_mm(x, w1, "btd,de->bte", BF16), w2, "bte,ef->btf"))
            o = rms_norm(o, o_norm, eps).reshape(B, T, H * d) * gate
            return _mm(o, w_o, "bte,ed->btd", BF16)

        with jax.named_scope(SCOPE_KDA):
            conv_init = nn.initializers.normal(1.0 / np.sqrt(self.conv))
            (q, fq), (k, fk), (v, fv) = (
                branch(x, p(f"w_{n}", (D, H * d)),
                       p(f"conv_{n}", (self.conv, H * d), conv_init), normed, scale)
                for n, normed, scale in (("q", True, d ** -0.5), ("k", True, 1.0),
                                         ("v", False, 1.0)))
            w_f1, w_f2 = p("w_f1", (D, d)), p("w_f2", (d, H * d))
            # A in [1, 16], dt in [1e-3, 1e-1] (the Mamba-2 ranges FLA uses)
            a_log = p("A_log", (H,), lambda key, s: jnp.log(
                jax.random.uniform(key, s, F32, 1.0, 16.0)))

            def dt_init(key, s):
                dt = jnp.exp(jax.random.uniform(key, s, F32, np.log(1e-3), np.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)

            a = decay(x, w_f1, w_f2, a_log, p("dt_bias", (H * d,), dt_init))
            beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", x.astype(F32),
                                             p("w_beta", (D, H))))
            o, fused = kda_chunked(q, k, v, a, beta, self.chunk)
            return (output(o.astype(BF16), x, p("w_g1", (D, d)), p("w_g2", (d, H * d)),
                           p("o_norm", (d,), nn.initializers.ones), p("w_o", (H * d, D))),
                    jnp.stack([fused, fq + fk + fv]))


class _Mla(nn.Module):
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    eps: float

    @nn.compact
    def __call__(self, x):
        D, H = x.shape[-1], self.heads
        B, T, _ = x.shape
        p = lambda name, shape, init=_dense_init(): self.param(name, init, shape)
        with jax.named_scope(SCOPE_MLA):
            q = _mm(x, p("w_q", (D, H * (self.nope + self.rope))),
                    "btd,de->bte", BF16).reshape(B, T, H, self.nope + self.rope)
            kva = _mm(x, p("w_kva", (D, self.kv_rank + self.rope)), "btd,de->bte")
            c = rms_norm(kva[..., : self.kv_rank],
                         p("kv_norm", (self.kv_rank,), nn.initializers.ones), self.eps)
            k_r = kva[..., self.kv_rank:].astype(BF16)     # shared by the heads, unrotated
            kvb = _mm(c, p("w_kvb", (self.kv_rank, H * (self.nope + self.v_dim))),
                      "btr,re->bte", BF16).reshape(B, T, H, self.nope + self.v_dim)
            k = jnp.concatenate(
                [kvb[..., : self.nope],
                 jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, self.rope))], axis=-1)
            o, fused = mla_attention(q, k, kvb[..., self.nope:])
            return _mm(o.reshape(B, T, H * self.v_dim),
                       p("w_o", (H * self.v_dim, D)), "bte,ed->btd", BF16), fused


class _Dense(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        D = x.shape[-1]
        p = lambda name, shape: self.param(name, _dense_init(), shape)
        return swiglu(x, p("w_gate", (D, self.width)), p("w_up", (D, self.width)),
                      p("w_down", (self.width, D)))


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
        p = lambda name, shape: self.param(name, _dense_init(), shape)
        flat = x.reshape(B * T, D)
        with jax.named_scope(SCOPE_ROUTE):
            # ``e_score_correction_bias``: held at zero, no balancing update
            bias = self.param("router_bias", nn.initializers.zeros, (self.experts,))
            ids, weights = route(flat, p("w_router", (D, self.experts)),
                                 jax.lax.stop_gradient(bias), self.top_k, self.scaling)
        with jax.named_scope(SCOPE_EXPERTS):
            y, sizes = expert_layer(
                flat, ids, weights, self.held, p("w_gate", (E, D, self.width)),
                p("w_up", (E, D, self.width)), p("w_down", (E, self.width, D)))
        with jax.named_scope(SCOPE_SHARED):
            y = y + swiglu(flat, p("shared_gate", (D, self.width)),
                           p("shared_up", (D, self.width)),
                           p("shared_down", (self.width, D)))
        return y.reshape(B, T, D), sizes


class _Layer(nn.Module):
    cfg: Any            # a hashable tuple of (key, value) pairs
    mixer: str          # "kda" | "mla"
    sparse: bool

    @nn.compact
    def __call__(self, h):
        c = dict(self.cfg)
        eps = c["rms_norm_eps"]
        norm = lambda name: self.param(name, nn.initializers.ones, (h.shape[-1],))
        x = rms_norm(h, norm("norm_mixer"), eps)
        if self.mixer == "kda":
            m, fused = _Kda(c["num_heads"], c["kda_head_dim"], c["short_conv_kernel_size"],
                            c["kda_chunk"], eps, name="kda")(x)
        else:
            m, fused = _Mla(c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                            c["v_head_dim"], c["kv_lora_rank"], eps, name="mla")(x)
        h = h + m.astype(h.dtype)
        x = rms_norm(h, norm("norm_ffn"), eps)
        if self.sparse:
            y, load = _Moe(c["num_experts"], c["num_experts_per_token"],
                             tuple(c["experts_held"]), c["moe_intermediate_size"],
                             c["routed_scaling_factor"], name="moe")(x)
        else:
            y = _Dense(c["intermediate_size"], name="ffn")(x)
            load = jnp.zeros((len(c["experts_held"]),), jnp.int32)
        return h + y.astype(h.dtype), load, fused


class _KimiLinear(nn.Module):
    """x [B, T] token ids -> the next token's logits after the last one
    given [B, V]; with ``hidden``, (hidden states after the final norm
    [B, T, D] in bfloat16, the untied head [D, V], rows each held expert
    took in each layer [layers, E], what fused kernels computed [3]: MLA
    layers, KDA layers, KDA branches)."""

    cfg: Any
    vocab: int

    def layer_kinds(self):
        c = dict(self.cfg)
        return [("mla" if i in c["full_attn_layers"] else "kda",
                 i > c["first_k_dense_replace"])
                for i in range(1, c["num_hidden_layers"] + 1)]

    @nn.compact
    def __call__(self, x, train: bool = False, hidden: bool = False):
        c = dict(self.cfg)
        D = c["hidden_size"]
        embed = self.param("embed", _dense_init(), (self.vocab, D))
        head = self.param("head", _dense_init(), (D, self.vocab))
        h = jnp.take(embed, x, axis=0).astype(BF16)
        loads, fused = [], {"mla": jnp.float32(0.0), "kda": jnp.zeros((2,), F32)}
        layer = nn.remat(_Layer) if train else _Layer
        for i, (mixer, sparse) in enumerate(self.layer_kinds()):
            h, load, kernel = layer(self.cfg, mixer, sparse, name=f"layer_{i + 1}")(h)
            loads.append(load)
            fused[mixer] = fused[mixer] + kernel
        h = rms_norm(h, self.param("norm_out", nn.initializers.ones, (D,)),
                     c["rms_norm_eps"]).astype(BF16)
        if hidden:
            return h, head, jnp.stack(loads), jnp.concatenate([fused["mla"][None], fused["kda"]])
        # Serving: the next token's distribution after the last one given.
        return _mm(h[:, -1], head, "bd,dv->bv")


def blocked_logit_stats(h, head, y, smoothing, block: int = LOSS_BLOCK,
                        per_token: bool = False):
    """Over all positions, a block of the sequence at a time (each block
    recomputed in the backward pass, so the ``[tokens, vocab]`` logits never
    exist whole): summed cross entropy against ``y`` with label smoothing (a
    traced scalar), hits of the argmax, and the count of labelled positions
    (``y`` >= 0). ``per_token``: the same three before they are summed, each
    [B, T] (the cross entropy nought where ``y`` is not a label), for an
    objective that weights a token's cross entropy by something that has a
    gradient of its own (``ouro.py``'s exit distribution). The language-model
    templates' one loss."""
    T = h.shape[1]

    @jax.checkpoint
    def one(hb, yb):
        logits = _mm(hb, head, "btd,dv->btv")
        mask = yb >= 0
        safe = jnp.where(mask, yb, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        smooth = -jnp.mean(logp, axis=-1)
        ce = (1.0 - smoothing) * nll + smoothing * smooth
        hit = (jnp.argmax(logits, axis=-1) == safe) & mask
        if per_token:
            return jnp.where(mask, ce, 0.0), hit, mask
        return (jnp.where(mask, ce, 0.0).sum(), hit.sum().astype(jnp.int32),
                mask.sum().astype(jnp.int32))

    if per_token:
        # One block after the other (``lax.map``): unrolled, the compiler is free
        # to hold several blocks' logits at once (11.5 GB of temporaries against
        # 8.6 in ``ouro.py``'s step, compiled for a described v5e: PERF.md, PR 33).
        # A length the block does not divide is one block.
        blocks = T // block if T % block == 0 else 1
        first = lambda a: jnp.moveaxis(a.reshape((a.shape[0], blocks, -1) + a.shape[2:]), 1, 0)
        parts = jax.lax.map(lambda xs: one(*xs), (first(h), first(y)))
        return tuple(jnp.moveaxis(a, 0, 1).reshape(y.shape) for a in parts)
    cut = lambda start: (h[:, start: start + block], y[:, start: start + block])
    ce = jnp.zeros((), F32)
    hits = n = jnp.zeros((), jnp.int32)
    for start in range(0, T, block):
        c1, h1, n1 = one(*cut(start))
        ce, hits, n = ce + c1, hits + h1, n + n1
    return ce, hits, n


class BlockedLossLm(JaxModel):
    """What every language-model template shares of the ``JaxModel`` contract
    (this file's ``KimiLinear``, ``lfm2_moe.py``'s ``Lfm2Moe``, ``ouro.py``'s
    ``Ouro``): token ids in, a trial that fills a chip (serial lane, an epoch
    step by step through one executable), a traced ``label_smoothing``, and a
    loss and counts of its own, taken a block of the sequence at a time
    (``blocked_logit_stats``), in place of the ones over whole logits."""

    @classmethod
    def packable(cls) -> bool:
        return False  # one trial fills the chip

    @classmethod
    def epoch_program(cls) -> bool:
        return False  # a step of seconds, a compile of minutes: one step program

    def _input_dtype(self):
        return np.int32

    def _dataset_arch(self, ds):
        return int(ds.classes), tuple(ds.x.shape[1:])

    def _dynamic_hyper(self, takes_dropout: bool) -> Dict[str, float]:
        hyper = super()._dynamic_hyper(takes_dropout)
        hyper["label_smoothing"] = float(self.knobs.get("label_smoothing", 0.0))
        return hyper

    def _loss_and_count(self, module):
        """(``loss_fn(params, batch, rng, hyper)`` -> (loss, metrics),
        ``eval_count(params, batch)`` -> (hits, labelled positions)) of the
        template's module."""
        raise NotImplementedError

    def _loop_fns(self, num_classes, input_shape):
        fns = super()._loop_fns(num_classes, input_shape)
        loss_fn, eval_count = self._loss_and_count(fns["module"])
        fns.update(loss_fn=loss_fn, eval_count=eval_count)
        return fns


class SparseExpertLm(BlockedLossLm):
    """What the sparse-expert templates share beyond ``BlockedLossLm``: the
    held experts' loads as counters. A template's module returns, with
    ``hidden``, (hidden states after the final norm, the head [D, V], rows
    each held expert took in each layer, what its fused kernels computed) and
    lists its layers as ``layer_kinds()`` -> [(mixer, sparse)]; the template
    names its top-k knob and turns the last of the four into counters."""

    TOP_K_KNOB = "num_experts_per_token"

    def _kernel_counts(self, mixers: Sequence[str], fused) -> Dict[str, Any]:
        """``count.<name>`` metrics of a step: its layers by mixer, and those
        a fused kernel computed (``fused``: the module's fourth result)."""
        raise NotImplementedError

    def _loss_and_count(self, module):
        sparse = np.array([sp for _m, sp in module.layer_kinds()])
        mixers = [mixer for mixer, _sp in module.layer_kinds()]
        top_k, width = int(self.knobs[self.TOP_K_KNOB]), int(self.knobs["moe_intermediate_size"])

        def stats(params, batch, train, smoothing):
            h, head, loads, fused = module.apply({"params": params}, batch["x"],
                                                 train=train, hidden=True)
            with jax.named_scope(SCOPE_LM_LOSS):
                ce, hits, n = blocked_logit_stats(h, head, batch["y"], smoothing)
            return ce, hits, n, loads, fused

        def loss_fn(params, batch, rng, hyper):
            ce, hits, n, loads, fused = stats(params, batch, True, hyper["label_smoothing"])
            n = jnp.maximum(n, 1)
            loads = loads[sparse].astype(F32)
            skew = jnp.max(loads, axis=-1) / jnp.maximum(jnp.mean(loads, axis=-1), 1.0)
            slots = top_k * batch["x"].size
            slab = _slab_rows(slots, loads.shape[-1], width)
            return ce / n, {
                "acc": hits / n,
                "count.moe.slots_held": loads.sum(),
                # the rows of the slabs the expert layers ran: held, rounded up a layer
                "count.moe.rows_room": (jnp.ceil(loads.sum(axis=-1) / slab) * slab).sum(),
                "count.moe.slots_total": jnp.float32(slots * sparse.sum()),
                "gauge.moe.held_load_max_over_mean": skew.mean(),
                **self._kernel_counts(mixers, fused)}

        def eval_count(params, batch):
            _ce, hits, n, _loads, _fused = stats(params, batch, False, 0.0)
            return hits, n

        return loss_fn, eval_count


class KimiLinear(SparseExpertLm):
    """The template. Shape knobs default to a size a CPU trains in
    seconds; a tenant's model file pins them (the benchmark's
    configuration pins the published widths). ``expert_shard`` of
    ``expert_shards`` says which experts this chip holds: ids
    ``expert_shard * (num_experts // expert_shards)`` onward."""

    @staticmethod
    def get_knob_config():
        fixed = lambda v: FixedKnob(v, affects_shape=True)
        return {
            "hidden_size": fixed(64), "num_heads": fixed(4), "kda_head_dim": fixed(16),
            "short_conv_kernel_size": fixed(4), "kda_chunk": fixed(16),
            "qk_nope_head_dim": fixed(16), "qk_rope_head_dim": fixed(8),
            "v_head_dim": fixed(16), "kv_lora_rank": fixed(32),
            "intermediate_size": fixed(128), "moe_intermediate_size": fixed(32),
            "num_experts": fixed(16), "num_experts_per_token": fixed(4),
            "expert_shards": fixed(4), "expert_shard": fixed(0),
            "routed_scaling_factor": fixed(2.446), "first_k_dense_replace": fixed(1),
            "num_hidden_layers": fixed(5), "full_attn_every": fixed(4),
            "rms_norm_eps": fixed(1e-5),
            "learning_rate": FloatKnob(3e-5, 1e-3, is_exp=True),
            "label_smoothing": FloatKnob(0.0, 0.1),
            "batch_size": fixed(2), "epochs": FixedKnob(1), "seed": FixedKnob(0),
        }

    def module_config(self) -> tuple:
        kn = self.knobs
        per = int(kn["num_experts"]) // int(kn["expert_shards"])
        first = int(kn["expert_shard"]) * per
        c = {k: kn[k] for k in (
            "hidden_size", "num_heads", "kda_head_dim", "short_conv_kernel_size",
            "kda_chunk", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "intermediate_size", "moe_intermediate_size",
            "num_experts", "num_experts_per_token", "routed_scaling_factor",
            "first_k_dense_replace", "num_hidden_layers", "rms_norm_eps")}
        c["experts_held"] = tuple(range(first, first + per))
        every = int(kn["full_attn_every"])
        c["full_attn_layers"] = tuple(range(every, int(kn["num_hidden_layers"]) + 1, every))
        return tuple(sorted(c.items()))

    def build_module(self, num_classes, input_shape):
        return _KimiLinear(cfg=self.module_config(), vocab=int(num_classes))

    def _kernel_counts(self, mixers, fused):
        return {"count.mla.fused": fused[0],
                "count.mla.layers": jnp.float32(mixers.count("mla")),
                "count.kda.fused": fused[1],
                "count.kda.layers": jnp.float32(mixers.count("kda")),
                "count.kda.branch_fused": fused[2]}


if __name__ == "__main__":
    # Dev harness run (`python -m rafiki_tpu.models.kimi_linear`): an
    # explicit CPU request is applied before the first backend use.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu.model.dev import test_model_class

    _fixed = {k: v.value for k, v in KimiLinear.get_knob_config().items()
              if isinstance(v, FixedKnob)}
    test_model_class(
        KimiLinear, "LANGUAGE_MODELING",
        "synthetic://tokens?vocab=256&n=16&len=96&seed=0",
        "synthetic://tokens?vocab=256&n=4&len=96&seed=1",
        queries=[[5, 9, 3] * 8, [17, 2] * 12],
        knobs=dict(_fixed, learning_rate=1e-3, label_smoothing=0.05),
    )
