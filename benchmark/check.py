"""The comparison that decides ``correct`` for a sweep cell.

What the timed window produces, trial by trial, is a row in the store:
knobs, a score, and parameters in ``ParamsStore``. After the window has
closed, the peak memory has been read and the program's state is freed,
two pack rounds are compared with the configuration's plain float32
reference (``references/``), which starts from its own initial parameters
and its own copy of the data.

One whole round of the window, drawn from the seed:

``score_gap``   every member: the score the program recorded (packed
                evaluation) against the reference's accuracy of the
                parameters read back from the store (persist, read-back).
``change_gap``  one member: the reference follows the whole trial (every
                optimizer step, same rows, same dropout masks) and the
                norm of each parameter leaf's change over the trial is
                compared, by the worst leaf, with the program's (packed
                train epoch): the gap between the two norms, against the
                reference's norm of that leaf or of the median leaf,
                whichever is larger.
``val_loss_gap`` the same member: validation cross-entropy (by the
                reference's forward) of the program's parameters against
                that of the reference's own, relative.

A trial's end is 195 Adam steps from its start, and by then rounding of
any size has grown to the same tenth of a leaf's change: those numbers see
a step that does nothing or an answer that is altered, and cannot tell
bfloat16 from float8 or a whole batch from half of one. So the driver also
sends one *first-step round* through the same entry, pack width, batch and
validation set: trials of one optimizer step. One Adam step moves every
parameter by the learning rate against the sign of its gradient, so the
stored parameters of such a trial hold the sign of every element of the
first gradient, and the trial's log holds the first step's loss:

``first_step_flips``  every member: the share of parameters whose first
                update goes the other way than the reference's, or does not
                show where the reference's does. Rounding flips the sign
                where a gradient element is small against its error, so the
                share measures the precision of the whole step, forward and
                backward, element by element; over 15 million elements it
                is steady to three digits from seed to seed. Worst member.
``first_loss_gap``    every member: the first step's loss as the trial's
                log has it against the reference's, relative. Worst member.

The control is the reference put in the program's place with a float8
training step (``Fp8``), the step below the bfloat16 the configurations
state: ``stand_in_rounds`` makes both rounds of such members (or of members
with a fault planted), and ``compare`` judges them as it judges the
program's (``control.py``).
"""

from __future__ import annotations

import importlib
import json
import pickle
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import datagen

MAGIC = b"RTPK1\n"
B1, B2, EPS = 0.9, 0.999, 1e-8  # optax.scale_by_adam defaults


def reference_of(cfg: dict):
    return importlib.import_module(f"references.{cfg['reference']}")


# -- reading back what the program stored ------------------------------------

def parse_params_blob(blob: bytes) -> Dict[str, np.ndarray]:
    """A stored parameter blob as {leaf path: float32 array}. The blob is
    a pickle the program wrote ({"arch", "packed", ...}); "packed" is the
    RTPK1 layout: magic, u64-le header length, JSON [(k, shape, dtype)],
    raw little-endian buffers in that order."""
    import ml_dtypes

    payload = pickle.loads(blob)
    raw = payload["packed"]
    if raw[: len(MAGIC)] != MAGIC:
        raise ValueError("stored parameters are not an RTPK1 blob")
    off = len(MAGIC)
    hlen = int.from_bytes(raw[off: off + 8], "little")
    off += 8
    spec = json.loads(raw[off: off + hlen].decode())
    off += hlen
    out = {}
    for ent in spec:
        dt = (np.dtype(ml_dtypes.bfloat16) if ent["dtype"] == "bfloat16"
              else np.dtype(ent["dtype"]))
        shape = tuple(ent["shape"])
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        out[ent["k"]] = np.frombuffer(raw, dtype=dt, count=n, offset=off
                                      ).reshape(shape).astype(np.float32)
        off += n * dt.itemsize
    return out


# -- the reference's trial ---------------------------------------------------

def round_float8(x: jnp.ndarray, mantissa_bits: int, min_exponent: int,
                 top: float) -> jnp.ndarray:
    """``x`` rounded to the nearest value of a float8 format under a
    per-tensor scale to its range, in float32 arithmetic: a ``convert`` to
    a float8 type is whatever the platform makes of it (a chip without
    float8 units may widen it), this is the format itself. Round to nearest
    even on ``mantissa_bits``, gradual underflow below ``2**min_exponent``,
    nothing above ``top`` (the scale puts the largest value there)."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * scale
    _m, e = jnp.frexp(y)                      # |y| in [2**(e-1), 2**e)
    ulp = jnp.exp2((jnp.maximum(e - 1, min_exponent) - mantissa_bits)
                   .astype(jnp.float32))
    return jnp.clip(jnp.round(y / ulp) * ulp, -top, top) / scale


def _round_e4m3(x):
    return round_float8(x, 3, -6, 448.0)


def _round_e5m2(x):
    return round_float8(x, 2, -14, 57344.0)


@jax.custom_vjp
def _grad_e5m2(y):
    return y


_grad_e5m2.defvjp(lambda y: (y, None),
                  lambda _res, g: (_round_e5m2(g),))


class Fp8:
    """A float8 training step, the usual recipe: what goes into a matmul
    (activations and weights) in e4m3, the gradient that comes back into
    one in e5m2, each under a per-tensor scale; accumulation, normalisation
    and the optimizer stay in float32."""

    @staticmethod
    def inputs(x: jnp.ndarray) -> jnp.ndarray:
        # Straight through: the backward pass sees the identity (a cast's
        # own gradient would be rounded to fp8 unscaled, and vanish).
        q = _round_e4m3(x)
        return x + jax.lax.stop_gradient(q - x)

    output = staticmethod(_grad_e5m2)


QUANTS: Dict[Optional[str], Any] = {None: None, "fp8": Fp8}


def trial_keys(model_seed: int):
    """(step key, init key) as the program's loops derive them."""
    k = jax.random.split(jax.random.PRNGKey(int(model_seed)))
    return k[0], k[1]


def epoch_indices(n: int, batch: int, shuffle_seed: int) -> np.ndarray:
    steps = n // batch
    return (np.random.default_rng(int(shuffle_seed)).permutation(n)
            [: steps * batch].reshape(steps, batch).astype(np.int32))


def warmup_steps(planned: int) -> int:
    return min(100, max(1, planned // 10))


def first_step_rows(cfg: dict) -> int:
    """Rows of the first-step round's train set: one batch, one step."""
    return int(cfg["knobs"]["batch_size"]["fixed"])


@partial(jax.jit, static_argnames=("ref", "cfg_json", "quant", "fault"))
def _train_epoch(params, X, Y, idx, step_key, lr, rate, warmup, *, ref,
                 cfg_json, quant, fault):
    mod = importlib.import_module(f"references.{ref}")
    cfg = json.loads(cfg_json)
    q = QUANTS[quant]
    use_dropout = "dropout" in cfg["knobs"]

    def loss_fn(p, xb, yb, key):
        logits = mod.forward(p, xb, cfg, train=True,
                             dropout_key=key if use_dropout else None,
                             dropout_rate=rate, quant=q)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0].mean()

    def body(carry, ib):
        p, m, v, t, key = carry
        key, sub = jax.random.split(key)
        xb, yb = jnp.take(X, ib, axis=0), jnp.take(Y, ib, axis=0)
        if fault == "half_batch":
            xb, yb = xb[: xb.shape[0] // 2], yb[: yb.shape[0] // 2]
        loss, g = jax.value_and_grad(loss_fn)(p, xb, yb, sub)
        t1 = t + 1
        m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
        c1 = 1 - B1 ** t1.astype(jnp.float32)
        c2 = 1 - B2 ** t1.astype(jnp.float32)
        lr_t = lr * jnp.minimum((t.astype(jnp.float32) + 1.0)
                                / jnp.maximum(warmup, 1.0), 1.0)
        if fault != "state_unchanged":
            p = jax.tree.map(
                lambda a, mm, vv: a - lr_t * (mm / c1) / (jnp.sqrt(vv / c2) + EPS),
                p, m, v)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        return (p, m, v, t1, key), (loss, gn)

    zeros = jax.tree.map(jnp.zeros_like, params)
    (p, _m, _v, _t, _k), (losses, gnorms) = jax.lax.scan(
        body, (params, zeros, zeros, jnp.zeros((), jnp.int32), step_key), idx)
    return p, losses, gnorms


@partial(jax.jit, static_argnames=("ref", "cfg_json", "quant", "block"))
def _evaluate(params, X, Y, *, ref, cfg_json, quant, block):
    """(correct count, summed cross-entropy) over X in blocks of rows."""
    mod = importlib.import_module(f"references.{ref}")
    cfg = json.loads(cfg_json)
    q = QUANTS[quant]
    n = X.shape[0] // block

    def body(carry, i):
        xb = jax.lax.dynamic_slice_in_dim(X, i * block, block)
        yb = jax.lax.dynamic_slice_in_dim(Y, i * block, block)
        logits = mod.forward(params, xb, cfg, train=False, quant=q)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0].sum()
        hit = (jnp.argmax(logits, axis=-1) == yb).sum()
        return (carry[0] + hit, carry[1] + nll), None

    (hit, nll), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    return hit, nll


class Reference:
    """One configuration's reference on one run's data."""

    def __init__(self, cfg: dict, seed: int, model_seed: int):
        self.cfg = cfg
        self.cfg_json = json.dumps(cfg, sort_keys=True)
        self.ref = cfg["reference"]
        self.mod = reference_of(cfg)
        self.batch = int(cfg["knobs"]["batch_size"]["fixed"])
        self.model_seed = int(model_seed)
        train_seed, val_seed = datagen.data_seeds(seed)
        xt, yt = datagen.images_of(cfg, int(cfg["train_n"]), train_seed)
        xv, yv = datagen.images_of(cfg, int(cfg["eval_n"]), val_seed)
        x1, y1 = datagen.images_of(cfg, first_step_rows(cfg), train_seed)
        self.Xt, self.Yt = jnp.asarray(xt), jnp.asarray(yt)
        self.Xv, self.Yv = jnp.asarray(xv), jnp.asarray(yv)
        self.X1, self.Y1 = jnp.asarray(x1), jnp.asarray(y1)
        self.step_key, init_key = trial_keys(model_seed)
        self.init_params = self.mod.init(init_key, cfg)
        self._followed: Dict[str, Any] = {}  # knobs -> the plain trial

    def train(self, knobs: Dict[str, Any], quant: Optional[str] = None,
              fault: Optional[str] = None, first_step: bool = False):
        """Follow one trial: every step of its one epoch over the train set,
        or (``first_step``) over the first-step round's set of one batch.
        Returns (params, per-step losses, per-step gradient norms)."""
        memo = (json.dumps([knobs, first_step], sort_keys=True)
                if not (quant or fault) else None)
        if memo in self._followed:
            return self._followed[memo]
        if int(self.cfg["knobs"]["epochs"]["fixed"]) != 1:
            raise ValueError("the reference follows one-epoch trials")
        X, Y = (self.X1, self.Y1) if first_step else (self.Xt, self.Yt)
        idx = epoch_indices(X.shape[0], self.batch, self.model_seed)
        with jax.default_matmul_precision("highest"):
            p, losses, gnorms = _train_epoch(
                self.init_params, X, Y, jnp.asarray(idx),
                self.step_key, jnp.float32(knobs["learning_rate"]),
                jnp.float32(knobs.get("dropout", 0.0)),
                jnp.float32(warmup_steps(idx.shape[0])), ref=self.ref,
                cfg_json=self.cfg_json, quant=quant, fault=fault)
        out = p, np.asarray(losses), np.asarray(gnorms)
        if memo is not None:
            self._followed[memo] = out
        return out

    def evaluate(self, params, quant: Optional[str] = None) -> Tuple[float, float]:
        """(accuracy, mean cross-entropy) on the validation set — all of
        its rows, as the program's evaluation scores all of them."""
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        n = int(self.Xv.shape[0])
        block = next(b for b in (500, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                     if n % b == 0)
        with jax.default_matmul_precision("highest"):
            hit, nll = _evaluate(params, self.Xv, self.Yv, ref=self.ref,
                                 cfg_json=self.cfg_json, quant=quant,
                                 block=block)
        return float(hit) / n, float(nll) / n


def bf16_round(x) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def storage_noise(x: np.ndarray) -> float:
    """Norm of the rounding error that storing ``x`` in bfloat16 is
    expected to add: each element is off by up to half a unit in its last
    place (8 bits of mantissa), uniformly."""
    a = np.abs(np.asarray(x, np.float64))
    ulp = np.exp2(np.floor(np.log2(np.maximum(a, 1e-30))) - 7)
    return float(np.sqrt(np.sum(ulp * ulp) / 12.0))


def as_stored(init: Dict[str, Any], trained: Dict[str, Any]
              ) -> Tuple[Dict[str, np.ndarray], Dict[str, bool]]:
    """The reference's trained parameters as the store would keep them, and
    which leaves can show their movement through it. The store keeps
    bfloat16 (the program's ``serving_params_dtype``), so the reference's
    are rounded the same way before norms are taken; a leaf whose whole
    movement is under five times that rounding's own norm (a norm's scale
    moved by 0.004 from 1.0 by a small learning rate) is not resolvable and
    is left out of ``change_gap``, by this rule on the reference alone."""
    full = {k: np.asarray(v) for k, v in trained.items()}
    resolvable = {
        k: float(np.linalg.norm(full[k] - np.asarray(init[k])))
        >= 5.0 * storage_noise(full[k]) for k in full}
    return {k: bf16_round(v) for k, v in full.items()}, resolvable


def change_gap(init: Dict[str, Any], ref: Dict[str, Any],
               got: Dict[str, Any],
               resolvable: Optional[Dict[str, bool]] = None
               ) -> Tuple[float, str]:
    """Worst leaf's |‖got-init‖ - ‖ref-init‖| / max(‖ref-init‖ of that
    leaf, of the median leaf). Left out by a rule on the reference alone:
    leaves it moves by under a thousandth of the median leaf (a gradient
    that is nought to rounding), and leaves marked not ``resolvable``."""
    if set(ref) != set(got):
        missing = sorted(set(ref) ^ set(got))[:4]
        return float("inf"), f"leaf sets differ: {missing}"
    norms = {}
    for k in ref:
        a = np.asarray(init[k], np.float64)
        if np.asarray(got[k]).shape != a.shape:
            return float("inf"), f"shape of {k}"
        norms[k] = (float(np.linalg.norm(np.asarray(ref[k], np.float64) - a)),
                    float(np.linalg.norm(np.asarray(got[k], np.float64) - a)))
    med = float(np.median([r for r, _ in norms.values()]))
    worst, where = 0.0, ""
    for k, (r, g) in norms.items():
        if r < 1e-3 * med or (resolvable is not None and not resolvable[k]):
            continue
        gap = abs(g - r) / max(r, med)
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def pick_followed(knobs: List[dict], rng) -> int:
    """Which member of a round the reference follows through its trial:
    drawn from those at or under the round's median learning rate. At the
    top of the swept range a trial can collapse to chance, and whether it
    does turns on rounding, in bfloat16 and in float32 alike, so there the
    two have no common answer at the trial's end."""
    lrs = [k["learning_rate"] for k in knobs]
    calm = [i for i, lr in enumerate(lrs) if lr <= float(np.median(lrs))]
    return int(calm[int(rng.integers(len(calm)))])


def draw_knobs(cfg: dict, rng) -> Dict[str, float]:
    """One trial's free knobs, drawn over the configuration's ranges."""
    knobs = {}
    for name, spec in cfg["knobs"].items():
        if "float_exp" in spec:
            lo, hi = spec["float_exp"]
            knobs[name] = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        elif "float" in spec:
            lo, hi = spec["float"]
            knobs[name] = float(rng.uniform(lo, hi))
    return knobs


def first_step_flips(init: Dict[str, Any], ref: Dict[str, Any],
                     got: Dict[str, Any]) -> Tuple[float, str]:
    """(share, worst leaf) of the parameters whose first update, as the
    store shows it, is not the reference's. ``ref`` and ``got`` are
    parameters after one Adam step as the store keeps them (bfloat16), so
    an element's update shows as the sign of what it holds less the
    initial value rounded the same way (rounding keeps order, so that sign
    is the update's or nought, never the other). Counted over the elements
    whose update shows in the reference: one that goes the other way, or
    does not show, is a flip."""
    if set(ref) != set(got):
        return float("inf"), f"leaf sets differ: {sorted(set(ref) ^ set(got))[:4]}"
    flips = seen = 0
    worst, where = 0.0, ""
    for k in ref:
        base = bf16_round(init[k])
        if np.asarray(got[k]).shape != base.shape:
            return float("inf"), f"shape of {k}"
        want = np.sign(np.asarray(ref[k], np.float32) - base)
        have = np.sign(np.asarray(got[k], np.float32) - base)
        shows = want != 0
        f, n = int(np.sum(shows & (have != want))), int(shows.sum())
        flips, seen = flips + f, seen + n
        if n >= 4096 and f / n > worst:
            worst, where = f / n, k
    if seen == 0:
        return float("inf"), "no update shows in the reference"
    return flips / seen, where


def stand_in_rounds(ref: "Reference", knobs: List[dict],
                    quant: Optional[str] = None, fault: Optional[str] = None,
                    score_offset: float = 0.0) -> Tuple[List[dict], List[dict]]:
    """Both rounds as ``compare`` takes them, produced not by the program
    but by the reference put in its place: in a lower precision (the
    control) or with a fault planted. What each member holds is what the
    program's would: parameters as the store keeps them (bfloat16), the
    score of those by its own evaluation, its own first step's loss."""
    members, first = [], []
    for k in knobs:
        p, _losses, _g = ref.train(k, quant=quant, fault=fault)
        stored = {name: bf16_round(v) for name, v in p.items()}
        acc, _ = ref.evaluate(stored, quant=quant)
        members.append({"knobs": k, "score": acc + score_offset, "params": stored})
        p1, losses, _g = ref.train(k, quant=quant, fault=fault, first_step=True)
        first.append({"knobs": k, "loss": float(losses[0]),
                      "params": {name: bf16_round(v) for name, v in p1.items()}})
    return members, first


def compare(cfg: dict, seed: int, model_seed: int, members: List[dict],
            first: List[dict], follow: int, limits: Dict[str, float],
            log: Callable[[str], None] = lambda s: None,
            ref: Optional["Reference"] = None) -> Dict[str, Any]:
    """``members``: one pack round of the window, each {"knobs", "score",
    "params": {leaf: array}}; ``follow``: which of them the reference
    trains; ``first``: the first-step round, each {"knobs", "loss",
    "params"} (``ref``: the run's reference, where the caller has built it
    already). Returns {"correct", "numbers": {name: {"value", "limit"}}}."""
    ref = ref or Reference(cfg, seed, model_seed)
    flips, loss_gap, worst_first, worst_leaf1 = 0.0, 0.0, -1, ""
    if not first:
        flips = loss_gap = float("inf")
    for i, m in enumerate(first):
        p1, losses, _ = ref.train(m["knobs"], first_step=True)
        share, leaf1 = first_step_flips(
            ref.init_params, {k: bf16_round(v) for k, v in p1.items()}, m["params"])
        lgap = (abs(float(m["loss"]) - float(losses[0])) / float(losses[0])
                if m.get("loss") is not None else float("inf"))
        log(f"first-step member {i} (lr {m['knobs']['learning_rate']:.3g}): "
            f"{100 * share:.3f}% of the first updates flipped (worst leaf "
            f"{leaf1}), loss {m.get('loss')} reference {float(losses[0]):.6f}")
        if not (share <= flips):  # NaN-safe
            flips, worst_first, worst_leaf1 = share, i, leaf1
        if not (lgap <= loss_gap):
            loss_gap = lgap
    score_gap, worst_member = 0.0, -1
    val_loss_prog = None
    for i, m in enumerate(members):
        acc, nll = ref.evaluate(m["params"])
        gap = abs(float(m["score"]) - acc)
        log(f"member {i}: score {m['score']:.4f} reference accuracy of the "
            f"stored parameters {acc:.4f}")
        if not (gap <= score_gap):
            score_gap, worst_member = gap, i
        if i == follow:
            val_loss_prog = nll
    m = members[follow]
    p_ref, losses, _ = ref.train(m["knobs"])
    p_ref, resolvable = as_stored(ref.init_params, p_ref)
    cgap, leaf = change_gap(ref.init_params, p_ref, m["params"], resolvable)
    log(f"change_gap over {sum(resolvable.values())} of {len(resolvable)} "
        f"leaves (the others move by less than the store's rounding)")
    _, val_loss_ref = ref.evaluate(p_ref)
    vgap = abs(val_loss_prog - val_loss_ref) / max(val_loss_ref, 1e-9)
    log(f"followed member {follow} (lr {m['knobs']['learning_rate']:.3g}): "
        f"reference first/last step loss {losses[0]:.4f}/{losses[-1]:.4f}, "
        f"worst leaf {leaf}, validation loss {val_loss_prog:.4f} vs "
        f"{val_loss_ref:.4f}")
    values = {"first_step_flips": flips, "first_loss_gap": loss_gap,
              "score_gap": score_gap, "change_gap": cgap, "val_loss_gap": vgap}
    numbers = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    ok = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
             for n in numbers.values())
    return {"correct": bool(ok), "numbers": numbers,
            "detail": {"worst_member": worst_member, "worst_leaf": leaf,
                       "worst_first_member": worst_first,
                       "worst_first_leaf": worst_leaf1, "followed": follow}}
