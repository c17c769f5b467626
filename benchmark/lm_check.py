"""The comparison that decides ``correct`` for a language-model sweep cell.

The serial lane's unit of output is one trial: a row with knobs and a score
(next-token accuracy on the validation set), parameters in ``ParamsStore``
(bfloat16), a log with the last step's loss. After the window has closed and
the program's state is freed, two trials are compared with the
configuration's plain float32 reference (``references/kimi_linear.py``: the
recurrence token by token, whole-row softmax, every held expert on every
token), which starts from its own initial parameters and its own copy of the
token stream (``lm_datagen``).

(a) A *first-step trial* through the same entry: one optimizer step on one
batch (the cell's warm-up trial is one). One Adam step moves every parameter
by the learning rate against the sign of its gradient, so its stored
parameters hold the sign of every element of the first gradient:

``first_step_flips``  the share of parameters whose first update goes the
                other way than the reference's, or does not show where the
                reference's does, among those whose gradient stands out in the
                reference (``first_step_flips`` below: why). Measures the
                precision of the whole step, forward and backward.
``first_loss_gap``    the first step's loss as the trial's log has it (label
                smoothing included) against the reference's, relative.

(b) One trial of the window, drawn from ``--seed``:

``score_gap``   the score the program recorded against the reference's
                accuracy of the *stored* parameters on all validation tokens
                (blocked evaluation, persist, read-back).
``unmoved_share`` the share of the stored parameters that are still the
                initial ones (rounded as the store rounds): a trial whose
                state was left as it was reads 1.

The reference does NOT follow the window's trial step by step (eight
float32 steps of a 602 M-parameter model cost more than a run's whole time
limit allows: PERF.md section 6, PR 27); what several steps add to one (the
optimizer's moments, the warm-up, the shuffle) is the shared loop the image
cells' comparison follows for 195 steps in every run of theirs.

How the reference is run. Its functions are the plain ones; this file only
cuts the model at its layers, so that a *kind* of layer is one program,
compiled once and used for every layer of that kind, forward and backward
(the chain rule by hand: a layer's vector-Jacobian product is taken where
its input was kept, which is what ``jax.checkpoint`` a layer does inside
one program), and asks the compiler for its least effort
(``COMPILER_OPTIONS``): a piece runs a handful of times a run, and compiled
whole at full effort the reference took 180-220 s of a run to build on a
v5e. None of that changes what is computed: ``benchmark/tests`` hold the
pieces to ``jax.value_and_grad`` of the reference's own ``loss``.

Helpers that are not about images come from ``check.py`` (keys, shuffles,
warm-up, bfloat16 rounding, float8, the blob's layout).
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import check
import datagen
import lm_datagen
from check import B1, B2, EPS, QUANTS


#: What the reference's pieces are compiled with: the compiler's least effort
#: at making the program fast and at fitting it (the same float32 arithmetic;
#: a piece of the published size then compiles in 9 s and not 44, compiled
#: here for a described v5e: PERF.md section 6, PR 27). The CPU's compiler
#: does not know these options.
COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0, "memory_fitting_effort": -1.0}


def first_step_rows(cfg: dict) -> int:
    """Documents of the first-step trial's train set: one batch, one step."""
    return int(cfg["knobs"]["batch_size"]["fixed"])


def fit_options(cfg: dict) -> Dict[str, Any]:
    """How the reference is made to fit (none changes a number): at the
    published widths the token scan is cut into segments whose insides are
    recomputed, 256 rows of attention scores exist at a time, and a batch
    of sequences at a time goes through the layers; at a test's size
    nothing."""
    big = int(cfg["hidden_size"]) * int(cfg["seq_len"]) >= 1 << 20
    return ({"fit": True, "q_block": 256, "seq_block": first_step_rows(cfg)} if big
            else {"fit": False, "q_block": 0, "seq_block": 0})


def _adam(p, m, v, g, t, lr_t):
    """optax.scale_by_adam and the program's scaled update, written out.
    (Compiled with the moments donated, ``Pieces.DONATED``, and not the
    parameters: the first step's are the initial ones, which the comparison
    reads again.)"""
    t1 = (t + 1).astype(jnp.float32)
    m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
    v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
    c1, c2 = 1 - B1 ** t1, 1 - B2 ** t1
    p = jax.tree.map(lambda a, mm, vv: a - lr_t * (mm / c1) / (jnp.sqrt(vv / c2) + EPS),
                     p, m, v)
    return p, m, v


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _bf16(x):
    """float32 values rounded as a bfloat16 store rounds them. (Not two
    casts: inside one program the TPU's compiler drops a cast to bfloat16
    and back, as excess precision it is allowed to keep; my chip run, PR 27,
    read 3.0% flips and 0.005% unmoved that way where rounding reads 0.9%.)"""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)


def _flip_counts(init, ref, got, grad):
    """leaf -> (flips, counted): ``first_step_flips``' rule, leaf by leaf."""
    def one(init, ref, got, grad):
        base = _bf16(init)
        want, have = jnp.sign(_bf16(ref) - base), jnp.sign(got.astype(jnp.float32) - base)
        g = jnp.abs(grad)
        counted = (want != 0) & (g >= 0.1 * jnp.sqrt(jnp.mean(g * g)))
        return (jnp.sum(counted & (have != want), dtype=jnp.int32),
                jnp.sum(counted, dtype=jnp.int32))
    return {k: one(init[k], ref[k], got[k], grad[k]) for k in init}


def _same_counts(init, got):
    """leaf -> elements of ``got`` that are ``init`` rounded as the store rounds."""
    return {k: jnp.sum(_bf16(init[k]) == got[k].astype(jnp.float32), dtype=jnp.int32)
            for k in init}


def _of_layer(p: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i``'s entries of a flat parameter dict, keyed by what follows
    ``layer_<i>/``."""
    pre = f"layer_{i}/"
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


class Pieces:
    """The reference of one configuration cut at its layers: a function of
    arrays alone for each kind of layer (forward; vector-Jacobian product),
    for the head (final norm, logits, token statistics and their
    gradients) and for the embedding's gradient; beside them, as programs of
    the same making, the initial parameters, the Adam step, a sum of two
    gradients and the comparison's counts over whole parameter sets.
    ``run(name, *args)`` compiles a piece at its first call with arguments
    of those shapes."""

    #: arguments a piece may overwrite
    DONATED = {"adam": (1, 2), "add": (0,)}

    def __init__(self, cfg: dict, quant: Optional[str], fit: bool, q_block: int):
        self.mod = mod = check.reference_of(cfg)
        self.cfg = cfg
        qz = QUANTS[quant]
        self.kinds: List[Tuple[str, bool]] = list(mod.layer_kinds(cfg))
        self.fns: Dict[str, Callable] = {}
        self.exe: Dict[tuple, Any] = {}

        def layer_of(mixer: str, sparse: bool):
            def fwd(lp, h):
                return mod.layer({f"layer_0/{k}": v for k, v in lp.items()}, 0, h, cfg,
                                 mixer, sparse, qz, fit, q_block)

            def vjp(lp, h, dh):
                return jax.vjp(fwd, lp, h)[1](dh)
            return fwd, vjp

        for mixer, sparse in set(self.kinds):
            name = self.kind_name(mixer, sparse)
            self.fns[f"{name}.fwd"], self.fns[f"{name}.vjp"] = layer_of(mixer, sparse)

        def head(tp, h, y, smoothing):
            return mod.head_stats(tp, mod.final_norm(tp, h, cfg), y, smoothing, qz, fit)

        def head_vjp(tp, h, y, smoothing, scale):
            """-> (summed cross entropy, hits, d tp, d h), the cotangent
            ``scale`` on the cross entropy."""
            ce, pull, hits = jax.vjp(lambda tp, h: head(tp, h, y, smoothing), tp, h,
                                     has_aux=True)
            return (ce, hits) + pull(scale)

        self.fns["compare.flips"], self.fns["compare.same"] = _flip_counts, _same_counts
        self.fns["init"] = lambda key: mod.init(key, cfg)
        self.fns["adam"], self.fns["add"] = _adam, _tree_add
        self.fns["head"] = lambda tp, h, y: head(tp, h, y, 0.0)
        self.fns["head.vjp"] = head_vjp
        self.fns["embed.vjp"] = lambda table, x, dh: jax.vjp(
            lambda t: mod.embed({"embed": t}, x), table)[1](dh)[0]

    @staticmethod
    def kind_name(mixer: str, sparse: bool) -> str:
        return f"{mixer}.{'moe' if sparse else 'ffn'}"

    @staticmethod
    def _key(name: str, args) -> tuple:
        return (name,) + tuple(tuple(np.shape(a)) for a in jax.tree.leaves(args))

    def build(self, name: str, *args) -> tuple:
        """Compile ``name`` for arguments of these shapes (arrays or
        ``jax.ShapeDtypeStruct``s), if it is not built; its key."""
        key = self._key(name, args)
        if key not in self.exe:
            options = COMPILER_OPTIONS if jax.default_backend() == "tpu" else None
            with jax.default_matmul_precision("highest"):
                self.exe[key] = jax.jit(
                    self.fns[name], donate_argnums=self.DONATED.get(name, ())
                ).lower(*args).compile(compiler_options=options)
        return key

    def run(self, name: str, *args):
        return self.exe[self.build(name, *args)](*args)

    def build_all(self, batch: int, workers: int = 8) -> Dict[str, float]:
        """Every piece ``compare`` calls, built for ``batch`` sequences of the
        configuration's length, side by side on threads (the compiler
        releases the interpreter's lock). Returns the seconds each took."""
        cfg, mod = self.cfg, self.mod
        T, D = int(cfg["seq_len"]), int(cfg["hidden_size"])
        shapes = jax.eval_shape(lambda k: mod.init(k, cfg), jax.random.PRNGKey(0))
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        h, y = f32(batch, T, D), jax.ShapeDtypeStruct((batch, T), jnp.int32)
        tp = {k: shapes[k] for k in ("norm_out", "head")}
        args: Dict[str, tuple] = {"head": (tp, h, y), "embed.vjp": (shapes["embed"], y, h),
                                  "head.vjp": (tp, h, y, f32(), f32()),
                                  "compare.flips": (shapes,) * 4, "compare.same": (shapes,) * 2,
                                  "init": (jax.ShapeDtypeStruct((2,), jnp.uint32),),
                                  "adam": (shapes,) * 4 + (jax.ShapeDtypeStruct((), jnp.int32), f32())}
        for i, (mixer, sparse) in enumerate(self.kinds, start=1):
            name = self.kind_name(mixer, sparse)
            args[f"{name}.fwd"] = (_of_layer(shapes, i), h)
            args[f"{name}.vjp"] = (_of_layer(shapes, i), h, h)
        took: Dict[str, float] = {}

        def one(name: str) -> None:
            t0 = time.monotonic()
            self.build(name, *args[name])
            took[name] = time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, sorted(args)))
        return took


class Reference:
    """One configuration's reference on one run's token streams."""

    def __init__(self, cfg: dict, seed: int, model_seed: int):
        self.cfg = cfg
        self.mod = check.reference_of(cfg)
        self.batch = int(cfg["knobs"]["batch_size"]["fixed"])
        self.model_seed = int(model_seed)
        self.opts = fit_options(cfg)
        train_seed, val_seed = datagen.data_seeds(seed)
        self.train_set = lm_datagen.tokens_of(cfg, int(cfg["train_n"]), train_seed)
        self.val_set = lm_datagen.tokens_of(cfg, int(cfg["eval_n"]), val_seed)
        self.first_set = lm_datagen.tokens_of(cfg, first_step_rows(cfg), train_seed)
        _step_key, init_key = check.trial_keys(model_seed)
        self._init_key = init_key
        self._init_host: Optional[Dict[str, np.ndarray]] = None
        self._init_dev: Optional[Dict[str, jax.Array]] = None
        self._followed: Dict[str, Any] = {}
        self._pieces: Dict[Optional[str], Pieces] = {}
        self.first_gradient: Optional[Dict[str, np.ndarray]] = None

    def pieces(self, quant: Optional[str] = None) -> Pieces:
        if quant not in self._pieces:
            self._pieces[quant] = Pieces(self.cfg, quant, self.opts["fit"],
                                         self.opts["q_block"])
        return self._pieces[quant]

    def build(self) -> Dict[str, float]:
        """The float32 pieces at the shapes ``compare`` calls them with."""
        return self.pieces().build_all(self.opts["seq_block"] or self.batch)

    def init_params(self) -> Dict[str, np.ndarray]:
        """Initial parameters, on the host."""
        if self._init_host is None:
            self._init_host = jax.device_get(self.init_device())
        return self._init_host

    def init_device(self) -> Dict[str, jax.Array]:
        """Initial parameters, on the device (one copy, read-only)."""
        if self._init_dev is None:
            self._init_dev = (self.pieces().run("init", self._init_key)
                              if self._init_host is None
                              else jax.tree.map(jnp.asarray, self._init_host))
        return self._init_dev

    def _blocks(self, n: int):
        block = self.opts["seq_block"] or n
        return [(i, min(i + block, n)) for i in range(0, n, block)]

    def _trunk(self, pc: Pieces, p, x, keep: bool):
        """Through the layers: the last one's output, and (``keep``) every
        layer's input."""
        h = self.mod.embed(p, x)
        kept = []
        for i, (mixer, sparse) in enumerate(pc.kinds, start=1):
            if keep:
                kept.append(h)
            h = pc.run(f"{pc.kind_name(mixer, sparse)}.fwd", _of_layer(p, i), h)
        return h, kept

    def loss_and_grads(self, p, xb, yb, smoothing, quant: Optional[str] = None):
        """(mean cross entropy with label smoothing over the batch's
        positions, its gradient by every parameter): the chain rule along
        the layers, a block of sequences at a time."""
        pc = self.pieces(quant)
        tp = {k: p[k] for k in ("norm_out", "head")}
        scale = jnp.float32(1.0 / yb.size)
        total, grads = 0.0, None
        for a, b in self._blocks(xb.shape[0]):
            x, y = jnp.asarray(xb[a:b]), jnp.asarray(yb[a:b])
            h, kept = self._trunk(pc, p, x, keep=True)
            ce, _hits, d_tp, dh = pc.run("head.vjp", tp, h, y, jnp.float32(smoothing), scale)
            g = dict(d_tp)
            for i in range(len(pc.kinds), 0, -1):
                d_lp, dh = pc.run(f"{pc.kind_name(*pc.kinds[i - 1])}.vjp",
                                  _of_layer(p, i), kept.pop(), dh)
                g.update({f"layer_{i}/{k}": v for k, v in d_lp.items()})
            g["embed"] = pc.run("embed.vjp", p["embed"], x, dh)
            total += float(ce)
            grads = g if grads is None else pc.run("add", grads, g)
        return total / yb.size, grads

    def train(self, knobs: Dict[str, Any], quant: Optional[str] = None,
              fault: Optional[str] = None, first_step: bool = False,
              on_device: bool = False):
        """Follow one trial: every step of its one epoch over the train set,
        or (``first_step``) over the first-step trial's one batch. Returns
        (params, per-step losses); params and ``first_gradient`` on the host,
        or (``on_device``) left on the device. ``compare`` follows the
        first-step trial alone; the whole epoch is for the control, where
        the reference stands in for the program."""
        memo = (json.dumps([knobs, first_step, on_device], sort_keys=True)
                if not (quant or fault) else None)
        if memo in self._followed:
            return self._followed[memo]
        if int(self.cfg["knobs"]["epochs"]["fixed"]) != 1:
            raise ValueError("the reference follows one-epoch trials")
        X, Y = self.first_set if first_step else self.train_set
        idx = check.epoch_indices(X.shape[0], self.batch, self.model_seed)
        warmup = float(check.warmup_steps(idx.shape[0]))
        lr = float(knobs["learning_rate"])
        smoothing = float(knobs.get("label_smoothing", 0.0))
        p = self.init_device()
        m = v = None
        losses = []
        for t, ib in enumerate(idx):
            xb, yb = X[ib], Y[ib]
            if fault == "half_batch":
                xb, yb = xb[: len(ib) // 2], yb[: len(ib) // 2]
            loss, g = self.loss_and_grads(p, xb, yb, smoothing, quant)
            losses.append(loss)
            if first_step and memo is not None:
                self.first_gradient = g if on_device else jax.device_get(g)   # read by ``compare``
            if fault == "state_unchanged":
                continue
            lr_t = jnp.float32(lr * min((t + 1.0) / max(warmup, 1.0), 1.0))
            if m is None:
                m, v = jax.tree.map(jnp.zeros_like, p), jax.tree.map(jnp.zeros_like, p)
            p, m, v = self.pieces().run("adam", p, m, v, g, jnp.int32(t), lr_t)
            del g
        out = (p if on_device else jax.device_get(p)), np.asarray(losses)
        del p, m, v
        if memo is not None:
            self._followed[memo] = out
        return out

    def evaluate(self, params, quant: Optional[str] = None) -> Tuple[float, float]:
        """(accuracy, mean cross entropy) over every validation token."""
        pc = self.pieces(quant)
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        tp = {k: p[k] for k in ("norm_out", "head")}
        X, Y = self.val_set
        hits = ce = 0.0
        for a, b in self._blocks(X.shape[0]):
            h, _ = self._trunk(pc, p, jnp.asarray(X[a:b]), keep=False)
            c, n = pc.run("head", tp, h, jnp.asarray(Y[a:b]))
            ce, hits = ce + float(c), hits + float(n)
        return hits / Y.size, ce / Y.size


def first_step_flips(init: Dict[str, Any], ref: Dict[str, Any], got: Dict[str, Any],
                     grad: Dict[str, Any], pieces: Pieces
                     ) -> Tuple[float, str, float, Dict[str, tuple]]:
    """(share, worst leaf, share of all elements counted, {leaf: (flips,
    counted, elements)}): of the parameters whose first gradient *stands
    out* in the reference, the share whose first update, as the store shows
    it, is not the reference's. As ``check.first_step_flips`` (``ref``: the
    reference's parameters after one Adam step, rounded here as the store
    rounds; ``got``: what the store holds, bfloat16) with one more rule on
    the reference alone; ``grad`` is the reference's own first gradient.
    Adam moves every element by the learning rate against the sign of its
    gradient however small that is, and here a step's loss is a mean over
    16,384 tokens of which most rows of the embedding, the head and the
    experts meet a few: within a leaf the gradient's elements spread over
    orders of magnitude, and the sign of one far under its leaf's own scale
    is rounding's to give, in bfloat16 and in float32 alike (a first version
    that counted every element read 24-26% on every seed: my chip runs, PR
    27). An element is counted where the reference's gradient is at least a
    tenth of its leaf's root mean square and its update shows in bfloat16.
    Counted on the device (``_flip_counts``: 602 M elements a set; numpy took
    two minutes of a run), where the reference's sets already are."""
    if set(ref) != set(got) or set(ref) != set(init):
        return float("inf"), f"leaf sets differ: {sorted(set(ref) ^ set(got))[:4]}", 0.0, {}
    for k in ref:
        if np.shape(got[k]) != np.shape(init[k]):
            return float("inf"), f"shape of {k}", 0.0, {}
    dev = lambda tree: {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}
    counts = jax.device_get(pieces.run(
        "compare.flips", dev(init), dev(ref), dev(got), dev(grad)))
    leaves = {k: (int(f), int(n), int(np.prod(np.shape(init[k])))) for k, (f, n) in counts.items()}
    flips, seen, total = (sum(v[i] for v in leaves.values()) for i in range(3))
    worst, where = 0.0, ""
    for k, (f, n, _size) in leaves.items():
        if n >= 4096 and f / n > worst:
            worst, where = f / n, k
    if seen == 0:
        return float("inf"), "no update stands out and shows in the reference", 0.0, {}
    return flips / seen, where, seen / total, leaves


def unmoved_share(init: Dict[str, Any], got: Dict[str, Any], pieces: Pieces) -> float:
    """The share of the stored parameters (bfloat16) that are the initial
    ones rounded the same way. Eight Adam steps move an element by up to
    eight learning rates; what stays are the rows of the embedding whose
    token the trial never met (no gradient at all), the router's bias, and
    elements whose steps cancelled within half a bfloat16 step."""
    if set(init) != set(got) or any(np.shape(init[k]) != np.shape(got[k]) for k in init):
        return float("inf")
    dev = lambda tree: {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}
    same = jax.device_get(pieces.run("compare.same", dev(init), dev(got)))
    return sum(int(v) for v in same.values()) / max(
        sum(int(np.prod(np.shape(v))) for v in init.values()), 1)


def stand_in_trials(ref: Reference, knobs: dict, quant: Optional[str] = None,
                    fault: Optional[str] = None, score_offset: float = 0.0
                    ) -> Tuple[dict, dict]:
    """(the window's trial, the first-step trial) as ``compare`` takes
    them, produced not by the program but by the reference put in its
    place, in a lower precision (the control) or with a fault planted."""
    p, _losses = ref.train(knobs, quant=quant, fault=fault)
    stored = {k: check.bf16_round(v) for k, v in p.items()}
    acc, _ = ref.evaluate(stored, quant=quant)
    p1, losses1 = ref.train(knobs, quant=quant, fault=fault, first_step=True)
    return ({"knobs": knobs, "score": acc + score_offset, "params": stored},
            {"knobs": knobs, "loss": float(losses1[0]),
             "params": {k: check.bf16_round(v) for k, v in p1.items()}})


def compare(cfg: dict, seed: int, model_seed: int, trial: dict, first: dict,
            limits: Dict[str, float], log: Callable[[str], None] = lambda s: None,
            ref: Optional[Reference] = None, keep: bool = True) -> Dict[str, Any]:
    """``trial``: one trial of the window, {"knobs", "score", "params"};
    ``first``: the first-step trial, {"knobs", "loss", "params"}. Returns
    {"correct", "numbers": {name: {"value", "limit"}}}. The sets are compared
    on the device, where the reference's already are. ``keep`` false (a run's
    own comparison): each 2.4 GB set is let go once it is read."""
    ref = ref or Reference(cfg, seed, model_seed)
    pc = ref.pieces()
    t0 = time.monotonic()
    took = ref.build()
    log(f"the reference's pieces built in {time.monotonic() - t0:.1f} s: "
        f"{ {k: round(v, 1) for k, v in sorted(took.items())} }")
    t0 = time.monotonic()
    init = ref.init_device()
    p1, losses1 = ref.train(first["knobs"], first_step=True, on_device=True)
    jax.block_until_ready(p1)
    log(f"the reference's first step took {time.monotonic() - t0:.1f} s")
    t0 = time.monotonic()
    flips, leaf1, counted, leaves = first_step_flips(init, p1, first["params"],
                                                     ref.first_gradient, pc)
    for k in sorted(leaves, key=lambda k: -leaves[k][2])[:6]:   # the largest leaves, for the log
        f, n, size = leaves[k]
        log(f"  {k}: {100 * f / max(n, 1):.2f}% flipped, {100 * n / size:.1f}% counted")
    del p1
    if not keep:
        ref.first_gradient = None
        ref._followed.clear()
        first.pop("params")
    loss_gap = (abs(float(first["loss"]) - float(losses1[0])) / float(losses1[0])
                if first.get("loss") is not None else float("inf"))
    log(f"first-step trial (lr {first['knobs']['learning_rate']:.3g}): "
        f"{100 * flips:.3f}% of the first updates that stand out flipped ({100 * counted:.1f}% "
        f"of the elements counted, worst leaf {leaf1}), "
        f"loss {first.get('loss')} reference {float(losses1[0]):.6f}")
    stored = {k: jnp.asarray(v, jnp.float32) for k, v in trial["params"].items()}
    if not keep:
        trial.pop("params")
    t1 = time.monotonic()
    acc, val_loss = ref.evaluate(stored)
    unmoved = unmoved_share(init, stored, pc)
    del stored
    log(f"the counts took {t1 - t0:.1f} s, the reference's evaluation {time.monotonic() - t1:.1f} s")
    score_gap = abs(float(trial["score"]) - acc)
    log(f"window trial (lr {trial['knobs']['learning_rate']:.3g}): score "
        f"{trial['score']:.5f}, reference accuracy of the stored parameters {acc:.5f} "
        f"(validation loss {val_loss:.5f}), {100 * unmoved:.2f}% of them as initialised")
    values = {"first_step_flips": flips, "first_loss_gap": loss_gap,
              "score_gap": score_gap, "unmoved_share": unmoved}
    numbers = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    ok = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
             for n in numbers.values())
    return {"correct": bool(ok), "numbers": numbers,
            "detail": {"worst_first_leaf": leaf1}}
