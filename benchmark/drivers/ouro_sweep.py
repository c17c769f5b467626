"""Driver of the ``ouro`` sweep cells: ``drivers/lm_sweep.py``'s run, with what
a model whose stack is a loop changes.

* ``verify``: ``lm_check.compare`` with a reference that knows the loop
  (``LoopedReference``): its trunk visits the held layers R times and keeps
  every visit's input; the final norm is a piece of its own, applied after
  every pass; the head's piece runs once a pass with the token weights
  ``p_t`` and hands back each token's cross entropy; the exit gate has a
  piece of its own, whose vector-Jacobian product carries ``d loss / d p_t``
  (the cross entropies and the entropy term) back to the gate and to every
  ``h_t``; the chain rule sums a layer's gradient over its visits.
* ``layer_inputs``: device seconds joined with this template's scope groups
  (``ouro.attn``, ``lm.ffn``, ``ouro.gate``, ``lm.loss``), the attention
  kernels' seconds and calls by name for ``gqa_attention_roofline.lm``, and
  the step's ``loop.layer_calls`` for ``layer_call_ms.lm``.

Everything else (set-up, warm-up trial, window, read-back, the layer
pieces, Adam, the counts) is ``lm_sweep``'s and ``lm_check``'s, and the join
across a kernel's printed lines ``lfm2_sweep``'s, imported.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import lm_check
from drivers import lfm2_sweep, lm_sweep
from drivers import sweep as _sweep

#: scope -> the per-layer metric's group; the first that an instruction's
#: ``op_name`` holds wins, as in ``lm_sweep.SCOPE_GROUPS``.
SCOPE_GROUPS = (("lm.loss", "loss"), ("ouro.attn", "attn"), ("lm.ffn", "ffn"),
                ("ouro.gate", "gate"))
#: the counters of a step this template emits, logged after a run
COUNTERS = ("loop.passes", "loop.layer_calls", "attn.layers", "attn.fused")
#: the leaves of the head's piece, of the loop's norm and of the gate's piece
HEAD, NORM, GATE = ("head",), "norm_out", ("gate_w", "gate_b")
#: faults the control plants in the reference, beside ``lm_check``'s own
#: (half a batch, a state left unchanged): a pass too few, and exit weights
#: that no gate moves
FAULTS = ("three_passes", "uniform_exit")


class LoopedPieces(lm_check.Pieces):
    """``lm_check.Pieces`` with the loop's own: ``norm.fwd`` / ``norm.vjp``
    (``N_f``, after every pass), ``exit.fwd`` / ``exit.vjp`` (the gate, the
    exit distribution and the objective given every pass's cross entropies),
    and a head's piece without the norm, weighted per token."""

    def __init__(self, cfg: dict, quant: Optional[str], fit: bool, q_block: int):
        super().__init__(cfg, quant, fit, q_block)
        mod, qz = self.mod, lm_check.QUANTS[quant]
        beta = mod.dims(cfg)["beta"]

        def norm(scale, h):
            return mod.final_norm({NORM: scale}, h, cfg)

        def head_vjp(tp, h, y, smoothing, weights):
            """-> (each token's cross entropy, hits, d tp, d h) of
            sum(weights x cross entropy), ``weights`` [B, T] held constant."""
            def weighted(tp, h):
                ce, hit = mod.head_token_stats(tp, h, y, smoothing, qz, fit)
                return jnp.sum(weights * ce), (ce, jnp.sum(hit))
            _total, pull, (ce, hits) = jax.vjp(weighted, tp, h, has_aux=True)
            return (ce, hits) + pull(jnp.float32(1.0))

        def exit_fwd(gp, hs, uniform=False):
            """-> the exit distribution [R, B, T] of the passes' outputs ``hs``."""
            p = mod.exit_distribution(mod.gate_logits(gp, list(hs)))
            # (the control: weights no gate moves)
            return jnp.full_like(p, 1.0 / p.shape[0]) if uniform else p

        def exit_total(gp, hs, ce, uniform: bool):
            return jnp.sum(mod.objective(exit_fwd(gp, hs, uniform), ce, beta))

        def exit_vjp(gp, hs, ce, scale, uniform=False):
            """-> (the summed objective, d gp, d hs) with the cotangent
            ``scale``, every pass's cross entropies ``ce`` held constant."""
            total, pull = jax.vjp(lambda gp, hs: exit_total(gp, hs, ce, uniform), gp, hs)
            return (total,) + pull(scale)

        self.fns.update({
            "norm.fwd": norm,
            "norm.vjp": lambda scale, h, dh: jax.vjp(norm, scale, h)[1](dh),
            "head": lambda tp, h, y: mod.head_stats(tp, h, y, 0.0, qz, fit),
            "head.vjp": head_vjp,
            "exit.fwd": exit_fwd, "exit.vjp": exit_vjp,
            "exit.fwd.uniform": lambda gp, hs: exit_fwd(gp, hs, True),
            "exit.vjp.uniform": lambda gp, hs, ce, scale: exit_vjp(gp, hs, ce, scale, True)})

    def build_all(self, batch: int, workers: int = 8) -> Dict[str, float]:
        """As ``Pieces.build_all``, for the loop's pieces."""
        cfg, mod = self.cfg, self.mod
        T, D, R = int(cfg["seq_len"]), int(cfg["hidden_size"]), int(cfg["total_ut_steps"])
        shapes = jax.eval_shape(lambda k: mod.init(k, cfg), jax.random.PRNGKey(0))
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        h, y = f32(batch, T, D), jax.ShapeDtypeStruct((batch, T), jnp.int32)
        tp, gp = ({k: shapes[k] for k in names} for names in (HEAD, GATE))
        layer = lm_check._of_layer(shapes, 1)
        name = self.kind_name(*self.kinds[0])
        calls = [
            ("head", (tp, h, y)), ("embed.vjp", (shapes["embed"], y, h)),
            ("head.vjp", (tp, h, y, f32(), f32(batch, T))),
            ("norm.fwd", (shapes[NORM], h)), ("norm.vjp", (shapes[NORM], h, h)),
            ("exit.fwd", (gp, f32(R, batch, T, D))),
            ("exit.vjp", (gp, f32(R, batch, T, D), f32(R, batch, T), f32())),
            (f"{name}.fwd", (layer, h)), (f"{name}.vjp", (layer, h, h)),
            ("compare.flips", (shapes,) * 4), ("compare.same", (shapes,) * 2),
            ("init", (jax.ShapeDtypeStruct((2,), jnp.uint32),)),
            ("adam", (shapes,) * 4 + (jax.ShapeDtypeStruct((), jnp.int32), f32()))]
        # the sums of the chain rule: a leaf over its visits, a state's three
        # gradients, a batch's blocks
        sums = {leaf.shape: leaf for leaf in (h, shapes["head"], shapes[NORM], *layer.values())}
        calls += [("add", (x, x)) for x in (*sums.values(), shapes)]
        took: Dict[str, float] = {}

        def one(call) -> None:
            t0 = time.monotonic()
            self.build(call[0], *call[1])
            took[call[0]] = took.get(call[0], 0.0) + time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, calls))
        return took


class LoopedReference(lm_check.Reference):
    """``lm_check.Reference`` for a configuration whose stack is a loop. A
    sequence at a time where the reference is made to fit: it keeps R x
    layers inputs, each pass's two states and their gradients."""

    def __init__(self, cfg: dict, seed: int, model_seed: int):
        super().__init__(cfg, seed, model_seed)
        if self.opts["fit"]:
            self.opts = dict(self.opts, seq_block=1)
        self.fault: Optional[str] = None

    def pieces(self, quant: Optional[str] = None):
        if quant not in self._pieces:
            self._pieces[quant] = LoopedPieces(self.cfg, quant, self.opts["fit"],
                                               self.opts["q_block"])
        return self._pieces[quant]

    def train(self, knobs, quant=None, fault=None, **kw):
        """As ``Reference.train``; a fault of ``FAULTS`` is planted in the
        loop (``loss_and_grads``, and ``evaluate`` until the next call), the
        others where they were."""
        self.fault = fault if fault in FAULTS else None
        return super().train(knobs, quant=quant, fault=fault, **kw)

    def _passes(self) -> int:
        R = int(self.cfg["total_ut_steps"])
        return R - 1 if self.fault == "three_passes" else R

    def _exit(self, which: str) -> str:
        return f"exit.{which}.uniform" if self.fault == "uniform_exit" else f"exit.{which}"

    def _trunk(self, pc, p, x, keep: bool):
        """Through the loop: every pass's output after ``N_f``, and
        (``keep``) every layer visit's input and every pass's output before
        ``N_f``, in the order they were made."""
        name = pc.kind_name(*pc.kinds[0])
        h = self.mod.embed(p, x)
        hs, kept = [], []
        for _t in range(self._passes()):
            for i in range(1, len(pc.kinds) + 1):
                if keep:
                    kept.append(h)
                h = pc.run(f"{name}.fwd", lm_check._of_layer(p, i), h)
            if keep:
                kept.append(h)
            h = pc.run("norm.fwd", p[NORM], h)
            hs.append(h)
        return hs, kept

    def loss_and_grads(self, p, xb, yb, smoothing, quant: Optional[str] = None):
        """(the objective's mean over the batch's positions, its gradient by
        every parameter): the chain rule back through the passes, a layer's
        gradient summed over its visits, a block of sequences at a time."""
        pc = self.pieces(quant)
        name = pc.kind_name(*pc.kinds[0])
        tp, gp = ({k: p[k] for k in names} for names in (HEAD, GATE))
        scale = jnp.float32(1.0 / yb.size)

        def add(g, k, v):
            g[k] = pc.run("add", g[k], v) if k in g else v

        total, grads = 0.0, None
        for a, b in self._blocks(xb.shape[0]):
            x, y = jnp.asarray(xb[a:b]), jnp.asarray(yb[a:b])
            hs, kept = self._trunk(pc, p, x, keep=True)
            stacked = jnp.stack(hs)
            p_exit = pc.run(self._exit("fwd"), gp, stacked)
            g: Dict[str, Any] = {}
            ce, d_head = [], []
            for t, h in enumerate(hs):
                ce_t, _hits, d_tp, dh = pc.run("head.vjp", tp, h, y, jnp.float32(smoothing),
                                               p_exit[t] * scale)
                ce.append(ce_t)
                d_head.append(dh)
                add(g, "head", d_tp["head"])
            objective, d_gp, d_hs = pc.run(self._exit("vjp"), gp, stacked, jnp.stack(ce), scale)
            g.update(d_gp)
            del stacked, hs
            dh = None       # what the next pass's first layer hands back
            for t in range(len(d_head) - 1, -1, -1):
                into = pc.run("add", d_head.pop(), d_hs[t])
                if dh is not None:
                    into = pc.run("add", into, dh)
                d_scale, dh = pc.run("norm.vjp", p[NORM], kept.pop(), into)
                add(g, NORM, d_scale)
                for i in range(len(pc.kinds), 0, -1):
                    d_lp, dh = pc.run(f"{name}.vjp", lm_check._of_layer(p, i), kept.pop(), dh)
                    for k, v in d_lp.items():
                        add(g, f"layer_{i}/{k}", v)
            g["embed"] = pc.run("embed.vjp", p["embed"], x, dh)
            total += float(objective)
            grads = g if grads is None else pc.run("add", grads, g)
        return total / yb.size, grads

    def evaluate(self, params, quant: Optional[str] = None) -> Tuple[float, float]:
        """(accuracy, mean cross entropy) of the LAST pass's logits over
        every validation token."""
        pc = self.pieces(quant)
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        tp = {k: p[k] for k in HEAD}
        X, Y = self.val_set
        hits = ce = 0.0
        for a, b in self._blocks(X.shape[0]):
            hs, _ = self._trunk(pc, p, jnp.asarray(X[a:b]), keep=False)
            c, n = pc.run("head", tp, hs[-1], jnp.asarray(Y[a:b]))
            ce, hits = ce + float(c), hits + float(n)
        return hits / Y.size, ce / Y.size


def run(ctx) -> Dict[str, Any]:
    from rafiki_tpu import telemetry

    before = dict(telemetry.snapshot()["counters"])
    res = lm_sweep.run(ctx)
    counters = telemetry.snapshot()["counters"]
    steps = counters.get("loop.passes", 0.0) / max(int(ctx.cfg["total_ut_steps"]), 1)
    ctx.log("counters since the process began: " + ", ".join(
        f"{k} {counters.get(k, 0.0):.0f}" for k in COUNTERS) + f" ({steps:.0f} steps)")
    # (the window's own share: the warm-up trial's one step is taken off)
    warm = int(ctx.cfg["total_ut_steps"]) * int(ctx.cfg["num_hidden_layers"])
    res["measured"]["counters"]["loop.layer_calls"] = (
        counters.get("loop.layer_calls", 0.0) - before.get("loop.layer_calls", 0.0) - warm)
    return res


def verify(ctx, res: Dict[str, Any]) -> Dict[str, Any]:
    compare = ctx.overrides.get("compare", lm_check.compare)
    seed, model_seed = int(ctx.args.seed), _sweep.model_seed(ctx.args.seed)
    return compare(ctx.cfg, seed, model_seed, res["trial"], res["first"],
                   ctx.overrides.get("limits", ctx.cell["limits"]), ctx.log,
                   ref=LoopedReference(ctx.cfg, seed, model_seed), keep=False)


def _this_templates_groups():
    """``lfm2_sweep``'s join, kernel sums and ``layer_inputs`` read their scope
    groups from that module's ``SCOPE_GROUPS`` when they are called (an
    accepted file, not this PR's to give a parameter): for the time of a call
    it holds this template's."""
    return mock.patch.object(lfm2_sweep, "SCOPE_GROUPS", SCOPE_GROUPS)


def scope_seconds(text: str, op_seconds: Dict[str, float]) -> Dict[str, float]:
    """``lfm2_sweep.scope_seconds`` with this template's groups."""
    with _this_templates_groups():
        return lfm2_sweep.scope_seconds(text, op_seconds)


def layer_inputs(ctx, res: Dict[str, Any], device: Dict[str, Any]) -> None:
    """``lfm2_sweep.layer_inputs`` (the counts, the join, the attention
    kernels' seconds and calls by name: the same library kernel, the needed
    FLOPs a visit from this configuration's reference) with this template's
    groups."""
    with _this_templates_groups():
        lfm2_sweep.layer_inputs(ctx, res, device)
