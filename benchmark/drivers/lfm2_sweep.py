"""Driver of the ``lfm2_moe`` sweep cells: ``drivers/lm_sweep.py``'s run, with
the two things a template whose head is its embedding, and whose operators
have other names, changes.

* ``verify``: ``lm_check.compare`` with a reference whose chain rule knows the
  tied table (``TiedReference``): the head's piece takes the table where
  ``lm_check`` hands it a separate head, and the table's gradient is the sum
  of what the head's piece and the embedding's piece give.
* ``layer_inputs``: device seconds joined with this template's scope groups
  (``lfm2.conv``, ``lfm2.attn``, ``moe.``, ``lm.loss``), an instruction matched
  with its ``op_name`` across the lines a Pallas kernel's ``custom-call`` is
  printed over (PERF.md section 7 (a)), and the attention kernels' seconds
  and calls by name for ``gqa_attention_roofline.lm``.

Everything else (set-up, warm-up trial, window, read-back, the reference's
pieces, the counts) is ``lm_sweep``'s and ``lm_check``'s, imported.
"""

from __future__ import annotations

import re
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import lm_check
from drivers import lm_sweep
from drivers import sweep as _sweep

#: scope -> the per-layer metric's group; the first that an instruction's
#: ``op_name`` holds wins, as in ``lm_sweep.SCOPE_GROUPS``.
SCOPE_GROUPS = (("lm.loss", "loss"), ("moe.", "moe"), ("lfm2.conv", "conv"),
                ("lfm2.attn", "attn"))
#: the fused attention's kernels in the step program, by the start of their
#: instruction's name, with the share of ``attention_kernel_flops`` a call of
#: each needs: a step calls the forward kernel twice for one needed forward
#: (the second is ``nn.remat``'s), and the two backward kernels once each for
#: one needed backward.
ATTENTION_KERNELS = (("splash_mha_fwd", "forward", 0.5), ("splash_mha_dq", "backward", 0.5),
                     ("splash_mha_dkv", "backward", 0.5))
#: the leaves the head's piece differentiates: the final norm and the table
TIED = ("norm_out", "embed")


class TiedPieces(lm_check.Pieces):
    """``lm_check.Pieces`` whose head's pieces take ``TIED`` (no ``head`` leaf)."""

    def build_all(self, batch: int, workers: int = 8) -> Dict[str, float]:
        """As ``Pieces.build_all``, the head's pieces taking the table."""
        cfg, mod = self.cfg, self.mod
        T, D = int(cfg["seq_len"]), int(cfg["hidden_size"])
        shapes = jax.eval_shape(lambda k: mod.init(k, cfg), jax.random.PRNGKey(0))
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        h, y = f32(batch, T, D), jax.ShapeDtypeStruct((batch, T), jnp.int32)
        tp = {k: shapes[k] for k in TIED}
        args: Dict[str, tuple] = {
            "head": (tp, h, y), "embed.vjp": (shapes["embed"], y, h),
            "head.vjp": (tp, h, y, f32(), f32()), "add": (shapes["embed"],) * 2,
            "compare.flips": (shapes,) * 4, "compare.same": (shapes,) * 2,
            "init": (jax.ShapeDtypeStruct((2,), jnp.uint32),),
            "adam": (shapes,) * 4 + (jax.ShapeDtypeStruct((), jnp.int32), f32())}
        for i, (op, sparse) in enumerate(self.kinds, start=1):
            name = self.kind_name(op, sparse)
            args[f"{name}.fwd"] = (lm_check._of_layer(shapes, i), h)
            args[f"{name}.vjp"] = (lm_check._of_layer(shapes, i), h, h)
        took: Dict[str, float] = {}

        def one(name: str) -> None:
            t0 = time.monotonic()
            self.build(name, *args[name])
            took[name] = time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, sorted(args)))
        return took


class TiedReference(lm_check.Reference):
    """``lm_check.Reference`` for a configuration whose head is its table."""

    def pieces(self, quant: Optional[str] = None):
        if quant not in self._pieces:
            self._pieces[quant] = TiedPieces(self.cfg, quant, self.opts["fit"],
                                             self.opts["q_block"])
        return self._pieces[quant]

    def loss_and_grads(self, p, xb, yb, smoothing, quant: Optional[str] = None):
        """As ``Reference.loss_and_grads``; the table's gradient is the
        head's piece's plus the embedding's piece's."""
        pc = self.pieces(quant)
        tp = {k: p[k] for k in TIED}
        scale = jnp.float32(1.0 / yb.size)
        total, grads = 0.0, None
        for a, b in self._blocks(xb.shape[0]):
            x, y = jnp.asarray(xb[a:b]), jnp.asarray(yb[a:b])
            h, kept = self._trunk(pc, p, x, keep=True)
            ce, _hits, d_tp, dh = pc.run("head.vjp", tp, h, y, jnp.float32(smoothing), scale)
            g = dict(d_tp)
            for i in range(len(pc.kinds), 0, -1):
                d_lp, dh = pc.run(f"{pc.kind_name(*pc.kinds[i - 1])}.vjp",
                                  lm_check._of_layer(p, i), kept.pop(), dh)
                g.update({f"layer_{i}/{k}": v for k, v in d_lp.items()})
            g["embed"] = pc.run("add", g["embed"], pc.run("embed.vjp", p["embed"], x, dh))
            total += float(ce)
            grads = g if grads is None else pc.run("add", grads, g)
        return total / yb.size, grads

    def evaluate(self, params, quant: Optional[str] = None) -> Tuple[float, float]:
        pc = self.pieces(quant)
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        tp = {k: p[k] for k in TIED}
        X, Y = self.val_set
        hits = ce = 0.0
        for a, b in self._blocks(X.shape[0]):
            h, _ = self._trunk(pc, p, jnp.asarray(X[a:b]), keep=False)
            c, n = pc.run("head", tp, h, jnp.asarray(Y[a:b]))
            ce, hits = ce + float(c), hits + float(n)
        return hits / Y.size, ce / Y.size


def run(ctx) -> Dict[str, Any]:
    from rafiki_tpu import telemetry

    res = lm_sweep.run(ctx)
    counters = telemetry.snapshot()["counters"]
    ctx.log("counters since the process began (a step each): " + ", ".join(
        f"{k} {counters.get(k, 0.0):.0f}" for k in ("attn.fused", "attn.layers", "conv.layers")))
    return res


def verify(ctx, res: Dict[str, Any]) -> Dict[str, Any]:
    compare = ctx.overrides.get("compare", lm_check.compare)
    seed, model_seed = int(ctx.args.seed), _sweep.model_seed(ctx.args.seed)
    return compare(ctx.cfg, seed, model_seed, res["trial"], res["first"],
                   ctx.overrides.get("limits", ctx.cell["limits"]), ctx.log,
                   ref=TiedReference(ctx.cfg, seed, model_seed), keep=False)


_INSTRUCTION_START = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(text: str) -> Dict[str, str]:
    """instruction name -> its ``op_name``, over a compiled module's text. An
    instruction's text runs to the next instruction's start, so one printed
    over several lines (a Pallas kernel's ``custom-call``, whose
    ``kernel_metadata`` holds a line break) keeps its ``op_name``."""
    starts = list(_INSTRUCTION_START.finditer(text))
    out = {}
    for m, nxt in zip(starts, starts[1:] + [None]):
        found = _OP_NAME.search(text, m.end(), nxt.start() if nxt else len(text))
        if found:
            out[m.group(1)] = found.group(1)
    return out


def scope_seconds(text: str, op_seconds: Dict[str, float]) -> Dict[str, float]:
    """``lm_sweep.scope_seconds`` with this template's groups and
    ``instruction_scopes``' join."""
    group_of = {name: next((g for key, g in SCOPE_GROUPS if key in op_name), "other")
                for name, op_name in instruction_scopes(text).items()}
    out: Dict[str, float] = {"joined": 0.0, "total": 0.0}
    for name, sec in op_seconds.items():
        out["total"] += sec
        g = group_of.get(name)
        if g is None:
            continue
        out["joined"] += sec
        out[g] = out.get(g, 0.0) + sec
    return out


def attention_kernel_calls(events) -> Dict[str, Dict[str, float]]:
    """kernel (the start of its instruction's name) -> {"calls", "seconds"}
    over traced operations (name, start, duration in nanoseconds)."""
    import trace_reduce

    out: Dict[str, Dict[str, float]] = {}
    for name, _start, dur in events:
        short = trace_reduce.short_name(name)
        prefix = next((p for p, _pass, _share in ATTENTION_KERNELS if short.startswith(p)), None)
        if prefix:
            k = out.setdefault(prefix, {"calls": 0, "seconds": 0.0})
            k["calls"] += 1
            k["seconds"] += dur / 1e9
    return out


def layer_inputs(ctx, res: Dict[str, Any], device: Dict[str, Any]) -> None:
    import check
    import peaks
    import trace_reduce

    cfg, m = ctx.cfg, res["measured"]
    batch = int(cfg["knobs"]["batch_size"]["fixed"])
    ref = check.reference_of(cfg)
    m.update(
        forward_flops=ref.forward_flops(cfg),
        train_tokens_per_trial=m["steps_per_trial"] * batch * int(cfg["seq_len"])
        * int(cfg["knobs"]["epochs"]["fixed"]),
        eval_tokens_per_trial=int(cfg["eval_n"]) * int(cfg["seq_len"]),
        peak=peaks.peak(device["kind"]) if ctx.platform == "tpu" else None)
    # Whatever fails here leaves the device-share metrics and the roofline
    # out and nothing else.
    try:
        if m.get("trace") and ctx.lm_epoch_text:
            ops: Dict[str, float] = {}
            in_step = []
            for plane in trace_reduce.device_planes(trace_reduce.load_xplane(ctx.trace_dir)):
                events = lm_sweep.epoch_program_events(plane, ctx.lm_epoch_text)
                in_step += events
                for name, sec in trace_reduce.self_times(events).items():
                    name = trace_reduce.short_name(name)
                    ops[name] = ops.get(name, 0.0) + sec
            kernels = attention_kernel_calls(in_step)
            if ops:
                s = m["scope_seconds"] = scope_seconds(ctx.lm_epoch_text, ops)
                ctx.log(f"device seconds by scope, in the step program: {s}; joined / total "
                        f"{s['joined'] / max(s['total'], 1e-12):.4f}")
            if kernels:
                flops = ref.attention_kernel_flops(cfg, batch)
                m["attention_kernels"] = {
                    "seconds": sum(k["seconds"] for k in kernels.values()),
                    "needed_flops": sum(kernels.get(prefix, {"calls": 0})["calls"] * share
                                        * flops[which] for prefix, which, share in ATTENTION_KERNELS)}
                ctx.log(f"the attention's kernels in the step program: {kernels}; "
                        f"{m['attention_kernels']}")
    except Exception as e:  # reported, never fatal
        ctx.log(f"device time by scope not taken: {type(e).__name__}: {e}")
    finally:
        ctx.cleanup()
