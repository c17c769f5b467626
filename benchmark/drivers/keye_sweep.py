"""Driver of the ``keye_vl2`` sweep cells: ``drivers/lm_sweep.py``'s run, with
what a model whose attention reads a learned selection of its keys changes.

* ``verify``: ``lm_check.compare`` with a reference whose layers hand back
  the indexer's loss beside their output (``DsaReference``: a layer's piece
  returns (output, the loss summed over tokens), its vector-Jacobian product
  takes a cotangent for each), and one more number, ``selection_gap``: in the
  first-step trial's layers, the (query, key) pairs in one of the
  reference's selection (``lax.top_k`` of float32 scores) and the program's
  (``keye_vl2.index_inputs`` and ``keye_vl2.dsa_select``, the program's own
  functions, compiled here) and not in the other, over twice the reference's
  pairs, both given the reference's layer input: where both select
  min(t + 1, topk) keys a query, the share of the reference's pairs that were
  exchanged; a selection too large or too small reads more. It tells a
  selection that differs near its threshold (rounding of the indexer's
  bfloat16 products) from one that is wrong. It holds ``index_inputs`` and
  ``dsa_select`` as functions, recompiled here, and nothing after them:
  what the step's kernels made of the mask and its tile table is held by the
  numbers of the timed trial, ``first_step_flips`` and ``first_loss_gap``
  (the control's dense attention and top-1,024 read over them, PERF.md
  section 6), and the selection's size by ``run``'s count of the keys.
* ``run``: the window's ``dsa.*`` counters, and a problem where the keys
  selected a layer and step are not sum_t min(t + 1, topk).
* ``layer_inputs``: device seconds joined with this template's scope groups
  (``dsa.``, ``moe.``, ``lm.loss``) for ``dsa_device_share.lm`` and the
  other cells' ``.lm`` readers, and the sparse attention kernels' seconds and
  calls by name, with the work the mechanism needs
  (``references/keye_vl2.py::sparse_attention_work``), for
  ``dsa_attention_roofline.lm``.

Everything else (set-up, warm-up trial, window, read-back, the pieces' making,
Adam, the counts) is ``lm_sweep``'s and ``lm_check``'s, and the join across a
kernel's printed lines ``lfm2_sweep``'s, imported.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional
from unittest import mock

import jax
import jax.numpy as jnp

import lm_check
from drivers import lfm2_sweep, lm_sweep
from drivers import sweep as _sweep

#: scope -> the per-layer metric's group; the first that an instruction's
#: ``op_name`` holds wins, as in ``lm_sweep.SCOPE_GROUPS``.
SCOPE_GROUPS = (("lm.loss", "loss"), ("dsa.", "dsa"), ("moe.", "moe"))
#: the counters of a step this template emits, taken over the window
COUNTERS = ("dsa.layers", "dsa.fused", "dsa.keys_selected", "dsa.tiles_visited",
            "dsa.tiles_total")
#: the sparse attention's kernels by the start of their instruction's name,
#: the pass each serves and the share of that pass's needed work a call
#: carries: a step calls the forward kernel twice for one needed forward (the
#: second is ``nn.remat``'s), and the two backward kernels once each for one
#: needed backward
DSA_KERNELS = (("dsa_fwd", "forward", 0.5), ("dsa_dq", "backward", 0.5),
               ("dsa_dkv", "backward", 0.5))
#: the layer kind's name in the pieces
KIND = "dsa.moe"


class DsaPieces(lm_check.Pieces):
    """``lm_check.Pieces`` whose layer pieces carry the indexer's loss:
    ``dsa.moe.fwd`` (lp, h) -> (output, loss summed over tokens),
    ``dsa.moe.vjp`` (lp, h, dh, dloss) -> (d lp, d h); beside them
    ``select`` (lp, h) -> the layer's selection, and
    ``compare.selection`` (reference's, program's) -> (pairs in one and not
    the other, twice the reference's pairs). ``fault`` is the control's (``FAULTS`` of the
    reference)."""

    def __init__(self, cfg: dict, quant: Optional[str], fit: bool, q_block: int,
                 fault: Optional[str] = None):
        super().__init__(cfg, quant, fit, q_block)
        mod, qz = self.mod, lm_check.QUANTS[quant]
        lift = lambda lp: {f"layer_0/{k}": v for k, v in lp.items()}

        def fwd(lp, h):
            return mod.layer(lift(lp), 0, h, cfg, "dsa", True, qz, fit, q_block, fault)

        def differ(ref, got):
            return (jnp.sum(ref != (got != 0), dtype=jnp.int32),
                    2 * jnp.sum(ref, dtype=jnp.int32))

        self.fns.update({
            f"{KIND}.fwd": fwd,
            f"{KIND}.vjp": lambda lp, h, dh, dloss: jax.vjp(fwd, lp, h)[1]((dh, dloss)),
            "select": lambda lp, h: mod.selection(lift(lp), 0, h, cfg, q_block, fault, qz),
            "compare.selection": differ})

    def build_all(self, batch: int, workers: int = 8) -> Dict[str, float]:
        """As ``Pieces.build_all``, for this kind of layer's pieces."""
        cfg, mod = self.cfg, self.mod
        T, D = int(cfg["seq_len"]), int(cfg["hidden_size"])
        shapes = jax.eval_shape(lambda k: mod.init(k, cfg), jax.random.PRNGKey(0))
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        h, y = f32(batch, T, D), jax.ShapeDtypeStruct((batch, T), jnp.int32)
        tp = {k: shapes[k] for k in ("norm_out", "head")}
        layer = lm_check._of_layer(shapes, 1)
        calls = [
            ("head", (tp, h, y)), ("embed.vjp", (shapes["embed"], y, h)),
            ("head.vjp", (tp, h, y, f32(), f32())),
            (f"{KIND}.fwd", (layer, h)), (f"{KIND}.vjp", (layer, h, h, f32())),
            ("select", (layer, h)),
            ("compare.selection", (jax.ShapeDtypeStruct((batch, T, T), jnp.bool_),
                                   jax.ShapeDtypeStruct((batch, T, T), jnp.int8))),
            ("compare.flips", (shapes,) * 4), ("compare.same", (shapes,) * 2),
            ("init", (jax.ShapeDtypeStruct((2,), jnp.uint32),)),
            ("adam", (shapes,) * 4 + (jax.ShapeDtypeStruct((), jnp.int32), f32())),
            ("add", (shapes, shapes))]
        took: Dict[str, float] = {}

        def one(call) -> None:
            t0 = time.monotonic()
            self.build(call[0], *call[1])
            took[call[0]] = took.get(call[0], 0.0) + time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, calls))
        return took


def program_selection(cfg: dict) -> Callable:
    """(layer parameters, layer input [B, T, D]) -> the program's selection
    of that layer [B, T, T] int8: the template's own indexer and selection
    functions, jitted here (``DsaReference.build`` compiles it ahead, beside
    the reference's pieces)."""
    from rafiki_tpu.models import keye_vl2 as KV
    from rafiki_tpu.models import kimi_linear as K

    sa = cfg["sa_config"]
    heads, topk = int(sa["indexer_num_heads"]), int(sa["topk"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])

    @jax.jit
    def select(lp, h):
        x = K.rms_norm(h, lp["norm_attn"], eps)
        qI, kI, w = KV.index_inputs(x, lp["attn/index_q"], lp["attn/index_k"],
                                    lp["attn/index_k_norm"], lp["attn/index_k_bias"],
                                    lp["attn/index_w"], heads, theta)
        return KV.dsa_select(qI, kI, w, topk)[0]

    return select


class DsaReference(lm_check.Reference):
    """``lm_check.Reference`` for the sparse-attention configurations. A
    sequence at a time where the reference is made to fit. With ``probe``,
    the first step's layers are held to the program's selection
    (``selection_gap``)."""

    def __init__(self, cfg: dict, seed: int, model_seed: int, probe: bool = False):
        super().__init__(cfg, seed, model_seed)
        if self.opts["fit"]:
            self.opts = dict(self.opts, seq_block=1)
        self.fault: Optional[str] = None
        self.probe = program_selection(cfg) if probe else None
        self.selection_counts = [0, 0]      # pairs in one selection alone, 2 x the reference's
        self.selection_s = 0.0

    def pieces(self, quant: Optional[str] = None):
        key = (quant, self.fault)
        if key not in self._pieces:
            self._pieces[key] = DsaPieces(self.cfg, quant, self.opts["fit"],
                                          self.opts["q_block"], self.fault)
        return self._pieces[key]

    def train(self, knobs, quant=None, fault=None, **kw):
        """As ``Reference.train``; a fault of the reference's ``FAULTS`` is
        planted in the layers (until the next call), the others where they
        were."""
        self.fault = fault if fault in self.mod.FAULTS else None
        return super().train(knobs, quant=quant, fault=fault, **kw)

    def build(self) -> Dict[str, float]:
        """The pieces, and the program's selection compiled for the first
        step's layers, side by side."""
        if self.probe is None:
            return super().build()
        cfg, batch = self.cfg, self.opts["seq_block"] or self.batch
        shapes = jax.eval_shape(lambda k: self.mod.init(k, cfg), jax.random.PRNGKey(0))
        h = jax.ShapeDtypeStruct((batch, int(cfg["seq_len"]), int(cfg["hidden_size"])),
                                 jnp.float32)
        with ThreadPoolExecutor(max_workers=1) as pool:
            t0 = time.monotonic()
            probe = pool.submit(lambda: self.probe.lower(lm_check._of_layer(shapes, 1), h).compile())
            took = super().build()
            self.probe = probe.result()
            took["program.select"] = time.monotonic() - t0
        return took

    def selection_gap(self) -> float:
        differ, selected = self.selection_counts
        return differ / selected if selected else float("inf")

    def _trunk(self, pc, p, x, keep: bool):
        h, kept = self.mod.embed(p, x), []
        for i in range(1, len(pc.kinds) + 1):
            if keep:
                kept.append(h)
            h, _loss = pc.run(f"{KIND}.fwd", lm_check._of_layer(p, i), h)
        return h, kept

    def loss_and_grads(self, p, xb, yb, smoothing, quant: Optional[str] = None):
        """(the objective's mean over the batch's positions, its gradient by
        every parameter): the chain rule along the layers, each layer's
        indexer loss taking the same cotangent as the cross entropy."""
        pc = self.pieces(quant)
        tp = {k: p[k] for k in ("norm_out", "head")}
        scale = jnp.float32(1.0 / yb.size)
        total, grads = 0.0, None
        for a, b in self._blocks(xb.shape[0]):
            x, y = jnp.asarray(xb[a:b]), jnp.asarray(yb[a:b])
            h, kept = self.mod.embed(p, x), []
            for i in range(1, len(pc.kinds) + 1):
                kept.append(h)
                if self.probe is not None:
                    self._hold_to_the_program(pc, lm_check._of_layer(p, i), h)
                h, index_loss = pc.run(f"{KIND}.fwd", lm_check._of_layer(p, i), h)
                total += float(index_loss)
            self.probe = None                     # the first step's layers alone
            ce, _hits, d_tp, dh = pc.run("head.vjp", tp, h, y, jnp.float32(smoothing), scale)
            g = dict(d_tp)
            for i in range(len(pc.kinds), 0, -1):
                d_lp, dh = pc.run(f"{KIND}.vjp", lm_check._of_layer(p, i), kept.pop(), dh, scale)
                g.update({f"layer_{i}/{k}": v for k, v in d_lp.items()})
            g["embed"] = pc.run("embed.vjp", p["embed"], x, dh)
            total += float(ce)
            grads = g if grads is None else pc.run("add", grads, g)
        return total / yb.size, grads

    def _hold_to_the_program(self, pc, lp, h) -> None:
        t0 = time.monotonic()
        got = self.probe(lp, h)
        ref = pc.run("select", lp, h)
        differ, selected = jax.device_get(pc.run("compare.selection", ref, got))
        del got, ref
        self.selection_counts[0] += int(differ)
        self.selection_counts[1] += int(selected)
        self.selection_s += time.monotonic() - t0


def keys_selected_a_layer(cfg: dict) -> float:
    """sum over t of min(t + 1, topk), times the batch: a layer's keys a step."""
    from references import keye_vl2 as R

    T = int(cfg["seq_len"])
    return int(cfg["knobs"]["batch_size"]["fixed"]) * R._selected_keys(
        T, int(cfg["sa_config"]["topk"]))


def run(ctx) -> Dict[str, Any]:
    from rafiki_tpu import telemetry

    before = dict(telemetry.snapshot()["counters"])
    res = lm_sweep.run(ctx)
    counters = telemetry.snapshot()["counters"]
    grown = {k: counters.get(k, 0.0) - before.get(k, 0.0) for k in COUNTERS}
    layer_steps = grown["dsa.layers"]
    ctx.log("counters of the run (warm-up and window): " + ", ".join(
        f"{k} {v:.0f}" for k, v in grown.items()))
    res["measured"]["counters"].update(grown)
    want = keys_selected_a_layer(ctx.cfg) * layer_steps
    if layer_steps and grown["dsa.keys_selected"] != want:
        res["problems"].append(f"the selection held {grown['dsa.keys_selected']:.0f} keys in "
                               f"{layer_steps:.0f} layer steps where {want:.0f} are due")
    return res


def verify(ctx, res: Dict[str, Any]) -> Dict[str, Any]:
    compare = ctx.overrides.get("compare", lm_check.compare)
    seed, model_seed = int(ctx.args.seed), _sweep.model_seed(ctx.args.seed)
    limits = ctx.overrides.get("limits", ctx.cell["limits"])
    ref = DsaReference(ctx.cfg, seed, model_seed, probe=True)
    verdict = compare(ctx.cfg, seed, model_seed, res["trial"], res["first"], limits, ctx.log,
                      ref=ref, keep=False)
    gap = ref.selection_gap()
    ctx.log(f"selection: {ref.selection_counts[0]} pairs of the first step's layers are in "
            f"one of the reference's and the program's selections alone, of "
            f"{ref.selection_counts[1] // 2} the reference selects ({100 * gap:.4f}%); "
            f"the two selections and their comparison took {ref.selection_s:.1f} s")
    verdict["numbers"]["selection_gap"] = {"value": gap, "limit": limits["selection_gap"]}
    verdict["correct"] = bool(verdict["correct"] and gap <= limits["selection_gap"])
    return verdict


def _this_templates_groups():
    """``lfm2_sweep``'s join reads its scope groups from that module's
    ``SCOPE_GROUPS`` when it is called (a file this one leaves as it is): for
    the time of a call it holds this template's."""
    return mock.patch.object(lfm2_sweep, "SCOPE_GROUPS", SCOPE_GROUPS)


def scope_seconds(text: str, op_seconds: Dict[str, float]) -> Dict[str, float]:
    """``lfm2_sweep.scope_seconds`` with this template's groups."""
    with _this_templates_groups():
        return lfm2_sweep.scope_seconds(text, op_seconds)


def dsa_kernel_calls(events) -> Dict[str, Dict[str, float]]:
    """kernel (the start of its instruction's name) -> {"calls", "seconds"}
    over traced operations (name, start, duration in nanoseconds)."""
    import trace_reduce

    out: Dict[str, Dict[str, float]] = {}
    for name, _start, dur in events:
        short = trace_reduce.short_name(name)
        prefix = next((p for p, _pass, _share in DSA_KERNELS if short.startswith(p)), None)
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
            if ops:
                s = m["scope_seconds"] = scope_seconds(ctx.lm_epoch_text, ops)
                ctx.log(f"device seconds by scope, in the step program: {s}; joined / total "
                        f"{s['joined'] / max(s['total'], 1e-12):.4f}")
            kernels = dsa_kernel_calls(in_step)
            if kernels:
                work = ref.sparse_attention_work(cfg, sequences=batch)
                need = {key: sum(kernels.get(prefix, {"calls": 0})["calls"] * share
                                 * work[f"{which}_{key}"]
                                 for prefix, which, share in DSA_KERNELS)
                        for key in ("flops", "bytes")}
                m["dsa_kernels"] = {"seconds": sum(k["seconds"] for k in kernels.values()),
                                    "needed_flops": need["flops"], "needed_bytes": need["bytes"]}
                ctx.log(f"the sparse attention's kernels in the step program: {kernels}; "
                        f"{m['dsa_kernels']}")
    except Exception as e:  # reported, never fatal
        ctx.log(f"device time by scope not taken: {type(e).__name__}: {e}")
    finally:
        ctx.cleanup()
