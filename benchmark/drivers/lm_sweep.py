"""Driver of a language-model sweep cell: one train job through
``LocalScheduler`` in the serial lane (``trial_pack`` 1).

As ``drivers/sweep.py`` (whose helpers it uses) with a trial where that has
a pack round: set-up makes the stores, the model file a tenant would upload
(the committed template's bytes plus a subclass that pins the
configuration's knobs), the token streams' URIs from ``--seed``, and runs
the traffic's warm-up trial as a job of its own. That trial is the
comparison's *first-step trial* too: the same entry on a train set of one
batch, so one optimizer step (the template runs an epoch step by step, so
the step it compiles is the window's own whatever the train set's length).
The window is one ``run_train_job`` on a job whose ``TIME_HOURS`` budget is
``--seconds``. ``verify`` compares the first-step trial and one trial of the
window, drawn from the seed, with the plain reference (``lm_check.py``) once
the program's state is freed. ``layer_inputs`` adds the reference's FLOP count, the
chip's peak and, from the trace, the step program's device time by named
scope.
"""

from __future__ import annotations

import gc
import math
import re
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from drivers import sweep as _sweep

#: scope of the block or the step -> the per-layer metric's group; the
#: first that an instruction's ``op_name`` holds wins (a backward op keeps
#: its forward scope inside ``transpose(jvp(...))``).
SCOPE_GROUPS = (("lm.loss", "loss"), ("moe.", "moe"), ("kda", "kda"), ("mla", "mla"))


def run(ctx) -> Dict[str, Any]:
    import jax

    from rafiki_tpu import telemetry
    from rafiki_tpu.config import Config, set_config
    from rafiki_tpu.model.dataset import dataset_utils
    from rafiki_tpu.ops.train import clear_program_cache, program_cache_stats
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.utils.events import events

    import check
    import datagen
    import lm_check
    import lm_datagen
    import numpy as np

    cfg, traffic, log = ctx.cfg, ctx.traffic, ctx.log
    sched = dict(traffic["scheduler"])
    seed = int(ctx.args.seed)

    compiles = _sweep.CompileCounter()
    compiles.install()
    import scipy.stats  # noqa: F401  the advisor's first fit imports these
    import sklearn.gaussian_process  # noqa: F401

    work = Path(tempfile.mkdtemp(prefix="rafiki-bench-"))
    tracer = None
    cleanups = {"n": 0}

    def cleanup() -> None:
        # run.py cleans up before ``layer_inputs``, which reads the trace
        # again: a traced run's first call keeps the directory.
        cleanups["n"] += 1
        if ctx.args.trace and cleanups["n"] == 1:
            return
        shutil.rmtree(work, ignore_errors=True)

    try:
        set_config(Config(data_dir=work / "data").ensure_dirs())
        events.configure(work / "logs")
        store = MetaStore(work / "meta.sqlite3")
        params = ParamsStore(work / "params")
        source = _sweep.model_source(ctx.repo, cfg, seed)
        train_seed, val_seed = datagen.data_seeds(seed)
        uris = (lm_datagen.token_uri(cfg, int(cfg["train_n"]), train_seed),
                lm_datagen.token_uri(cfg, int(cfg["eval_n"]), val_seed))
        ctx.lm_source, ctx.lm_uris = source, uris   # for ``epoch_program_text``
        task = cfg["task"]
        model = store.create_model("BenchModel", task, None, source, "BenchModel")

        def job(app: str, budget: dict, train_uri: str = uris[0]) -> dict:
            j = store.create_train_job(app, task, None, train_uri, uris[1], budget)
            store.create_sub_train_job(j["id"], model["id"])
            return j

        def completed(result) -> List[dict]:
            return [t for t in result.trials if t["status"] == "COMPLETED"
                    and t["score"] is not None and t["params_id"]
                    and math.isfinite(t["score"])]

        # -- warm-up: the window's own step, as a job of its own; one batch, so
        # one optimizer step: the comparison's first-step trial -----------------
        n_warm = int(traffic.get("warmup_trials", 1))
        if n_warm != 1:
            raise ValueError("this driver's warm-up is ONE trial, the first-step trial")
        t0 = time.monotonic()
        warm = LocalScheduler(store, params).run_train_job(
            job("bench-warmup", {"MODEL_TRIAL_COUNT": 1},
                lm_datagen.token_uri(cfg, lm_check.first_step_rows(cfg), train_seed))["id"],
            **sched)
        log(f"warm-up (the first-step trial): {len(completed(warm))}/1 trials in "
            f"{time.monotonic() - t0:.1f} s, compile events {compiles.snapshot()}")
        if len(completed(warm)) != 1:
            raise RuntimeError(
                f"the warm-up trial did not complete: "
                f"{[t['status'] for t in warm.trials]}, errors {warm.errors} "
                f"{[t.get('error') for t in warm.trials if t.get('error')][:1]}")
        first_row = completed(warm)[0]
        del warm

        # -- the window -------------------------------------------------------
        if ctx.args.trace:
            span = traffic["trace"]
            tracer = _sweep.Tracer(str(work / "trace"), float(span["start_s"]),
                                   float(span["seconds"]))
        c0, pc0 = compiles.snapshot(), program_cache_stats()
        counters0 = dict(telemetry.snapshot()["counters"])
        sched_obj = LocalScheduler(store, params)
        setup_s = time.time() - ctx.t_start
        win = job("bench-window", {"TIME_HOURS": float(ctx.args.seconds) / 3600.0})
        w0_wall, w0 = time.time(), time.monotonic()
        if tracer is not None:
            tracer.start()
        result = sched_obj.run_train_job(win["id"], **sched)
        window_s = time.monotonic() - w0
        if tracer is not None:
            tracer.window_done.set()
            tracer.join()
            log(f"tracer: traced {tracer.traced}, a span of "
                f"{tracer.span_s:.2f} s, stop_trace took {tracer.stop_s:.1f} s")
        c1, pc1 = compiles.snapshot(), program_cache_stats()
        snap1 = telemetry.snapshot()
        counters1, gauges = dict(snap1["counters"]), dict(snap1.get("gauges", {}))

        trials = result.trials
        done = completed(result)
        failed = len(trials) - len(done)
        log(f"window: {window_s:.2f} s, {len(trials)} trials claimed, "
            f"{len(done)} completed, job {result.status}, errors {result.errors}")
        for t in trials:
            if t not in done:
                log(f"trial {t['id'][:8]} {t['status']}: {str(t.get('error'))[-300:]}")
        spans = [r for r in telemetry.span_records() if r["ts"] >= w0_wall - 1e-3]
        epochs = [s for s in spans if s["name"] == "train.epoch"]
        by_name: Dict[str, List[float]] = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s["dur_s"])
        log("window spans (name count seconds): " + "; ".join(
            f"{n} {len(d)} {sum(d):.3f}" for n, d in
            sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:16]))
        delta = {k: counters1.get(k, 0.0) - counters0.get(k, 0.0)
                 for k in ("worker.packed_trials", "moe.slots_total",
                           "moe.slots_held", "persist.blob_bytes")}
        problems = []
        if result.status != "COMPLETED" or result.errors:
            problems.append(f"job {result.status}, errors {result.errors}")
        if delta["worker.packed_trials"] or len(epochs) < len(done):
            problems.append(
                f"{len(epochs)} train.epoch spans and "
                f"{delta['worker.packed_trials']:.0f} packed trials for "
                f"{len(done)} completed: the serial lane was not taken")
        if not done:
            problems.append("no trial completed in the window")

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        turnaround = [t["stopped_at"] - t["started_at"] for t in done]
        batch = int(cfg["knobs"]["batch_size"]["fixed"])
        measured = {
            "window_s": window_s, "setup_s": setup_s, "done": len(done),
            "claimed": len(trials), "turnaround_s": turnaround, "spans": spans,
            "steps_per_trial": int(cfg["train_n"]) // batch,
            "compiles": {key: c1[key] - c0[key] for key in c1},
            "program_cache_misses": pc1["misses"] - pc0["misses"],
            "chips": int(ctx.cell["chips"]), "counters": delta,
            "held_load_max_over_mean": gauges.get("moe.held_load_max_over_mean"),
        }
        metrics = {}
        if done:
            metrics = {"trials_per_hour": len(done) * 3600.0 / window_s,
                       "trial_turnaround_p90_s": _sweep.percentile(turnaround, 0.9)}
        metrics["setup_s"] = setup_s

        def read_back(t: dict) -> dict:
            losses = [e["values"]["loss"] for e in store.get_trial_logs(t["id"])
                      if e.get("type") == "values" and "loss" in e.get("values", {})]
            return {"knobs": t["knobs"], "score": float(t["score"]),
                    "loss": losses[-1] if losses else None,
                    "params": check.parse_params_blob(params.load(t["params_id"]))}

        # -- the first-step trial and one trial of the window, read back ------
        first: Optional[dict] = None
        trial: Optional[dict] = None
        if done and not problems:
            first = read_back(first_row)
            pick = int(np.random.default_rng(seed).integers(len(done)))
            trial = read_back(done[pick])
            log(f"check: trial {pick} of {len(done)} read back")

        # The step program's compiled text, for the device time by scope: taken
        # now, while the cached Program still holds the window's executable.
        ctx.lm_epoch_text = None
        if ctx.args.trace:
            try:
                ctx.lm_epoch_text = epoch_program_text(ctx)
            except Exception as e:  # reported, never fatal
                log(f"compiled text not taken: {type(e).__name__}: {e}")

        # -- free the program's state before the reference runs --------------
        store.close()
        events.close()
        del sched_obj, result
        dataset_utils.clear_cache()
        clear_program_cache()
        gc.collect()
        jax.clear_caches()
    finally:
        if tracer is not None:
            tracer.window_done.set()
        ctx.trace_dir = str(work / "trace")
        ctx.cleanup = cleanup

    return {"metrics": metrics, "measured": measured, "attempted": len(trials),
            "failed": failed, "problems": problems, "trial": trial, "first": first,
            "memory_peak_bytes": int(peak),
            "tracer_error": tracer.error if tracer is not None else None,
            "traced": bool(tracer is not None and tracer.traced)}


def verify(ctx, res: Dict[str, Any]) -> Dict[str, Any]:
    import lm_check

    compare = ctx.overrides.get("compare", lm_check.compare)
    return compare(ctx.cfg, int(ctx.args.seed), _sweep.model_seed(ctx.args.seed),
                   res["trial"], res["first"],
                   ctx.overrides.get("limits", ctx.cell["limits"]), ctx.log, keep=False)


def epoch_program_text(ctx) -> Optional[str]:
    """``compiled.as_text()`` of the serial lane's step program as the window
    ran it. The template runs an epoch step by step through ONE executable
    that its ``Program`` built ahead of time and keeps
    (``Program.compiled_steps``): the tenant's class is built again for its
    program's key, the cached ``Program`` fetched, and the text read off
    that executable. Nothing is compiled here (a second compile of this
    program takes minutes and gigabytes)."""
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.ops import train as T

    cls = load_model_class(ctx.lm_source, "BenchModel")
    knobs = {}
    for name, knob in cls.get_knob_config().items():
        knobs[name] = getattr(knob, "value", None)
        if knobs[name] is None:
            knobs[name] = knob.value_min
    m = cls(**knobs)
    ds = m._prepared_dataset(ctx.lm_uris[0])
    num_classes, input_shape = m._dataset_arch(ds)
    m._planned_steps = m.epochs * max(1, ds.size // m.batch_size)
    fns = m._loop_fns(num_classes, input_shape)

    def never():
        raise LookupError("the window's Program is not in the cache")

    program = T.get_program((fns["program_key"], T.mesh_cache_key(None), True), never)
    steps = list(program.compiled_steps.values())
    return steps[0].as_text() if len(steps) == 1 else None


_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def epoch_program_events(plane: dict, text: str) -> list:
    """The plane's operations that ran inside the epoch program: those that
    start within an event of the plane's ``XLA Modules`` line that bears the
    compiled text's module name (``jit_train_step(<id>)``). Instruction
    names (``fusion.12``) are unique within a module only, so the evaluation,
    the bfloat16 cast and the next trial's initialisation, which the traced
    span also holds, must not be joined to this module's scopes."""
    import trace_reduce

    module = _MODULE.search(text)
    runs = [(s, s + d) for ln in plane["lines"] if ln["name"] == "XLA Modules"
            for name, s, d in ln["events"]
            if module and name.split("(")[0].strip() == module.group(1)]
    return [e for e in trace_reduce.op_events(plane)
            if any(a <= e[1] < b for a, b in runs)]


def scope_seconds(text: str, op_seconds: Dict[str, float]) -> Dict[str, float]:
    """Device seconds by scope group: each traced operation's self time
    (``op_seconds``: the epoch program's operations alone), joined by its
    instruction's name to the ``op_name`` the compiled module holds for it.
    Operations of no listed scope go to ``other``."""
    group_of = {}
    for name, op_name in _INSTRUCTION.findall(text):
        group_of[name] = next((g for key, g in SCOPE_GROUPS if key in op_name), "other")
    out: Dict[str, float] = {"joined": 0.0, "total": 0.0}
    for name, sec in op_seconds.items():
        out["total"] += sec
        g = group_of.get(name)
        if g is None:
            continue
        out["joined"] += sec
        out[g] = out.get(g, 0.0) + sec
    return out


def layer_inputs(ctx, res: Dict[str, Any], device: Dict[str, Any]) -> None:
    import check
    import peaks
    import trace_reduce

    cfg, m = ctx.cfg, res["measured"]
    batch = int(cfg["knobs"]["batch_size"]["fixed"])
    m.update(
        forward_flops=check.reference_of(cfg).forward_flops(cfg),
        train_tokens_per_trial=m["steps_per_trial"] * batch * int(cfg["seq_len"])
        * int(cfg["knobs"]["epochs"]["fixed"]),
        eval_tokens_per_trial=int(cfg["eval_n"]) * int(cfg["seq_len"]),
        peak=peaks.peak(device["kind"]) if ctx.platform == "tpu" else None)
    # Device time by scope (PERF.md section 7, tracing (1)). Whatever fails
    # here leaves the five device-share metrics out and nothing else.
    try:
        if m.get("trace") and ctx.lm_epoch_text:
            ops: Dict[str, float] = {}
            planes = trace_reduce.device_planes(trace_reduce.load_xplane(ctx.trace_dir))
            for plane in planes:
                events = epoch_program_events(plane, ctx.lm_epoch_text)
                for name, sec in trace_reduce.self_times(events).items():
                    name = trace_reduce.short_name(name)
                    ops[name] = ops.get(name, 0.0) + sec
            seen = sorted({e[0] for p in planes for ln in p["lines"]
                           if ln["name"] == "XLA Modules" for e in ln["events"]})
            ctx.log(f"the trace's modules: {seen[:16]}")
            if ops:
                m["scope_seconds"] = scope_seconds(ctx.lm_epoch_text, ops)
                ctx.log(f"device seconds by scope, in the epoch program: {m['scope_seconds']}")
            else:
                ctx.log(f"no traced operation inside {_MODULE.findall(ctx.lm_epoch_text)[:1]}")
    except Exception as e:  # reported, never fatal
        ctx.log(f"device time by scope not taken: {type(e).__name__}: {e}")
    finally:
        ctx.cleanup()
