"""Driver of a sweep cell: one train job through ``LocalScheduler``.

Set-up: stores in a work directory under ``TMPDIR``, the model file a
user would upload (the committed template's bytes plus a subclass that
pins the configuration's knobs), the data sets' URIs from ``--seed``, and
the traffic's warm-up rounds as a job of their own, so that every
program the window runs is compiled and on the device. The window opens
when ``run_train_job`` is called on a job whose ``TIME_HOURS`` budget is
``--seconds`` (created immediately before: the budget counts from the job
row's time) and closes when it returns, so the round in flight at the
deadline finishes inside it and the saver is flushed. Every rate divides
by that elapsed time. Once it has closed and the peak memory is read, one
*first-step round* goes through the same entry with the same scheduler
arguments and validation set: a pack of trials on a train set of one batch,
so of one optimizer step each, whose stored parameters and logged loss are
what ``check.py`` reads the first step's precision from.

``run(ctx)`` measures, ``verify(ctx, res)`` decides ``correct`` once the
program's state is freed, ``layer_inputs(ctx, res, device)`` gives the
per-layer readers what they read: run.py knows none of a sweep's terms.
"""

from __future__ import annotations

import gc
import math
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

_SUBCLASS = '''

class BenchModel({base}):
    """The benchmark's trial: the template above with its shape knobs
    pinned by the configuration file; the free knobs are swept."""

    @staticmethod
    def get_knob_config():
        return {{
{knobs}
        }}
'''


def model_seed(seed: int) -> int:
    return int(seed) & 0x7FFFFFFF


def model_source(repo: Path, cfg: dict, seed: int) -> bytes:
    """A model file as a user would upload it."""
    lines = []
    for name, spec in cfg["knobs"].items():
        if "fixed" in spec:
            v = spec["fixed"]
            v = model_seed(seed) if v == "$seed" else v
            lines.append(f'            "{name}": FixedKnob({v!r}),')
        elif "float" in spec:
            lo, hi = spec["float"]
            lines.append(f'            "{name}": FloatKnob({lo!r}, {hi!r}),')
        elif "float_exp" in spec:
            lo, hi = spec["float_exp"]
            lines.append(f'            "{name}": FloatKnob({lo!r}, {hi!r}, is_exp=True),')
        else:
            raise ValueError(f"knob {name}: {spec}")
    src = (repo / cfg["template_file"]).read_bytes()
    return src + _SUBCLASS.format(base=cfg["template_class"],
                                  knobs="\n".join(lines)).encode()


class Tracer(threading.Thread):
    """Traces one fixed span of the window from a thread of its own (the
    window is one blocking call): from ``start_s`` after the window opens,
    for ``seconds``, both from the traffic's file. The mix lays the span so
    that it starts inside one round's epoch program and ends inside the
    next one's, with the whole hand-over between them."""

    def __init__(self, trace_dir: str, start_s: float, seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace_dir, self.start_s, self.seconds = trace_dir, start_s, seconds
        self.window_done = threading.Event()
        self.error: Optional[str] = None
        self.traced = False
        self.span_s = float("nan")
        self.stop_s = float("nan")

    def run(self) -> None:
        import jax

        if self.window_done.wait(self.start_s):
            return
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t0 = time.monotonic()
            self.window_done.wait(self.seconds)
            t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.span_s, self.stop_s = t1 - t0, time.monotonic() - t1
            self.traced = True
        except Exception as e:  # reported in the result, never fatal
            self.error = f"{type(e).__name__}: {e}"


class CompileCounter:
    """Backend compilations and persistent-cache misses, by jax's own
    monitoring events."""

    def __init__(self) -> None:
        self.counts = {"backend_compiles": 0, "cache_hits": 0,
                       "cache_misses": 0}

    def install(self) -> None:
        import jax

        def on_event(event: str, **_kw: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.counts["cache_misses"] += 1

        def on_duration(event: str, _secs: float, **_kw: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts["backend_compiles"] += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of
    the sample at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def run(ctx) -> Dict[str, Any]:
    """``ctx``: the run's context from run.py (cell, cfg, traffic, args,
    repo, t_start, log, platform). Returns the result's fields."""
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
    import numpy as np

    cfg, traffic, log = ctx.cfg, ctx.traffic, ctx.log
    sched = dict(traffic["scheduler"])
    k = int(sched["trial_pack"])
    seed = int(ctx.args.seed)

    compiles = CompileCounter()
    compiles.install()
    # Imports the advisor makes on its first fit: set-up, not window.
    import scipy.stats  # noqa: F401
    import sklearn.gaussian_process  # noqa: F401

    work = Path(tempfile.mkdtemp(prefix="rafiki-bench-"))
    tracer = None
    try:
        set_config(Config(data_dir=work / "data").ensure_dirs())
        events.configure(work / "logs")
        store = MetaStore(work / "meta.sqlite3")
        params = ParamsStore(work / "params")
        source = model_source(ctx.repo, cfg, seed)
        train_seed, val_seed = datagen.data_seeds(seed)
        uris = (datagen.image_uri(cfg, int(cfg["train_n"]), train_seed),
                datagen.image_uri(cfg, int(cfg["eval_n"]), val_seed))
        task = "IMAGE_CLASSIFICATION"
        model = store.create_model("BenchModel", task, None, source, "BenchModel")

        def job(app: str, budget: dict, train_uri: str = uris[0]) -> dict:
            j = store.create_train_job(app, task, None, train_uri, uris[1], budget)
            store.create_sub_train_job(j["id"], model["id"])
            return j

        def packed_trials() -> float:
            return telemetry.get_counter("worker.packed_trials")

        # -- warm-up: the cell's own shapes, as a job of its own ------------
        n_warm = k * int(traffic.get("warmup_rounds", 1))
        if n_warm:
            t0 = time.monotonic()
            p0 = packed_trials()
            warm = LocalScheduler(store, params).run_train_job(
                job("bench-warmup", {"MODEL_TRIAL_COUNT": n_warm})["id"], **sched)
            warm_done = [t for t in warm.trials if t["status"] == "COMPLETED"]
            log(f"warm-up: {len(warm_done)}/{n_warm} trials in "
                f"{time.monotonic() - t0:.1f} s, compile events {compiles.snapshot()}")
            if len(warm_done) != n_warm or packed_trials() - p0 != n_warm:
                raise RuntimeError(
                    f"warm-up did not complete {n_warm} packed trials: "
                    f"{[t['status'] for t in warm.trials]}, packed "
                    f"{packed_trials() - p0}, errors {warm.errors} "
                    f"{[t.get('error') for t in warm.trials if t.get('error')][:1]}")
            del warm

        # -- the window -------------------------------------------------------
        if ctx.args.trace:
            span = traffic["trace"]
            tracer = Tracer(str(work / "trace"), float(span["start_s"]),
                            float(span["seconds"]))
        c0, pc0, p0 = compiles.snapshot(), program_cache_stats(), packed_trials()
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
        packed_in_window = packed_trials() - p0

        trials = result.trials
        done = [t for t in trials if t["status"] == "COMPLETED"
                and t["score"] is not None and t["params_id"]
                and math.isfinite(t["score"])]
        failed = len(trials) - len(done)
        log(f"window: {window_s:.2f} s, {len(trials)} trials claimed, "
            f"{len(done)} completed, job {result.status}, errors {result.errors}")
        for t in trials:
            if t not in done:
                log(f"trial {t['id'][:8]} {t['status']}: "
                    f"{str(t.get('error'))[-300:]}")
        problems = []
        if result.status != "COMPLETED" or result.errors:
            problems.append(f"job {result.status}, errors {result.errors}")
        if packed_in_window != len(done):
            # TrainWorker.run turns packing off in silence when the runner
            # is not eligible: that is a failed run, not a slow one.
            problems.append(f"{packed_in_window:.0f} packed trials for "
                            f"{len(done)} completed: the packed lane was not taken")
        if not done:
            problems.append("no trial completed in the window")

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())

        spans = [r for r in telemetry.span_records() if r["ts"] >= w0_wall - 1e-3]
        turnaround = [t["stopped_at"] - t["started_at"] for t in done]
        n_steps = int(cfg["train_n"]) // int(cfg["knobs"]["batch_size"]["fixed"])
        measured = {
            "window_s": window_s, "setup_s": setup_s, "done": len(done),
            "claimed": len(trials), "turnaround_s": turnaround,
            "spans": spans, "k": k, "steps_per_trial": n_steps,
            "compiles": {key: c1[key] - c0[key] for key in c1},
            "program_cache_misses": pc1["misses"] - pc0["misses"],
            "chips": int(ctx.cell["chips"]),
        }
        metrics = {}
        if done:
            metrics = {
                "trials_per_hour": len(done) * 3600.0 / window_s,
                "trial_turnaround_p90_s": percentile(turnaround, 0.9),
            }
        metrics["setup_s"] = setup_s

        # -- the first-step round: the same entry, one optimizer step a trial
        first: List[dict] = []
        if done and not problems:
            t0 = time.monotonic()
            one = job("bench-first-step", {"MODEL_TRIAL_COUNT": k},
                      datagen.image_uri(cfg, check.first_step_rows(cfg), train_seed))
            p0 = packed_trials()
            res1 = LocalScheduler(store, params).run_train_job(one["id"], **sched)
            ok1 = [t for t in res1.trials if t["status"] == "COMPLETED"
                   and t["params_id"]]
            log(f"first-step round: {len(ok1)}/{k} trials in "
                f"{time.monotonic() - t0:.1f} s")
            if len(ok1) != k or packed_trials() - p0 != k:
                problems.append(f"first-step round: {len(ok1)} of {k} trials "
                                f"completed, {packed_trials() - p0:.0f} packed, "
                                f"errors {res1.errors}")
            for t in ok1:
                losses = [e["values"]["loss"] for e in store.get_trial_logs(t["id"])
                          if e.get("type") == "values" and "loss" in e.get("values", {})]
                first.append({"knobs": t["knobs"],
                              "loss": losses[-1] if losses else None,
                              "params": check.parse_params_blob(
                                  params.load(t["params_id"]))})
            del res1

        # -- one pack round of the window, drawn from the seed, read back ----
        members: List[dict] = []
        follow = 0
        if done and not problems:
            rounds = [trials[i:i + k] for i in range(0, len(trials), k)]
            rounds = [r for r in rounds if len(r) == k and all(t in done for t in r)]
            if not rounds:
                problems.append("no whole pack round completed in the window")
            else:
                rng = np.random.default_rng(seed)
                chosen = rounds[int(rng.integers(len(rounds)))]
                follow = check.pick_followed([t["knobs"] for t in chosen], rng)
                for t in chosen:
                    members.append({
                        "knobs": t["knobs"], "score": float(t["score"]),
                        "params": check.parse_params_blob(
                            params.load(t["params_id"]))})
                log(f"check: round {rounds.index(chosen)} of {len(rounds)}, "
                    f"member {follow} followed")

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
        ctx.cleanup = lambda: shutil.rmtree(work, ignore_errors=True)

    return {"metrics": metrics, "measured": measured, "attempted": len(trials),
            "failed": failed, "problems": problems, "members": members,
            "first": first, "follow": follow, "memory_peak_bytes": int(peak),
            "tracer_error": tracer.error if tracer is not None else None,
            "traced": bool(tracer is not None and tracer.traced)}


def verify(ctx, res: Dict[str, Any]) -> Dict[str, Any]:
    """``correct`` for a sweep cell: the round ``run`` read back against
    the configuration's reference (check.py), under the cell's limits."""
    import check

    compare = ctx.overrides.get("compare", check.compare)
    return compare(ctx.cfg, int(ctx.args.seed), model_seed(ctx.args.seed),
                   res["members"], res["first"], res["follow"],
                   ctx.overrides.get("limits", ctx.cell["limits"]), ctx.log)


def layer_inputs(ctx, res: Dict[str, Any], device: Dict[str, Any]) -> None:
    """Adds to ``res["measured"]`` what the sweep's per-layer readers need
    beyond the window's own readings: the configuration's arithmetic (the
    reference's count of one image's forward pass) and the chip's peak."""
    import check
    import peaks

    cfg, m = ctx.cfg, res["measured"]
    batch = int(cfg["knobs"]["batch_size"]["fixed"])
    m.update(
        forward_flops=check.reference_of(cfg).forward_flops(cfg),
        train_images_per_trial=m["steps_per_trial"] * batch
        * int(cfg["knobs"]["epochs"]["fixed"]),
        eval_images_per_trial=int(cfg["eval_n"]),
        peak=peaks.peak(device["kind"]) if ctx.platform == "tpu" else None)
