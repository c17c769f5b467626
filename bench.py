"""Benchmark: CIFAR-10-class AutoML trial throughput on one chip.

Prints ONE JSON line on stdout (always — a watchdog guarantees it even
on hangs; failures carry an "error" field with whatever was measured).
Needs a ``tpu`` device: without an explicit CPU request
(``RAFIKI_BENCH_PLATFORM=cpu`` / ``JAX_PLATFORMS=cpu``, the tests'
smoke scale) a run that finds no chip is an error, rc=1:

  {"metric": "cifar10_automl_trials_per_hour", "value": N,
   "unit": "trials/hour/chip", "vs_baseline": R, "detail": {...}}

Method — MEASURED, not extrapolated: the headline number comes from
running a real N-trial AutoML job end to end through LocalScheduler on
this chip — GP advisor proposing knobs, trials trained/evaluated/
persisted by the actual worker loop — and dividing trials by total
wall-clock. That wall-clock INCLUDES every XLA compile, advisor call,
dataset load and parameter dump the job performed (the round-2 bench
excluded a measured 12.8s/trial compile the framework then couldn't
amortize; the program cache + persistent compilation cache now
amortize it for real, and the number says so honestly).

Canonical workload (mirrors BASELINE.md acceptance configs 2-3): VGG16
width 1.0 on CIFAR-shaped synthetic data (50k train / 10k eval,
32x32x3, 10 classes), one epoch per trial; the GP sweeps lr, dropout
and batch size — the compile-relevant axis (batch) exercises the
program cache across its 3 shape buckets.

The task is calibrated to be NON-saturating so the accuracy clause is
falsifiable (scripts/calibrate_bench_task.py): 20% of labels are
flipped uniformly, capping a perfect classifier at (1-0.2)+0.2/10 =
0.82 top-1 regardless of scale, and pixel noise sigma=0.35 makes
1-epoch accuracy measurably lr/dropout-sensitive (smoke-scale
calibration 2026-07-30: good configs 0.71-0.77, bad configs at ~0.08
chance, spread ~0.7). ``best_top1 < top1_target`` flips the bench to
an error exit — a learning regression or an advisor steering into bad
regions turns the bench red instead of shaving the headline silently.
The canonical-scale target (0.70) is provisional pending a TPU
calibration run (`scripts/calibrate_bench_task.py --canonical`).

Also reported (detail): steady-state trials/hour (median over trials
that STARTED after the last program-cache miss — stragglers included;
null when no trial ran fully warm), wall_s_to_top1_target (first
wall-clock moment any trial crossed the accuracy target — the north
star's time-to-accuracy clause), cold (first-completed) and slowest
trial durations, per-step training throughput, TWO MFU figures vs the
v5e's 197 TFLOP/s bf16 peak (XLA whole-program flops AND analytic
conv+dense model flops; both null off-TPU), advisor cost measured
POST-GP-fit (>=30 observations), a GP-vs-random ``advisor_lift`` over
>=3 seeds with its dispersion, params dump time, program/compile-cache
statistics, and acceptance config 5 served BOTH ways: the
reference-shaped one-worker-per-trial ensemble and ServicesManager's
stacked top-k path (one vmapped XLA program). The artifact also embeds
``detail.telemetry`` — the unified telemetry snapshot
(rafiki_tpu/telemetry/): per-phase trial spans (advisor-propose /
build / train / evaluate / persist), program-cache hit/miss/eviction,
host-feed vs step time, and serving-path counters — so every headline
number decomposes into attributable spans.

vs_baseline: the 120 trials/hour/GPU denominator is an ESTIMATE
(BASELINE.md §Baseline derivation: V100 mixed-precision VGG16
CIFAR-10 ~1.8k img/s => ~28s epoch + eval + AutoML overhead ~30s per
canonical trial; the reference publishes no numbers). The per-chip
ratio equals the v5e-8 vs 8xV100 pod ratio. North star: >= 8.

``detail.trial_pack`` reports the packed-vs-serial microbench: k
same-program trials trained as one vmapped pack vs back-to-back serial
(docs/trial_packing.md), with the per-trial score parity delta.

``detail.goodput`` embeds the goodput/cost ledger (rafiki_tpu/obs/):
per-trial and per-pack wall split into compile / step / feed /
checkpoint / downtime buckets plus the job-level
``goodput = productive_step_s / wall_s`` ratio. ``detail.health``
carries the numerics health totals — divergences, capsules,
evictions, contained trials, badput charged (docs/health.md) — so a
NaN epidemic is named in the artifact instead of surfacing only as a
throughput dip. The accuracy gate is calibrated for
the canonical TPU scale; on plain CPU runs a miss is recorded as
``detail.top1_note`` but stays advisory (rc 0) unless the target was
explicitly forced.

Env knobs: RAFIKI_BENCH_TRIALS (default 30), RAFIKI_BENCH_DEADLINE_S
(default 1500), RAFIKI_BENCH_PLATFORM=cpu (tiny smoke-scale run for
tests), RAFIKI_BENCH_SELFTEST_FAIL=1 (forced failure, tests the error
path).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

BASELINE_TRIALS_PER_HOUR_PER_GPU = 120.0  # estimate — BASELINE.md §Baseline derivation
CANON_TRAIN, CANON_EVAL = 50_000, 10_000

#: Artifact schema: 1 = the first rounds' shape (no marker);
#: 2 adds this field plus the ``headline`` block. Bump when a consumer
#: (scripts/bench_report.py) would need to branch on the shape.
BENCH_SCHEMA_VERSION = 2

_OUT = {
    "metric": "cifar10_automl_trials_per_hour",
    "value": 0.0,
    "unit": "trials/hour/chip",
    "vs_baseline": 0.0,
    "detail": {"baseline_basis": "120 trials/hour/GPU — ESTIMATE, derivation in BASELINE.md"},
}
_EMIT_LOCK = threading.Lock()
_emitted = False


def _emit(error: str | None = None) -> None:
    """Print the single JSON result line exactly once. The lock makes
    the watchdog wait out an in-flight normal emit instead of racing it
    (two lines / a truncated line would break the driver's parse).
    _emitted flips only AFTER a successful print: the watchdog can fire
    while the main thread is mutating detail, and a serialization error
    here must not eat the one emission the driver parses."""
    global _emitted
    with _EMIT_LOCK:
        if _emitted:
            return
        if error is not None:
            _OUT["error"] = error
        # Stamped here, not at detail-build time, so every artifact
        # shape (full, watchdog-partial, error) carries the
        # same headline block for scripts/bench_report.py to trend.
        # Older rounds spelled some keys differently — .get fallbacks,
        # absent keys trend as no-data rather than KeyError.
        d = _OUT.get("detail") or {}
        _OUT["schema_version"] = BENCH_SCHEMA_VERSION
        _OUT["headline"] = {
            "trials_per_hour": _OUT.get("value"),
            "canonical_trial_s": d.get("canonical_trial_s",
                                       d.get("canonical_compute_s")),
            "compile_s": d.get("compile_s", d.get("cold_trial_s")),
            "train_img_per_s": d.get("train_img_per_s"),
        }
        line = None
        for _ in range(3):
            try:
                line = json.dumps(_OUT)
                break
            except RuntimeError:  # detail mutated mid-serialize; retry
                time.sleep(0.05)
        if line is None:  # last resort: drop the racing detail dict
            line = json.dumps({k: v for k, v in _OUT.items() if k != "detail"})
        print(line, flush=True)
        _emitted = True


def _watchdog(deadline_s: float):
    def fire():
        try:
            _emit(error=f"deadline exceeded ({deadline_s:.0f}s); partial detail included")
        finally:
            # stdout is delivered; nothing graceful left to do.
            os._exit(3)

    t = threading.Timer(deadline_s, fire)
    t.daemon = True
    t.start()
    return t


# -- backend ----------------------------------------------------------------


def _init_backend() -> str:
    """Initialise jax and return the platform the bench runs on. An
    explicit CPU request (``RAFIKI_BENCH_PLATFORM=cpu`` or
    ``JAX_PLATFORMS=cpu``) gives the tests' smoke scale; otherwise the
    bench needs a ``tpu`` device and anything else is an error — a
    measurement path that finds no chip fails, it does not fall back."""
    if os.environ.get("RAFIKI_BENCH_SELFTEST_FAIL"):
        raise RuntimeError("selftest: forced backend failure")
    from rafiki_tpu.utils.backend import force_cpu_backend, honor_env_platform

    cpu_requested = honor_env_platform()
    if os.environ.get("RAFIKI_BENCH_PLATFORM", "").lower() == "cpu":
        force_cpu_backend()
        cpu_requested = True
    import jax

    platform = jax.devices()[0].platform
    # lint: disable=RF002 — the bench refuses to measure anywhere but the chip; the installed jax registers it as "tpu"
    if not cpu_requested and platform != "tpu":
        raise RuntimeError(
            f"bench needs a tpu device, jax found {platform!r} "
            f"({jax.devices()[0].device_kind}); set RAFIKI_BENCH_PLATFORM=cpu "
            f"for the CPU smoke scale")
    return platform


# -- canonical bench model ---------------------------------------------------
#
# The canonical trial fixes the architecture (VGG16 width 1.0, 1 epoch
# — the unit the 120/hour baseline estimate prices) and sweeps the
# tuning axes: lr (log), dropout, batch size. Source form because the
# scheduler loads model templates from uploaded bytes, same as users do.

BENCH_MODEL_SRC = b'''
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob
from rafiki_tpu.models.vgg import Vgg, _Vgg


class BenchVgg(Vgg):
    """Canonical-trial VGG16: fixed arch, tunable lr/dropout/batch."""

    @staticmethod
    def get_knob_config():
        return {
            "depth": FixedKnob(16),
            "width_mult": FixedKnob(1.0),
            "dropout": FloatKnob(0.0, 0.5),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128, 256], affects_shape=True),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }
'''

BENCH_MODEL_SRC_SMOKE = b'''
from rafiki_tpu.model.knobs import CategoricalKnob, FixedKnob, FloatKnob
from rafiki_tpu.models.vgg import Vgg, _Vgg


class BenchVgg(Vgg):
    """Smoke-scale canonical trial for CPU test runs."""

    @staticmethod
    def get_knob_config():
        return {
            "depth": FixedKnob(11),
            "width_mult": FixedKnob(0.25),
            "dropout": FloatKnob(0.0, 0.5),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": CategoricalKnob([64, 128], affects_shape=True),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }
'''


def _scale(platform: str) -> dict:
    # noise/flip and the per-scale top1 targets come from
    # scripts/calibrate_bench_task.py (see module docstring): flip=0.2
    # puts the accuracy ceiling at 0.82; targets sit below the measured
    # good-config scores and well above the ~0.1 chance floor.
    common = dict(noise=0.35, flip=0.2, lift_seeds=3, platform=platform)
    # One knob read, mode-specific fallbacks: RAFIKI_BENCH_TRIALS set
    # overrides both scales; unset, cpu smokes at 3 and tpu runs 30.
    env_trials = os.environ.get("RAFIKI_BENCH_TRIALS")
    if platform == "cpu":  # smoke run for tests: seconds, not minutes
        return dict(src=BENCH_MODEL_SRC_SMOKE, train_n=2048, eval_n=512,
                    w=8, trials=int(env_trials) if env_trials else 3,
                    micro_steps=5, canon_train=2048, canon_eval=512,
                    micro=dict(depth=11, width=0.25, batch=64),
                    lift_trials=6, lift_warmup=2,
                    top1_target=0.30, **common)
    return dict(src=BENCH_MODEL_SRC, train_n=CANON_TRAIN, eval_n=CANON_EVAL,
                w=32, trials=int(env_trials) if env_trials else 30,
                micro_steps=100, canon_train=CANON_TRAIN, canon_eval=CANON_EVAL,
                micro=dict(depth=16, width=1.0, batch=128),
                lift_trials=12, lift_warmup=4,
                top1_target=0.70, **common)


# -- the real AutoML loop (headline) ----------------------------------------


def run_real_loop(sc: dict, detail: dict) -> None:
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.ops.train import program_cache_stats

    train_uri = (f"synthetic://images?classes=10&n={sc['train_n']}"
                 f"&w={sc['w']}&h={sc['w']}&c=3&seed=0"
                 f"&noise={sc['noise']}&flip={sc['flip']}")
    val_uri = (f"synthetic://images?classes=10&n={sc['eval_n']}"
               f"&w={sc['w']}&h={sc['w']}&c=3&seed=1"
               f"&noise={sc['noise']}&flip={sc['flip']}")
    import shutil

    tmp = tempfile.mkdtemp(prefix="rafiki-bench-")
    try:
        store = MetaStore(os.path.join(tmp, "meta.sqlite3"))
        params = ParamsStore(os.path.join(tmp, "params"))
        model = store.create_model("bench-vgg", "IMAGE_CLASSIFICATION", None,
                                   sc["src"], "BenchVgg")
        job = store.create_train_job("bench", "IMAGE_CLASSIFICATION", None,
                                     train_uri, val_uri,
                                     {"MODEL_TRIAL_COUNT": sc["trials"]})
        store.create_sub_train_job(job["id"], model["id"])

        cache0 = program_cache_stats()
        wall0 = time.time()  # epoch clock, comparable to trial rows
        t0 = time.monotonic()
        result = LocalScheduler(store, params).run_train_job(
            job["id"], n_workers=1, advisor_kind="gp")
        # lint: disable=RF007 — headline wall-clock, reported in the artifact
        wall = time.monotonic() - t0
        cache1 = program_cache_stats()
        if result.best_trials:
            # Acceptance config 5 (BASELINE.md): serve the top-k trials
            # behind the predictor/bus and measure query throughput —
            # both the per-trial-worker path and the stacked path.
            try:
                _measure_serving(store, params, result, sc, detail)
            except Exception as e:  # serving metrics are additive, not fatal
                detail["serving_error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    done = [t for t in result.trials if t["status"] == "COMPLETED"]
    # In completion order: the first trial to finish paid the cold
    # compiles; later "slow" trials are stragglers, a different fact.
    timed = sorted((t for t in done
                    if t.get("stopped_at") and t.get("started_at")),
                   key=lambda t: t["stopped_at"])
    durations = [t["stopped_at"] - t["started_at"] for t in timed]
    per_trial = sorted(durations)
    # Steady state = trials that ran ENTIRELY after the last cold
    # compile (started after the final program-cache miss), stragglers
    # included — the r4 "median of the fastest half" definition
    # excluded stragglers by construction and flattered the claim.
    # None when no trial ran fully warm (honest: no steady evidence).
    last_miss = cache1.get("last_miss_ts", 0.0)
    warm = sorted(t["stopped_at"] - t["started_at"] for t in timed
                  if t["started_at"] > last_miss)
    steady_s = warm[len(warm) // 2] if warm else None

    best_top1 = max((t["score"] for t in done), default=None)
    # North-star clause 2 analog: first wall-clock moment any trial's
    # score crossed the target, measured from job submission.
    hits = [t["stopped_at"] for t in done
            if t.get("score") is not None and t.get("stopped_at")
            and t["score"] >= sc["top1_target"]]
    wall_to_target = round(min(hits) - wall0, 2) if hits else None
    detail.update({
        "measured_trials": len(done),
        "errored_trials": len(result.trials) - len(done),
        "n_workers": 1,
        "job_wall_s": round(wall, 2),
        "measured_trials_per_hour": round(3600.0 * len(done) / wall, 2),
        "cold_trial_s": round(durations[0], 2) if durations else None,
        "slowest_trial_s": round(per_trial[-1], 2) if per_trial else None,
        "steady_trial_s": round(steady_s, 3) if steady_s is not None else None,
        "steady_trials_n": len(warm),
        "steady_trials_per_hour": (round(3600.0 / steady_s, 2)
                                   if steady_s else None),
        "wall_s_to_top1_target": wall_to_target,
        "best_top1": best_top1,
        "top1_target": sc["top1_target"],
        "top1_ceiling": round((1 - sc["flip"]) + sc["flip"] / 10, 3),
        "top1_miss": best_top1 is None or best_top1 < sc["top1_target"],
        "programs_compiled": cache1["misses"] - cache0["misses"],
        "program_cache_hits": cache1["hits"] - cache0["hits"],
        "job_status": result.status,
    })
    if result.status != "COMPLETED":
        raise RuntimeError(f"bench job ended {result.status}: {result.errors[:2]}")
    _OUT["value"] = detail["measured_trials_per_hour"]
    _OUT["vs_baseline"] = round(_OUT["value"] / BASELINE_TRIALS_PER_HOUR_PER_GPU, 3)


def _predict_ok(out) -> bool:
    return not any(isinstance(o, dict) and "error" in o for o in out)


def _measure_qps(pred, queries, rounds: int = 5,
                 warm_deadline_s: float = 120) -> tuple:
    """(qps, batch_latency_ms) through a live Predictor. Warm until the
    predict program has actually compiled: the first forward can exceed
    the predictor's timeout, which surfaces as {"error": ...} entries
    rather than an exception — those must never count as served."""
    deadline = time.monotonic() + warm_deadline_s
    while not _predict_ok(pred.predict(queries[:8])):
        if time.monotonic() > deadline:
            raise RuntimeError("predict never warmed (timeouts only)")
        time.sleep(1)
    t0 = time.monotonic()
    for _ in range(rounds):
        out = pred.predict(queries)
        if not _predict_ok(out):
            raise RuntimeError("timeout/error response during timed rounds")
    # lint: disable=RF007 — QPS denominator, reported in the artifact
    dt = time.monotonic() - t0
    assert len(out) == len(queries)
    return (round(rounds * len(queries) / dt, 1), round(1000.0 * dt / rounds, 1))


def _measure_serving(store, params, result, sc: dict, detail: dict) -> None:
    """Acceptance config 5 (BASELINE.md): predictor ensemble over the
    top-k trained models. The REAL top-2 trials are served both ways
    and both throughputs reported: (a) the reference-shaped fallback —
    one InferenceWorker per trial, the predictor scatter/gathers and
    mean-ensembles — and (b) through ServicesManager's stacked
    selection (admin/services_manager.py), where same-architecture
    trials fuse into ONE vmapped XLA program (parallel/serving.py).
    ``serving_path`` records which path the services manager actually
    engaged; ``serving_k`` the ensemble width."""
    import threading

    import numpy as np

    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.worker.inference import InferenceWorker

    best = result.best_trials[:2]
    detail["serving_k"] = len(best)
    cls = load_model_class(sc["src"], "BenchVgg")
    rng = np.random.default_rng(0)
    queries = list(rng.uniform(0, 1, size=(64, sc["w"], sc["w"], 3))
                   .astype(np.float32))

    # (a) one worker per trial: predictor fans out to k workers and
    # ensembles — the reference's serving shape.
    bus = InProcBus()
    models = []
    for t in best:
        m = cls(**t["knobs"])
        m.load_parameters(params.load(t["params_id"]))
        models.append(m)
    workers = [InferenceWorker(bus, "bench-fb", f"iw-{i}", m)
               for i, m in enumerate(models)]
    threads = [threading.Thread(target=w.run, daemon=True) for w in workers]
    for th in threads:
        th.start()
    try:
        deadline = time.monotonic() + 60
        while len(bus.get_workers("bench-fb")) < len(workers):
            if time.monotonic() > deadline:
                raise RuntimeError("inference workers never registered")
            time.sleep(0.05)
        qps, lat = _measure_qps(Predictor(bus, "bench-fb"), queries)
        detail["serving_qps_per_worker"] = qps
        detail["serving_batch_latency_ms"] = lat
    finally:
        for w in workers:
            w.stop()
        for th in threads:
            th.join(timeout=10)
        for m in models:
            m.destroy()

    if len(best) < 2:
        detail["serving_path"] = "per-trial (k=1)"
        return
    # (b) the stacked path, through the real services manager: it
    # re-loads the trial models itself and fuses them when stackable.
    from rafiki_tpu.admin.services_manager import ServicesManager

    inf = store.create_inference_job(result.job_id, None)
    sm = ServicesManager(store, params)
    pred = sm.create_inference_services(inf["id"], best, serve_http=False)
    try:
        handle = sm._inference_jobs[inf["id"]]
        path = ("stacked" if len(handle.workers) < len(best)
                else "per-trial-fallback")
        detail["serving_path"] = path
        qps, lat = _measure_qps(pred, queries)
        if path == "stacked":
            detail["serving_qps_stacked"] = qps
            detail["serving_batch_latency_stacked_ms"] = lat
        else:  # heterogeneous top-k: record it honestly, don't relabel
            detail["serving_qps_fallback_via_services_manager"] = qps
            detail["serving_batch_latency_fallback_ms"] = lat
    finally:
        sm.stop_inference_services(inf["id"])


# -- trial packing: packed-vs-serial microbench ------------------------------

PACK_MODEL_SRC = b'''
from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models.ff import _Mlp


class PackFF(JaxModel):
    """Fixed-shape FF for the trial-pack microbench: every lr shares
    one program key, so k trials always bucket into one pack."""

    @staticmethod
    def get_knob_config():
        return {
            "learning_rate": FloatKnob(1e-4, 1e-1, is_exp=True),
            "batch_size": FixedKnob(64),
            "epochs": FixedKnob(2),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Mlp(hidden_layers=2, hidden_units=128, num_classes=num_classes)
'''


def run_trial_pack_micro(sc: dict, detail: dict) -> None:
    """Packed-vs-serial trial throughput (docs/trial_packing.md): k
    same-program trials trained once back-to-back serially and once as
    a single vmapped pack, both WARM (each path's programs compiled by
    a throwaway round first — this measures the steady state the
    packing lever targets, not compile amortization, which is the
    program cache's own detail block). ``max_score_delta`` doubles as
    a parity check: packed per-trial scores must match serial ones."""
    from rafiki_tpu.model.base import load_model_class

    cls = load_model_class(PACK_MODEL_SRC, "PackFF")
    train = (f"synthetic://images?classes=10&n=2048&w=8&h=8&c=3&seed=0"
             f"&noise={sc['noise']}&flip={sc['flip']}")
    val = (f"synthetic://images?classes=10&n=512&w=8&h=8&c=3&seed=1"
           f"&noise={sc['noise']}&flip={sc['flip']}")
    k, epochs = 4, 2
    lrs = [3e-3, 1e-2, 3e-2, 1e-3]

    def serial_once() -> list:
        scores = []
        for lr in lrs:
            m = cls(learning_rate=lr)
            m.train(train)
            scores.append(float(m.evaluate(val)))
            m.destroy()
        return scores

    def packed_once() -> list:
        models = [cls(learning_rate=lr) for lr in lrs]
        cls.train_packed(models, train)
        scores = cls.evaluate_packed(models, val)
        for m in models:
            m.destroy()
        return scores

    serial_once()
    packed_once()  # both compiled programs now warm
    t0 = time.monotonic()
    s_serial = serial_once()
    # lint: disable=RF007 — packed-vs-serial A/B wall, reported in detail
    serial_s = time.monotonic() - t0
    t0 = time.monotonic()
    s_packed = packed_once()
    # lint: disable=RF007 — packed-vs-serial A/B wall, reported in detail
    packed_s = time.monotonic() - t0
    detail["trial_pack"] = {
        "k": k,
        "epochs": epochs,
        "serial_s": round(serial_s, 3),
        "serial_s_per_trial": round(serial_s / k, 3),
        "packed_s": round(packed_s, 3),
        "packed_s_per_trial": round(packed_s / k, 3),
        "speedup_vs_serial": round(serial_s / packed_s, 2),
        "max_score_delta": round(max(abs(a - b)
                                     for a, b in zip(s_serial, s_packed)), 4),
    }


# -- advisor lift: GP vs random on tiny real trials --------------------------

LIFT_MODEL_SRC = b'''
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models.vgg import Vgg


class LiftVgg(Vgg):
    """Tiny real-training probe for GP-vs-random lift: one shape
    bucket (fixed batch), wide log-lr axis where quality varies."""

    @staticmethod
    def get_knob_config():
        return {
            "depth": FixedKnob(11),
            "width_mult": FixedKnob(0.25),
            "dropout": FloatKnob(0.0, 0.5),
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True),
            "batch_size": FixedKnob(64),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }
'''


def run_advisor_lift(sc: dict, detail: dict) -> None:
    """GP-vs-random lift from tiny-but-real trials on the calibrated
    task (the knob space is where 1-epoch top-1 demonstrably varies —
    see scripts/calibrate_bench_task.py). Both advisors run the same
    trial count with fixed seeds; ``advisor_lift`` = mean post-warmup
    GP score minus the random advisor's mean over the same positions —
    the exploitation the GP buys once it has observations. Kept tiny
    (VGG11 w=0.25 on 8x8) so it costs seconds, not the headline's
    minutes; the full-size advisor quality signal is the headline
    job's gated best_top1."""
    from rafiki_tpu.advisor.gp import GpAdvisor
    from rafiki_tpu.advisor.random_advisor import RandomAdvisor
    from rafiki_tpu.model.base import load_model_class

    cls = load_model_class(LIFT_MODEL_SRC, "LiftVgg")
    train = (f"synthetic://images?classes=10&n=2048&w=8&h=8&c=3&seed=0"
             f"&noise={sc['noise']}&flip={sc['flip']}")
    val = (f"synthetic://images?classes=10&n=512&w=8&h=8&c=3&seed=1"
           f"&noise={sc['noise']}&flip={sc['flip']}")
    n, warmup = sc["lift_trials"], sc["lift_warmup"]

    def sweep(advisor) -> list:
        scores = []
        for _ in range(n):
            knobs = advisor.propose()
            m = cls(**knobs)
            m.train(train)
            s = float(m.evaluate(val))
            m.destroy()
            advisor.feedback(s, knobs)
            scores.append(round(s, 4))
        return scores

    kc = cls.get_knob_config()
    mean = lambda xs: sum(xs) / len(xs)
    # >=3 seeds with dispersion (r4 directive 8): a one-seed lift at
    # smoke scale is within noise; the claim must carry its spread.
    lifts, best_lifts = [], []
    diffs, gp_scores = [], []
    t0 = time.monotonic()
    for s in range(sc["lift_seeds"]):
        s_gp = sweep(GpAdvisor(kc, seed=s, n_initial=warmup))
        s_rnd = sweep(RandomAdvisor(kc, seed=100 + s))
        lifts.append(round(mean(s_gp[warmup:]) - mean(s_rnd[warmup:]), 4))
        best_lifts.append(round(max(s_gp) - max(s_rnd), 4))
        # position-paired post-warmup diffs, pooled across seeds: the
        # bootstrap resamples these, so the CI reflects both seed and
        # position noise (docs/search_anatomy.md).
        diffs.extend(g - r for g, r in zip(s_gp[warmup:], s_rnd[warmup:]))
        gp_scores.extend(s_gp)
    # lint: disable=RF007 — sweep A/B wall, reported in detail.search
    sweep_wall_s = time.monotonic() - t0
    m_lift = mean(lifts)
    spread = max(abs(l - m_lift) for l in lifts)
    detail["advisor_lift"] = round(m_lift, 4)
    detail["advisor_lift_spread"] = round(spread, 4)
    detail["advisor_lift_per_seed"] = lifts
    # significant only when the whole dispersion band clears zero
    detail["advisor_lift_significant"] = (m_lift - spread) > 0
    detail["advisor_lift_best"] = round(mean(best_lifts), 4)
    detail["advisor_lift_trials"] = n * sc["lift_seeds"]
    # Search-anatomy block: the same lift claim with a bootstrap CI
    # (fixed seed — byte-reproducible across runs on the same scores),
    # plus the probe sweep's regret curve and effective throughput so
    # bench_report --sweep can trend them from SWEEP_r*.json siblings.
    from rafiki_tpu.obs.search import stats as search_stats

    ci = search_stats.bootstrap_ci(diffs, seed=0)
    curve = search_stats.regret_curve(gp_scores)
    n_scored = 2 * n * sc["lift_seeds"]
    detail["search"] = {
        "advisor_lift": round(ci["mean"], 4),
        "lift_ci_low": round(ci["lo"], 4),
        "lift_ci_high": round(ci["hi"], 4),
        "lift_significant": ci["lo"] > 0,
        "n_diffs": ci["n"],
        "n_boot": ci["n_boot"],
        "boot_seed": ci["seed"],
        "best_score": curve["best_score"],
        "regret": curve["mean_regret"],
        "n_scored": n_scored,
        "sweep_wall_s": round(sweep_wall_s, 3),
        "effective_trials_per_hour": round(
            n_scored / sweep_wall_s * 3600.0, 2) if sweep_wall_s else 0.0,
    }
    # Curve-advisor plane (docs/early_kill.md): the probe sweep above
    # never kills (no epoch loop), so these are 0 here — but headline
    # runs under RAFIKI_CURVE_KILL pick up the session's counters, and
    # bench_report --sweep trends them alongside the throughput claim.
    from rafiki_tpu.obs.search.ledger import search_ledger

    snap = search_ledger.snapshot()
    for k in ("n_killed", "n_false_kills", "n_speculations",
              "n_corrections"):
        detail["search"][k] = snap.get(k, 0)


# -- microbench: step throughput, MFU, advisor, dump ------------------------


def _vgg_train_flops_per_image(depth: int, width_mult: float, w: int,
                               num_classes: int = 10) -> float:
    """Analytic conv+dense flops (2*MACs) for one image's forward pass
    through ``models/vgg._Vgg``, tripled for the train step (backward
    ~= 2x forward for conv/dense — the conventional model-flops MFU
    numerator, vs XLA's whole-program count which also bills norms,
    pooling, optimizer update and padding)."""
    from rafiki_tpu.models.vgg import _CFGS

    h = wd = w
    cin, fwd = 3, 0.0
    for v in _CFGS[depth]:
        if v == "M":
            if min(h, wd) >= 2:
                h, wd = h // 2, wd // 2
            continue
        cout = max(8, int(v * width_mult))
        fwd += 2.0 * h * wd * cout * cin * 9  # 3x3 SAME conv
        cin = cout
    d1 = max(64, int(512 * width_mult))
    fwd += 2.0 * (h * wd * cin) * d1
    fwd += 2.0 * d1 * num_classes
    return 3.0 * fwd


def run_micro(sc: dict, detail: dict) -> None:
    import jax
    import numpy as np

    from rafiki_tpu.models.vgg import Vgg

    m = sc["micro"]
    batch = m["batch"]
    model = Vgg(depth=m["depth"], width_mult=m["width"], dropout=0.1,
                learning_rate=1e-3, batch_size=batch, epochs=1, seed=0)
    tiny = (f"synthetic://images?classes=10&n={max(batch * 2, 256)}"
            f"&w={sc['w']}&h={sc['w']}&c=3&seed=0")
    # NOTE: run_micro executes AFTER run_real_loop on purpose — the
    # other order would pre-warm the persistent XLA cache with the
    # canonical HLO and the "compile-inclusive" headline would never
    # pay the real cold compile. Here the caches are fair game: micro
    # numbers are steady-state throughputs.
    model.train(tiny)

    loop = model._loop
    rng = np.random.default_rng(0)
    b = {"x": rng.uniform(0, 1, size=(batch, sc["w"], sc["w"], 3)).astype(np.float32),
         "y": rng.integers(0, 10, size=(batch,)).astype(np.int32)}
    dev_b = loop.plan.put_batch(b)
    loop.state, mt = loop._train_step(loop.state, dev_b)
    jax.block_until_ready(loop.state)
    steps = sc["micro_steps"]
    t0 = time.monotonic()
    for _ in range(steps):
        loop.state, mt = loop._train_step(loop.state, dev_b)
    jax.block_until_ready(loop.state)
    # lint: disable=RF007 — steady-state step timing, the microbench output
    step_s = (time.monotonic() - t0) / steps
    train_img_s = batch / step_s

    c, n = loop._eval_step(loop.state[0], dev_b)
    jax.block_until_ready(c)
    t0 = time.monotonic()
    for _ in range(max(10, steps // 3)):
        c, n = loop._eval_step(loop.state[0], dev_b)
    jax.block_until_ready(c)
    # lint: disable=RF007 — steady-state eval timing, the microbench output
    eval_img_s = max(10, steps // 3) * batch / (time.monotonic() - t0)

    # MFU only means something on the hardware whose peak is the
    # denominator: on the CPU smoke scale both fields are null, not a
    # rounded 0.0. On the chip the peak comes from the device-kind
    # table; a kind the table does not list is an error, not a default.
    mfu = mfu_model = None
    if sc["platform"] != "cpu":
        from rafiki_tpu.utils.backend import peak_bf16_flops

        peak = peak_bf16_flops(jax.devices()[0].device_kind)
        # whole-program flops from XLA's own cost model
        compiled = loop._train_step.lower(loop.state, dev_b).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))
        if flops > 0:
            mfu = flops / step_s / peak
        step_model_flops = _vgg_train_flops_per_image(
            m["depth"], m["width"], sc["w"]) * batch
        mfu_model = step_model_flops / step_s / peak

    t0 = time.monotonic()
    blob = model.dump_parameters()
    # lint: disable=RF007 — params dump timing, reported in detail
    dump_s = time.monotonic() - t0

    detail.update({
        "train_img_per_s": round(train_img_s, 1),
        "eval_img_per_s": round(eval_img_s, 1),
        "params_dump_s": round(dump_s, 3),
        "params_blob_mb": round(len(blob) / 1e6, 1),
        "mfu_vs_bf16_peak": round(mfu, 4) if mfu is not None else None,
        "mfu_model_flops": round(mfu_model, 4) if mfu_model is not None else None,
        "mfu_basis": ("mfu_vs_bf16_peak: XLA whole-program flops over "
                      "the device kind's peak (utils.backend) — overstates "
                      "vs model-flops MFU; mfu_model_flops: analytic "
                      "conv+dense fwd+bwd; both null on the CPU smoke"),
        "canonical_compute_s": round(
            sc["canon_train"] / train_img_s + sc["canon_eval"] / eval_img_s, 2),
    })
    model.destroy()

    # Advisor cost in steady state: measured AFTER the GP has real fits
    # (>=30 observations) — the random warmup phase costs ~0 and would
    # understate it.
    from rafiki_tpu.advisor import make_advisor
    from rafiki_tpu.model.base import load_model_class

    cls = load_model_class(sc["src"], "BenchVgg")
    adv = make_advisor(cls.get_knob_config(), kind="gp", seed=0)
    obs_rng = np.random.default_rng(1)
    for _ in range(32):
        knobs = adv.propose()
        adv.feedback(float(obs_rng.uniform(0.3, 0.9)), knobs)
    t0 = time.monotonic()
    rounds = 5
    for _ in range(rounds):
        knobs = adv.propose()
        adv.feedback(0.5, knobs)
    # lint: disable=RF007 — advisor cost measurement, reported in detail
    detail["advisor_s_per_trial_at_30obs"] = round((time.monotonic() - t0) / rounds, 4)


def _goodput_snapshot() -> dict:
    """The goodput ledger's per-entity split (compile/step/feed/
    checkpoint/downtime + goodput ratio), rounded for the artifact."""
    from rafiki_tpu.obs.ledger import ledger

    snap = ledger.snapshot()

    def _round(d):
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in d.items()}

    return {
        "entities": {name: _round(e)
                     for name, e in snap.get("entities", {}).items()},
        "total": _round(snap.get("total", {})),
        "goodput": (round(snap["goodput"], 4)
                    if snap.get("goodput") is not None else None),
    }


def _health_snapshot() -> dict:
    """Numerics health totals for the artifact: divergences caught,
    capsules banked, pack evictions, contained trials, and the
    wall-clock those divergences burned (already inside badput_s)."""
    from rafiki_tpu.obs import health

    return dict(health.stats())


def main() -> None:
    deadline = float(os.environ.get("RAFIKI_BENCH_DEADLINE_S", "1500"))
    wd = _watchdog(deadline)
    detail = _OUT["detail"]
    try:
        platform = _init_backend()
        # Always recorded, even on failure paths below: an artifact
        # with mfu null must say WHICH platform produced it.
        detail["platform"] = platform
        from rafiki_tpu.utils.backend import enable_compilation_cache

        detail["xla_cache_dir"] = enable_compilation_cache()
        import jax

        detail["device"] = str(jax.devices()[0])
        detail["device_kind"] = jax.devices()[0].device_kind
        detail["device_count"] = len(jax.devices())
        # Test hook: deterministic stall for the watchdog test (the
        # real run's duration depends on cache warmth).
        stall = float(os.environ.get("RAFIKI_BENCH_SELFTEST_SLEEP_S", "0"))
        if stall:
            time.sleep(stall)
        sc = _scale(platform)
        if os.environ.get("RAFIKI_BENCH_TOP1_TARGET"):  # tests force the red path
            sc["top1_target"] = float(os.environ["RAFIKI_BENCH_TOP1_TARGET"])
        detail["n_trials_requested"] = sc["trials"]
        from rafiki_tpu import telemetry

        from rafiki_tpu.obs.ledger import ledger

        run_real_loop(sc, detail)  # first: its compiles must be COLD
        # Embed the span/metric snapshot NOW, while it holds exactly the
        # headline job's trials — per-phase spans (advisor-propose /
        # build / train / evaluate / persist), program-cache hit/miss,
        # host-feed vs step time — so the BENCH artifact decomposes its
        # own wall-clock. Refreshed after the remaining sections so the
        # final artifact also covers serving/micro/lift activity.
        detail["telemetry"] = telemetry.snapshot()
        run_micro(sc, detail)
        with ledger.entity("bench:micro"):
            run_trial_pack_micro(sc, detail)
        run_advisor_lift(sc, detail)
        # Goodput ledger: the job's wall decomposed into compile / step /
        # feed / checkpoint / downtime per trial.
        detail["goodput"] = _goodput_snapshot()
        # Numerics health (docs/health.md): bench_report.py trends
        # divergences/evictions and the badput they charged — a silent
        # NaN epidemic shows up as a throughput regression; this names it.
        detail["health"] = _health_snapshot()
        detail["telemetry"] = telemetry.snapshot()
        if detail.get("top1_miss"):
            # The accuracy clause is a GATE, not a footnote: a learning
            # regression (or an advisor steering into bad regions) must
            # turn the bench red, not quietly shave the headline. A
            # None best_top1 is a job failure, not a regression — label
            # it so triage starts at the right subsystem. On a plain
            # CPU run the gate is ADVISORY (recorded, rc stays 0): the
            # targets are calibrated for the canonical TPU scale, and a
            # 3-trial smoke sweep misses them by seed noise. An
            # explicitly forced target keeps the red path testable on
            # CPU.
            best = detail.get("best_top1")
            forced = bool(os.environ.get("RAFIKI_BENCH_TOP1_TARGET"))
            if best is None or platform != "cpu" or forced:
                _emit(error=("no completed trials scored — job/infra "
                             "failure, see errored_trials" if best is None
                             else
                             f"best_top1 {best} below target "
                             f"{sc['top1_target']} "
                             f"(ceiling {detail.get('top1_ceiling')}) — "
                             "learning regression"))
                wd.cancel()
                sys.exit(1)
            detail["top1_note"] = (
                f"best_top1 {best} below smoke target {sc['top1_target']}: "
                "advisory on CPU — the gate is calibrated for the "
                "canonical TPU run")
        _emit()
    except BaseException as e:  # noqa: BLE001 — the JSON line must go out
        _emit(error=f"{type(e).__name__}: {e}")
        wd.cancel()
        sys.exit(1)
    wd.cancel()


if __name__ == "__main__":
    main()
