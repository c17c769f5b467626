#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call,
on one TPU chip, at the full width of the canonical model (VGG16 depth
16 / width 1.0, 32x32x3, 50,000 train / 10,000 validation synthetic
images made from a seed, bf16 compute):

  process  BEFORE this process touches jax: a ``ProcessScheduler`` job,
           one worker subprocess, two VGG16 trials. The child takes the
           chip, fills the persistent compile cache, exits, releases it.
  sweep    this process initialises jax (must find platform "tpu") and
           runs a GP-advisor sweep of four VGG16 trials through
           ``LocalScheduler`` — it should find the child's programs in
           the persistent cache.
  serve    the top-2 trials' parameters are read back from the store and
           served through ``ServicesManager`` (stacked worker on the bus,
           ``Predictor``, ``Gateway``); three requests must agree with the
           same ensemble rule applied to direct ``model.predict`` calls.
  packed   one packed round (k=4, ``PackedTrialRunner``) of the
           FeedForward template at its largest shape knobs; per-trial
           scores must match serial runs of the same knobs.

``--chips 4`` runs ONLY the path across chips and what it is compared
with, in one process: a ``MeshSweepScheduler`` sweep of eight seeded
VGG16 proposals on four chips against the same eight on one chip, a
``ShardedTrainLoop`` trial at width 4 against width 1 with a width-4
save restored at width 2, and inference replicas placed one per chip.

Contract (the driver reads only this):

* the LAST line on standard output is one JSON object,
  ``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
  with the device as the jax of the process that did the work reports it;
  everything but this script's own report lines goes to standard error;
* exit code 0 only if every phase passed; a failed phase exits 1 (last
  line ``"ok": false``);
* when jax finds no accelerator — ``JAX_PLATFORMS=cpu``, a machine with
  no chip — it exits 4 and prints no result at all.

Every number this prints is a smoke reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO = Path(__file__).resolve().parent

EXIT_PHASE_FAILED = 1
EXIT_NO_ACCELERATOR = 4

#: The size the driver runs: the canonical trial (BASELINE.md) and its
#: acceptance configs. Widths are the templates' own; nothing is cut.
FULL: Dict[str, Any] = {
    "vgg": {"depth": 16, "width_mult": 1.0, "batch_size": 256},
    "images": {"w": 32, "c": 3, "train_n": 50_000, "eval_n": 10_000},
    "process_trials": 2,
    "sweep_trials": 4,
    "requests": 3,
    "queries_per_request": 16,
    "ff": {"hidden_layers": 3, "hidden_units": 256, "batch_size": 128,
           "epochs": 2},
    "ff_images": {"w": 28, "c": 1, "train_n": 60_000, "eval_n": 10_000},
    "pack": 4,
    "mesh_trials": 8,
    "transformer": {"embed_dim": 128, "num_heads": 4, "num_layers": 2,
                    "batch_size": 64, "epochs": 2},
    "text": {"vocab": 81, "classes": 5, "length": 16, "train_n": 4096,
             "eval_n": 1024},
}

#: A seconds-scale size for the CPU rehearsal and tests/test_chip_smoke.py.
#: Reached only through ``main``'s internal argument, never the command line.
TINY: Dict[str, Any] = {
    "vgg": {"depth": 11, "width_mult": 0.25, "batch_size": 64},
    "images": {"w": 8, "c": 3, "train_n": 1024, "eval_n": 256},
    "process_trials": 1,
    "sweep_trials": 2,
    "requests": 3,
    "queries_per_request": 4,
    "ff": {"hidden_layers": 1, "hidden_units": 32, "batch_size": 32,
           "epochs": 4},
    "ff_images": {"w": 8, "c": 1, "train_n": 2048, "eval_n": 256},
    "pack": 4,
    "mesh_trials": 8,
    "transformer": {"embed_dim": 32, "num_heads": 2, "num_layers": 1,
                    "batch_size": 16, "epochs": 2},
    "text": {"vocab": 81, "classes": 5, "length": 16, "train_n": 256,
             "eval_n": 64},
}

# Tolerances, stated, with what the v5e showed (my chip runs, PR 22). On
# the CPU every pair below is bit-identical (tests/test_trial_pack.py,
# tests/test_shard.py); on the chip a vmapped or stacked program is a
# different XLA program than the serial one, so bf16 reductions may round
# differently. One score step is 1e-4 (10,000 validation rows).
STACKED_VS_DIRECT_PROB_TOL = 0.01   # |p_gateway - p_direct|; found 1.65e-3
PACKED_VS_SERIAL_SCORE_TOL = 0.01   # |accuracy| per trial; found 0
MESH_VS_ONE_CHIP_SCORE_TOL = 0.01   # packs of 2 vs a pack of 8; found 9e-4
SHARDED_WIDTH_SCORE_TOL = 0.01      # width 4 vs 1; found 0, state bit-identical
NOISE, FLIP = 0.35, 0.2             # the non-saturating task (benchmark/datagen.py)

_VGG_SUBCLASS = '''

class SmokeVgg(Vgg):
    """chip_smoke's trial: the template above with its shape knobs
    pinned (one batch-size bucket, one program) and lr/dropout tuned."""

    @staticmethod
    def get_knob_config():
        return {{
            "depth": FixedKnob({depth}),
            "width_mult": FixedKnob({width_mult}),
            "dropout": FloatKnob(0.0, 0.3),
            "learning_rate": FloatKnob(1e-4, 2e-3, is_exp=True),
            "batch_size": FixedKnob({batch_size}),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }}
'''

_FF_SUBCLASS = '''

class SmokeFF(FeedForward):
    """chip_smoke's packed trial: the template above at its largest
    shape knobs, lr tuned — every proposal shares one packing key."""

    @staticmethod
    def get_knob_config():
        return {{
            "hidden_layers": FixedKnob({hidden_layers}),
            "hidden_units": FixedKnob({hidden_units}),
            "learning_rate": FloatKnob(3e-4, 1e-2, is_exp=True),
            "batch_size": FixedKnob({batch_size}),
            "epochs": FixedKnob({epochs}),
            "seed": FixedKnob(0),
        }}
'''


def final_line(ok: bool, device: Dict[str, Any]) -> Dict[str, Any]:
    """The object the last stdout line carries: exactly ``ok`` and
    ``device``, and ``device`` exactly ``platform``, ``kind``, ``count``."""
    return {"ok": bool(ok),
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def template_source(template: str, subclass: str, **knobs: Any) -> bytes:
    """A model file as a user would upload it: the committed template's
    own bytes plus a few lines pinning its shape knobs."""
    src = (REPO / "rafiki_tpu" / "models" / template).read_bytes()
    return src + subclass.format(**knobs).encode()


def image_uris(spec: Dict[str, int]) -> "tuple[str, str]":
    def uri(n: int, seed: int) -> str:
        return (f"synthetic://images?classes=10&n={n}&w={spec['w']}"
                f"&h={spec['w']}&c={spec['c']}&seed={seed}"
                f"&noise={NOISE}&flip={FLIP}")

    return uri(spec["train_n"], 0), uri(spec["eval_n"], 1)


class NoAccelerator(RuntimeError):
    """jax found no device of the platform this run needs."""


class Report:
    """This script's report lines, on the REAL standard output.

    ``claim_stdout`` keeps a private handle on fd 1 and points fd 1 (and
    ``sys.stdout``) at standard error, so worker threads, warnings,
    werkzeug, C++ logging and child processes that inherit stdout can
    never write after — or between — the report lines. Lines are held
    back until the accelerator is confirmed: a run that finds none
    prints nothing at all.
    """

    def __init__(self) -> None:
        self._out = None
        self._held: Optional[List[str]] = []

    def claim_stdout(self) -> None:
        sys.stdout.flush()
        self._out = os.fdopen(os.dup(1), "w")
        os.dup2(2, 1)
        sys.stdout = sys.stderr

    def line(self, text: str) -> None:
        print(f"[chip_smoke] {text}", file=sys.stderr, flush=True)
        if self._held is not None:
            self._held.append(text)
        else:
            self._emit(f"[chip_smoke] {text}")

    def release(self) -> None:
        """The accelerator is confirmed: print what was held back."""
        held, self._held = self._held or [], None
        for text in held:
            self._emit(f"[chip_smoke] {text}")

    def final(self, obj: Dict[str, Any]) -> None:
        """The last line. Nothing is written to stdout after it."""
        self.release()
        self._emit(json.dumps(obj))
        self._out.close()

    def _emit(self, text: str) -> None:
        self._out.write(text + "\n")
        self._out.flush()


class Smoke:
    def __init__(self, size: Dict[str, Any], platform: str, report: Report):
        self.size = size
        self.platform = platform
        self.report = report
        self.failures: List[str] = []
        self.device: Optional[Dict[str, Any]] = None
        self.work: Optional[Path] = None
        self.cache = {"hits": 0, "misses": 0}
        self.best: List[dict] = []  # the sweep's top-k trials, for serving
        self.best_job_id = ""

    # -- plumbing ------------------------------------------------------------

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        if not ok:
            self.failures.append(name)
        self.report.line(f"check {name}: {'ok' if ok else 'FAILED'}"
                         + (f" ({detail})" if detail else ""))
        return bool(ok)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase; an exception inside it is a failed phase (the
        others still run, so one chip call shows every fault)."""
        t0 = time.monotonic()
        self.report.line(f"phase {name}: start")
        try:
            yield
        except NoAccelerator:
            raise
        except Exception as e:
            import traceback

            traceback.print_exc(file=sys.stderr)
            self.check(f"{name}.ran", False, f"{type(e).__name__}: {e}")
        self.report.line(f"phase {name}: {time.monotonic() - t0:.1f} s")

    def open_stores(self) -> None:
        """Everything this run writes lives under one temporary work
        directory (the driver gives the checkout a TMPDIR of its own)."""
        from rafiki_tpu import obs
        from rafiki_tpu.config import Config, set_config
        from rafiki_tpu.store import MetaStore, ParamsStore
        from rafiki_tpu.utils.events import events

        self.work = Path(tempfile.mkdtemp(prefix="rafiki-chip-smoke-"))
        set_config(Config(data_dir=self.work / "data").ensure_dirs())
        events.configure(self.work / "logs")
        if obs.configure_from_env(role="chip-smoke"):
            obs.recorder.install()
        self.store = MetaStore(self.work / "meta.sqlite3")
        self.params = ParamsStore(self.work / "params")

    def close(self) -> None:
        if self.work is None:  # never opened (the package did not import)
            return
        from rafiki_tpu.utils.events import events

        events.close()
        shutil.rmtree(self.work, ignore_errors=True)

    def events_named(self, *names: str) -> List[dict]:
        from rafiki_tpu.utils.events import events

        return [e for e in events.read() if e.get("event") in names]

    def train_job(self, app: str, source: bytes, cls_name: str,
                  uris: "tuple[str, str]", trials: int) -> dict:
        """Model upload -> train job -> sub job, as the admin does it."""
        task = "IMAGE_CLASSIFICATION"
        model = (self.store.get_model_by_name(cls_name)
                 or self.store.create_model(cls_name, task, None, source,
                                            cls_name))
        job = self.store.create_train_job(app, task, None, uris[0], uris[1],
                                          {"MODEL_TRIAL_COUNT": trials})
        self.store.create_sub_train_job(job["id"], model["id"])
        return job

    def check_job(self, name: str, result, n_trials: int) -> None:
        """Job COMPLETED, every trial COMPLETED, no error recorded
        anywhere (a scheduler that records a failed trial and finishes
        the job is a caught failure), finite scores above chance (every
        image task here has 10 classes; the bar is twice chance)."""
        statuses = [t["status"] for t in result.trials]
        scores = [t["score"] for t in result.trials]
        self.check(f"{name}.job_completed", result.status == "COMPLETED",
                   f"status {result.status}")
        self.check(f"{name}.all_trials_completed",
                   len(statuses) == n_trials
                   and all(s == "COMPLETED" for s in statuses), f"{statuses}")
        self.check(f"{name}.no_errors", not result.errors,
                   "; ".join(str(e)[-400:] for e in result.errors))
        self.check(f"{name}.scores_finite_above_chance",
                   bool(scores) and all(s is not None and math.isfinite(s)
                                        and s > 0.2 for s in scores),
                   f"scores {scores}, chance 0.1")

    def load_trial(self, cls: type, trial: dict, device: Any = None):
        """A trained model rebuilt from its trial row: knobs + the
        parameters read back from the store, on ``device`` if given."""
        import jax

        with (jax.default_device(device) if device is not None
              else contextlib.nullcontext()):
            model = cls(**trial["knobs"])
            model.load_parameters(self.params.load(trial["params_id"]))
        return model

    @staticmethod
    def trial_seconds(result) -> List[float]:
        return [round(t["stopped_at"] - t["started_at"], 1)
                for t in result.trials
                if t.get("stopped_at") and t.get("started_at")]

    # -- jax -----------------------------------------------------------------

    def init_jax(self, min_devices: int = 1) -> None:
        """First jax use of this process: the device must be of the
        platform this run needs, then the compile cache is placed."""
        import jax
        import jaxlib

        try:
            devs = jax.devices()
        except RuntimeError as e:  # JAX_PLATFORMS names a backend with no device
            raise NoAccelerator(str(e).splitlines()[0]) from e
        if devs[0].platform != self.platform:
            raise NoAccelerator(
                f"jax found platform {devs[0].platform!r} "
                f"({devs[0].device_kind}), this run needs {self.platform!r}")
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        self.report.release()
        from rafiki_tpu.utils.backend import enable_compilation_cache

        enable_compilation_cache()

        def on_event(event: str, **_kw: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        from importlib import metadata

        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = "not installed"
        self.report.line(
            f"versions: python {sys.version.split()[0]} jax {jax.__version__} "
            f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
        self.report.line(f"device: {self.device}")
        self.check("jax.enough_devices", len(devs) >= min_devices,
                   f"{len(devs)} device(s), need {min_devices}")

    def cache_entries(self) -> int:
        return (len(list(self.cache_path.glob("*")))
                if self.cache_path.is_dir() else 0)

    def cache_dir_report(self) -> None:
        from rafiki_tpu.utils.backend import compile_cache_dir

        placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        self.cache_path = Path(compile_cache_dir())
        n = self.cache_entries()
        self.report.line(
            f"compile cache: {self.cache_path} "
            f"({'JAX_COMPILATION_CACHE_DIR' if placed else 'default, inside the checkout'}), "
            f"{'empty' if n == 0 else f'{n} entries'} at start")

    # -- one chip ------------------------------------------------------------

    def vgg(self) -> "tuple[bytes, tuple[str, str]]":
        return (template_source("vgg.py", _VGG_SUBCLASS, **self.size["vgg"]),
                image_uris(self.size["images"]))

    def phase_process(self) -> None:
        """One process per chip: the scheduler process (this one) stays
        off jax while its worker subprocess owns the chip."""
        from jax._src import xla_bridge

        from rafiki_tpu.scheduler import ProcessScheduler

        src, uris = self.vgg()
        n = self.size["process_trials"]
        job = self.train_job("smoke-process", src, "SmokeVgg", uris, n)
        entries0 = self.cache_entries()
        result = ProcessScheduler(self.store, self.params).run_train_job(
            job["id"], n_workers=1, advisor_kind="gp", platform=self.platform)
        # A child that cannot take the chip shows here (its own output is
        # in result.errors); the parent's device check, next, tells "no
        # accelerator" from a fault.
        self.check_job("process", result, n)
        self.check("process.parent_stayed_off_jax",
                   not xla_bridge.backends_are_initialized(),
                   f"jax backends initialised in the scheduler process: "
                   f"{xla_bridge.backends_are_initialized()}")
        self.report.line(
            f"process: the child added {self.cache_entries() - entries0} "
            f"entries to the compile cache (what it compiled and did not "
            f"find there); trial seconds {self.trial_seconds(result)}")

    def phase_sweep(self) -> None:
        """The quickstart journey's train half at the canonical size."""
        from rafiki_tpu import telemetry
        from rafiki_tpu.ops.train import program_cache_stats
        from rafiki_tpu.scheduler import LocalScheduler

        src, uris = self.vgg()
        n = self.size["sweep_trials"]
        job = self.train_job("smoke-sweep", src, "SmokeVgg", uris, n)
        c0, p0 = dict(self.cache), program_cache_stats()
        t0 = time.monotonic()
        result = LocalScheduler(self.store, self.params).run_train_job(
            job["id"], n_workers=1, advisor_kind="gp")
        wall = time.monotonic() - t0
        p1 = program_cache_stats()
        self.check_job("sweep", result, n)
        self.check("sweep.cost_captured",
                   telemetry.get_counter("perf.cost_captures") >= 1,
                   f"perf.cost_captures "
                   f"{telemetry.get_counter('perf.cost_captures'):.0f}")
        vgg = self.size["vgg"]
        self.report.line(
            f"sweep: VGG depth {vgg['depth']} width {vgg['width_mult']}, "
            f"{self.size['images']['train_n']}/{self.size['images']['eval_n']} "
            f"images, GP advisor, {n} trials, one batch-size bucket "
            f"({vgg['batch_size']})")
        self.report.line(
            f"sweep: programs compiled {p1['misses'] - p0['misses']}, "
            f"program-cache hits {p1['hits'] - p0['hits']}; persistent cache "
            f"hits {self.cache['hits'] - c0['hits']}, "
            f"misses {self.cache['misses'] - c0['misses']}")
        self.report.line(
            f"sweep: {wall:.1f} s wall, {3600.0 * n / wall:.1f} trials/hour "
            f"including compile (smoke, not a benchmark); trial seconds "
            f"{self.trial_seconds(result)}; scores "
            f"{[round(t['score'], 4) for t in result.trials if t['score'] is not None]}")
        self.best, self.best_job_id = result.best_trials[:2], job["id"]

    def phase_serve(self) -> None:
        """Top-k parameters read back from the store, served through the
        real chain, compared with direct predicts on the same models."""
        import numpy as np

        from rafiki_tpu.admin.services_manager import ServicesManager
        from rafiki_tpu.model.base import load_model_class
        from rafiki_tpu.model.dataset import dataset_utils
        from rafiki_tpu.predictor.ensemble import ensemble_predictions

        if not self.check("serve.have_top_k", len(self.best) == 2,
                          f"{len(self.best)} completed trial(s) to serve"):
            return
        src, uris = self.vgg()
        val = dataset_utils.load(uris[1])
        per, n_req = self.size["queries_per_request"], self.size["requests"]
        inf = self.store.create_inference_job(self.best_job_id, None)
        sm = ServicesManager(self.store, self.params)
        answers = []
        try:
            sm.create_inference_services(inf["id"], self.best,
                                         serve_http=False)
            gateway = sm.get_gateway(inf["id"])
            for r in range(n_req):
                queries = val.x[r * per:(r + 1) * per].tolist()
                answers.append(gateway.predict(queries, deadline_s=120.0))
        finally:
            sm.stop_inference_services(inf["id"])
        stacked = [e for e in self.events_named("inference_stacked")
                   if e.get("job_id") == inf["id"]]
        self.check("serve.stacked_route_engaged", len(stacked) == 1,
                   f"{len(stacked)} inference_stacked event(s); none means "
                   f"the top-k fell back to the replicated route")

        cls = load_model_class(src, "SmokeVgg")
        models = [self.load_trial(cls, t) for t in self.best]
        worst, correct, total = 0.0, 0, 0
        shapes_ok = True
        for r, got in enumerate(answers):
            queries = val.x[r * per:(r + 1) * per]
            direct = [m.predict(queries.tolist()) for m in models]
            want = np.asarray([ensemble_predictions([d[i] for d in direct])
                               for i in range(per)], np.float64)
            errs = [g for g in got if isinstance(g, dict)]
            if errs:
                shapes_ok = False
                self.report.line(f"serve: request {r} answered {errs[:2]}")
                continue
            got = np.asarray(got, np.float64)
            shapes_ok &= (got.shape == (per, 10) and bool(np.isfinite(got).all())
                          and bool(np.allclose(got.sum(-1), 1.0, atol=1e-3)))
            if got.shape == want.shape:
                worst = max(worst, float(np.abs(got - want).max()))
            correct += int((got.argmax(-1) == val.y[r * per:(r + 1) * per]).sum())
            total += per
        for m in models:
            m.destroy()
        self.check("serve.answers_well_formed", shapes_ok,
                   f"{n_req} requests x {per} queries -> ({per}, 10) "
                   f"probability rows")
        self.check("serve.gateway_equals_direct_ensemble",
                   shapes_ok and worst <= STACKED_VS_DIRECT_PROB_TOL,
                   f"max |dp| {worst:.2e}, tolerance "
                   f"{STACKED_VS_DIRECT_PROB_TOL}")
        self.check("serve.ensemble_above_chance",
                   total > 0 and correct / total > 0.2,
                   f"{correct}/{total} of the labelled queries")

    def phase_packed(self) -> None:
        """The other single-chip lane: k trials vmapped into one
        program, against serial runs of the same knobs."""
        from rafiki_tpu import telemetry
        from rafiki_tpu.model.base import load_model_class
        from rafiki_tpu.scheduler import LocalScheduler

        ff, k = self.size["ff"], self.size["pack"]
        src = template_source("ff.py", _FF_SUBCLASS, **ff)
        uris = image_uris(self.size["ff_images"])
        job = self.train_job("smoke-packed", src, "SmokeFF", uris, k)
        rounds0 = telemetry.get_counter("worker.packed_rounds")
        trials0 = telemetry.get_counter("worker.packed_trials")
        result = LocalScheduler(self.store, self.params).run_train_job(
            job["id"], n_workers=1, advisor_kind="gp", trial_pack=k)
        self.check_job("packed", result, k)
        self.check("packed.one_pack_of_k",
                   telemetry.get_counter("worker.packed_rounds") - rounds0 == 1
                   and telemetry.get_counter("worker.packed_trials") - trials0 == k,
                   f"k={k} FeedForward {ff['hidden_layers']}x{ff['hidden_units']}"
                   f", batch {ff['batch_size']}, {ff['epochs']} epochs")
        cls = load_model_class(src, "SmokeFF")
        worst = 0.0
        for t in result.trials:
            if t["score"] is None:
                continue
            m = cls(**t["knobs"])
            m.train(uris[0])
            worst = max(worst, abs(float(m.evaluate(uris[1])) - t["score"]))
            m.destroy()
        self.check("packed.scores_match_serial",
                   worst <= PACKED_VS_SERIAL_SCORE_TOL,
                   f"max |d score| {worst:.2e}, tolerance "
                   f"{PACKED_VS_SERIAL_SCORE_TOL}")

    # -- four chips ----------------------------------------------------------

    def phase_mesh(self) -> None:
        """A sweep that spans chips against the same knob sets on one."""
        import jax

        from rafiki_tpu.model.dataset import dataset_utils
        from rafiki_tpu.scheduler import MeshSweepScheduler

        src, uris = self.vgg()
        n = self.size["mesh_trials"]
        devs = jax.devices()[:4]

        def sweep(app: str, chips: int):
            job = self.train_job(app, src, "SmokeVgg", uris, n)
            if chips == 4:
                self.best_job_id = job["id"]
            t0 = time.monotonic()
            # The seeded random advisor (seed 0): both runs draft the
            # same n knob sets in one propose_batch(n).
            result = MeshSweepScheduler(self.store, self.params).run_sweep(
                job["id"], chips=chips, trials_per_chip=n // chips,
                advisor_kind="random")
            self.report.line(f"mesh: {app}: {n} trials on {chips} chip(s), "
                             f"packs of {n // chips}, "
                             f"{time.monotonic() - t0:.1f} s (smoke, not a "
                             f"benchmark)")
            self.check_job(app, result, n)
            return result

        four = sweep("mesh-4chip", 4)
        # Not the scheduler's say-so: the train set's device copies are
        # made by the epoch loop on whichever chip it really ran on.
        train = dataset_utils.load(uris[0])
        copies = train.__dict__.get("_device_arrays", {})
        held = {d for x, _y in copies.values() for d in x.devices()}
        self.check("mesh.every_chip_trained", held >= set(devs),
                   f"train-set copies live on {sorted(str(d) for d in held)}")
        stats = [d.memory_stats() for d in devs]
        if all(s and "peak_bytes_in_use" in s for s in stats):
            peaks = [s["peak_bytes_in_use"] for s in stats]
            self.check("mesh.every_chip_held_the_work",
                       min(peaks) >= train.x.nbytes,
                       f"peak bytes in use per chip {peaks}, train set "
                       f"{train.x.nbytes}")
        else:
            self.report.line("mesh: this backend reports no memory_stats")
        degraded = self.events_named("mesh_degraded", "mesh_no_devices")
        self.check("mesh.no_degrade_event", not degraded,
                   f"{len(degraded)} mesh_degraded/mesh_no_devices event(s)")
        one = sweep("mesh-1chip", 1)

        def by_knobs(result) -> Dict[str, float]:
            return {json.dumps(t["knobs"], sort_keys=True): t["score"]
                    for t in result.trials if t["score"] is not None}

        a, b = by_knobs(four), by_knobs(one)
        same = len(a) == n and set(a) == set(b)
        worst = max((abs(a[k] - b[k]) for k in a if k in b), default=math.inf)
        self.check("mesh.same_knob_sets_both_runs", same,
                   f"{len(set(a) & set(b))} of {n} knob sets in common")
        self.check("mesh.scores_match_one_chip",
                   same and worst <= MESH_VS_ONE_CHIP_SCORE_TOL,
                   f"max |d score| {worst:.2e}, tolerance "
                   f"{MESH_VS_ONE_CHIP_SCORE_TOL}")
        self.best = four.best_trials[:2]

    def phase_sharded(self) -> None:
        """One trial sharded over a chip group: width 4 against width 1,
        and a width-4 save restored at width 2."""
        import numpy as np

        import jax

        from rafiki_tpu.model.base import load_model_class
        from rafiki_tpu.shard import gather_state, save_sharded, train_sharded

        text, knobs = self.size["text"], dict(self.size["transformer"])
        knobs.update(learning_rate=1e-3, seed=0)
        src = (REPO / "rafiki_tpu" / "models" / "transformer.py").read_bytes()
        cls = load_model_class(src, "Transformer")

        def uri(n: int, seed: int) -> str:
            return (f"synthetic://text?vocab={text['vocab']}"
                    f"&classes={text['classes']}&n={n}&len={text['length']}"
                    f"&seed={seed}")

        train, val = uri(text["train_n"], 0), uri(text["eval_n"], 1)
        devs = jax.devices()[:4]
        last = knobs["epochs"] - 1

        def save_last(epoch: int, loop) -> None:
            if epoch == last:
                save_sharded(self.params, "smoke-sharded", epoch, loop.state,
                             loop.width)

        def run(width: int, **kw):
            model = cls(**knobs)
            loop, history = train_sharded(model, train, devs[:width], **kw)
            return model, loop, history

        m4, loop4, h4 = run(4, checkpoint_sink=save_last)
        sharded = [leaf for leaf in jax.tree.leaves(loop4.state)
                   if len(leaf.sharding.device_set) == 4
                   and not leaf.sharding.is_fully_replicated]
        self.check("sharded.state_spans_four_chips",
                   bool(sharded) and all(
                       {s.device for s in leaf.addressable_shards} == set(devs)
                       for leaf in sharded),
                   f"{len(sharded)} leaves sharded over {len(devs)} chips")
        m1, loop1, h1 = run(1)
        s4, s1 = float(m4.evaluate(val)), float(m1.evaluate(val))
        g4, g1 = gather_state(loop4.state), gather_state(loop1.state)
        diffs = [float(np.abs(np.asarray(a, np.float64)
                              - np.asarray(b, np.float64)).max())
                 for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1))
                 if np.issubdtype(np.asarray(a).dtype, np.floating)]
        self.report.line(
            f"sharded: width 4 vs width 1: losses {[round(h['loss'], 6) for h in h4]}"
            f" vs {[round(h['loss'], 6) for h in h1]}, scores {s4:.4f} vs "
            f"{s1:.4f}, max |d state| {max(diffs):.3e} "
            f"({'bit-identical' if max(diffs) == 0.0 else 'not bit-identical'})")
        self.check("sharded.width4_matches_width1",
                   all(math.isfinite(h["loss"]) for h in h4 + h1)
                   and abs(s4 - s1) <= SHARDED_WIDTH_SCORE_TOL,
                   f"|d score| {abs(s4 - s1):.2e}, tolerance "
                   f"{SHARDED_WIDTH_SCORE_TOL}")
        # Reshard-on-restore through the lane's own resume path: the
        # width-2 group adopts the width-4 checkpoint of the last epoch
        # and has nothing left to train.
        _m2, loop2, h2 = run(2, resume_from=(self.params, "smoke-sharded"))
        g2 = gather_state(loop2.state)
        self.check("sharded.save_at_4_restores_at_2_exactly",
                   loop2.width == 2 and not h2 and all(
                       np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree.leaves(g4),
                                       jax.tree.leaves(g2))),
                   f"width {loop2.width}, {len(h2)} epoch(s) left to train; "
                   f"restore_sharded is data movement only")

    def phase_replicas(self) -> None:
        """Inference workers take the chip they are given: one replica
        of the sweep's best trial per chip behind one predictor, and the
        stacked top-2 on the last chip."""
        import threading

        import numpy as np

        import jax

        from rafiki_tpu.bus import InProcBus
        from rafiki_tpu.model.base import load_model_class
        from rafiki_tpu.model.dataset import dataset_utils
        from rafiki_tpu.parallel.serving import build_stacked
        from rafiki_tpu.predictor.predictor import Predictor
        from rafiki_tpu.worker.inference import InferenceWorker

        if not self.check("replicas.have_top_k", len(self.best) == 2,
                          f"{len(self.best)} completed trial(s) to serve"):
            return
        src, uris = self.vgg()
        cls = load_model_class(src, "SmokeVgg")
        devs = jax.devices()[:4]
        queries = dataset_utils.load(uris[1]).x[:8].tolist()

        bus, stop = InProcBus(), threading.Event()
        models = [self.load_trial(cls, self.best[0], d) for d in devs]
        workers = [InferenceWorker(bus, "smoke-replicas", f"iw{i}", m,
                                   stop_event=stop, device=d)
                   for i, (m, d) in enumerate(zip(models, devs))]
        threads = [threading.Thread(target=w.run, daemon=True)
                   for w in workers]
        for th in threads:
            th.start()
        try:
            deadline = time.monotonic() + 30
            while len(bus.get_workers("smoke-replicas")) < len(workers):
                if time.monotonic() > deadline:
                    raise RuntimeError("replicas never registered on the bus")
                time.sleep(0.02)
            report = Predictor(bus, "smoke-replicas", timeout_s=120.0
                               ).predict_detailed(queries)
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=10)
        placed = [{d for leaf in jax.tree.leaves(m._loop.params)
                   for d in leaf.devices()} for m in models]
        self.check("replicas.each_on_its_own_chip",
                   placed == [{d} for d in devs],
                   f"params live on {[sorted(str(d) for d in p) for p in placed]}")
        want = np.asarray(models[0].predict(queries))
        got = np.asarray(report.outputs)
        self.check("replicas.all_four_answered",
                   all(report.replies.get(w.worker_id) == len(queries)
                       for w in workers)
                   and got.shape == want.shape
                   and float(np.abs(got - want).max()) <= STACKED_VS_DIRECT_PROB_TOL,
                   f"replies per worker {report.replies}")
        stacked, why = build_stacked(self.best,
                                     [self.load_trial(cls, t, devs[-1])
                                      for t in self.best],
                                     devices=[devs[-1]])
        on = ({d for leaf in jax.tree.leaves(stacked._ens._stacked)
               for d in leaf.devices()} if stacked is not None else set())
        self.check("replicas.stacked_on_the_chip_it_was_given",
                   stacked is not None and on == {devs[-1]}
                   and np.asarray(stacked.predict(queries)).shape == want.shape,
                   f"route {why}, stacked params on {sorted(str(d) for d in on)}")
        for m in models:
            m.destroy()


def run(argv: Optional[List[str]], size: Dict[str, Any], platform: str,
        report: Report) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the path across chips and what it is "
                         "compared with (needs four chips)")
    args = ap.parse_args(argv)

    requested = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if requested and platform not in requested.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={requested!r} leaves no "
              f"{platform!r} device to find; no result", file=sys.stderr)
        return EXIT_NO_ACCELERATOR

    smoke = Smoke(size, platform, report)
    t0 = time.monotonic()
    try:
        smoke.open_stores()
        smoke.cache_dir_report()
        if args.chips == 4:
            smoke.init_jax(min_devices=4)
            if not smoke.failures:
                with smoke.phase("mesh"):
                    smoke.phase_mesh()
                with smoke.phase("sharded"):
                    smoke.phase_sharded()
                with smoke.phase("replicas"):
                    smoke.phase_replicas()
        else:
            with smoke.phase("process"):
                smoke.phase_process()
            smoke.init_jax()
            with smoke.phase("sweep"):
                smoke.phase_sweep()
            with smoke.phase("serve"):
                smoke.phase_serve()
            with smoke.phase("packed"):
                smoke.phase_packed()
    except NoAccelerator as e:
        print(f"chip_smoke: {e}; no result", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    finally:
        smoke.close()
    report.line(f"compile cache: this process had {smoke.cache['hits']} "
                f"persistent-cache hits and {smoke.cache['misses']} misses "
                f"(programs it compiled itself); {smoke.cache_entries()} "
                f"entries at end")
    report.line(f"total: {time.monotonic() - t0:.1f} s, "
                f"{len(smoke.failures)} failed check(s)"
                + (f": {smoke.failures}" if smoke.failures else ""))
    report.final(final_line(not smoke.failures, smoke.device))
    return 0 if not smoke.failures else EXIT_PHASE_FAILED


def main(argv: Optional[List[str]] = None, *, size: Dict[str, Any] = FULL,
         platform: str = "tpu") -> int:
    """``size`` and ``platform`` are internal arguments (the CPU rehearsal
    and the tests pass ``TINY``/``"cpu"``); the command line has neither."""
    report = Report()
    report.claim_stdout()
    return run(argv, size, platform, report)


if __name__ == "__main__":
    sys.exit(main())
