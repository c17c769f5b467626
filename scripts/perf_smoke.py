#!/usr/bin/env python
"""Perf-sentinel CI smoke: the whole detection chain, both polarities.

Three gates in one process (docs/perf.md):

  1. **Bench regression gate** — scripts/bench_report.py over a
     history whose later rounds carry an ``error`` must exit 0
     (error-bearing rounds are no-data, not regressions), and over a
     doctored two-round fixture with a 3x throughput drop must exit
     nonzero naming the metric.

  2. **Quiet run (no injection)** — a packed TrainWorker round under a
     fresh journal dir: cost capture (``perf/cost``) and step sampling
     (``perf/step``) must appear, the ``obs profile --json`` CLI must
     report achieved FLOP/s + MFU for the *packed* program, and there
     must be ZERO ``perf/anomaly`` records, ZERO ``slo/breach``
     records and ZERO flight recordings — the sentinel must not cry
     wolf on an uninjected run.

  3. **Injected run** — same process, reset stores, chaos plane now
     delaying ``train.epoch`` 0.25s from its 16th hit (a >100x step
     inflation): the anomaly detector must fire (``perf/anomaly`` +
     badput), the burn-rate engine must breach the anomaly-rate SLO
     (``slo/breach``), and the breach must dump a flight record.

``RAFIKI_PERF_K=6`` is pinned for the whole smoke: the injected spike
is ~100x the warm mean, so a wider band costs no sensitivity there
while making the quiet phase's zero-anomaly assertion robust to CPU
scheduler jitter on sub-millisecond steps.

Output: one JSON object on stdout. Exit code: 0 when every assertion
holds; 1 otherwise — this is a CI gate (scripts/check_tier1.sh).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_SRC = b"""
from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models.ff import _Mlp

class PerfFF(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "learning_rate": FloatKnob(1e-4, 1e-1, is_exp=True),
            "batch_size": FixedKnob(64),
            "epochs": FixedKnob(3),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Mlp(hidden_layers=1, hidden_units=64, num_classes=num_classes)
"""

TRAIN = "synthetic://images?classes=4&n=512&w=8&h=8&c=1&seed=0"
VAL = "synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=1"


def _run(cmd, **kw):
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=120, **kw)


def check_bench_gate(problems, tmp):
    """Gate 1: the report must pass a history whose later round errored
    and fail a doctored regression — both directions, via the real
    CLI."""
    report = os.path.join(REPO, "scripts", "bench_report.py")
    hist_dir = os.path.join(tmp, "history")
    os.makedirs(hist_dir)
    history = []
    for doc in (
            {"n": 1, "cmd": "bench", "rc": 0, "tail": [], "parsed": {
                "metric": "m", "value": 1200.0,
                "headline": {"trials_per_hour": 1200.0}}},
            {"n": 2, "cmd": "bench", "rc": 1, "tail": [], "parsed": {
                "metric": "m", "value": 0.0,
                "error": "RuntimeError: bench needs a tpu device"}}):
        p = os.path.join(hist_dir, f"BENCH_r{doc['n']:02d}.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        history.append(p)
    real = _run([sys.executable, report] + history)
    if real.returncode != 0:
        problems.append(f"bench_report on an errored-round history exited "
                        f"{real.returncode}: {real.stderr.strip()[:200]}")
    try:
        verdict = json.loads(real.stdout or "{}").get("verdict")
        if real.returncode == 0 and verdict != "ok":
            problems.append(f"bench_report rc 0 but verdict {verdict!r}")
    except ValueError:
        problems.append("bench_report emitted unparseable stdout")

    r1 = {"n": 1, "cmd": "bench", "rc": 0, "tail": [], "parsed": {
        "metric": "m", "value": 1200.0,
        "headline": {"trials_per_hour": 1200.0, "canonical_trial_s": 3.0,
                     "compile_s": 12.0, "train_img_per_s": 45000.0}}}
    r2 = json.loads(json.dumps(r1))
    r2["n"] = 2
    r2["parsed"]["headline"]["trials_per_hour"] = 400.0  # 3x drop
    fix = []
    for doc in (r1, r2):
        p = os.path.join(tmp, f"BENCH_r{doc['n']:02d}.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        fix.append(p)
    doctored = _run([sys.executable, report] + fix)
    if doctored.returncode == 0:
        problems.append("bench_report passed a doctored 3x regression")
    else:
        regressed = json.loads(doctored.stdout or "{}").get("regressed", [])
        if "trials_per_hour" not in regressed:
            problems.append(f"doctored regression blamed {regressed}, "
                            "expected trials_per_hour")
    return {"real_rc": real.returncode, "doctored_rc": doctored.returncode}


def _read_perf(log_dir):
    from rafiki_tpu.obs.journal import read_dir

    recs = read_dir(log_dir)
    return {
        "costs": [r for r in recs
                  if r["kind"] == "perf" and r["name"] == "cost"],
        "steps": [r for r in recs
                  if r["kind"] == "perf" and r["name"] == "step"],
        "anomalies": [r for r in recs
                      if r["kind"] == "perf" and r["name"] == "anomaly"],
        "breaches": [r for r in recs
                     if r["kind"] == "slo" and r["name"] == "breach"],
        "flights": glob.glob(os.path.join(log_dir, "flight-*.json")),
    }


def _fresh_stores(log_dir, tick_s):
    """Point the journal at a fresh dir and zero every in-process
    accumulator the two phases must not share."""
    from rafiki_tpu import telemetry
    from rafiki_tpu.obs.journal import journal
    from rafiki_tpu.obs.perf import profiler, slo

    os.environ["RAFIKI_LOG_DIR"] = log_dir
    journal.configure(log_dir, role="perfsmoke")
    telemetry.reset()
    profiler.reset()
    slo.configure([slo.SloSpec(name="step_anomaly_rate",
                               source="counter:perf.anomalies",
                               threshold=0.0, windows=(0.4, 1.2))],
                  tick_s=tick_s)


def run_packed_round(pack):
    """One packed TrainWorker round — the program whose MFU the CLI
    must report (obs profile joins its perf/cost x perf/step)."""
    from rafiki_tpu.advisor import AdvisorService
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import InProcAdvisorHandle, TrainWorker

    with tempfile.TemporaryDirectory(prefix="rafiki-perfsmoke-store-") as tmp:
        store = MetaStore(os.path.join(tmp, "meta.sqlite3"))
        params = ParamsStore(os.path.join(tmp, "params"))
        cls = load_model_class(MODEL_SRC, "PerfFF")
        model = store.create_model("perfff", "IMAGE_CLASSIFICATION", None,
                                   MODEL_SRC, "PerfFF")
        job = store.create_train_job("perfsmoke", "IMAGE_CLASSIFICATION",
                                     None, TRAIN, VAL,
                                     {"MODEL_TRIAL_COUNT": pack})
        sub = store.create_sub_train_job(job["id"], model["id"])
        advisors = AdvisorService()
        aid = advisors.create_advisor(cls.get_knob_config(), kind="random")
        worker = TrainWorker(store, params, sub["id"], cls,
                             InProcAdvisorHandle(advisors, aid),
                             TRAIN, VAL, {"MODEL_TRIAL_COUNT": pack},
                             async_persist=False, trial_pack=pack)
        return worker.run()


def run_serial_trials(n_trials):
    """Serial lr-varied trials sharing one program key, so the
    per-program detector accumulates warm samples across trials."""
    from rafiki_tpu.models.ff import FeedForward

    for i in range(n_trials):
        m = FeedForward(hidden_layers=1, hidden_units=32,
                        learning_rate=1e-3 * (1 + i),
                        batch_size=32, epochs=5, seed=0)
        m.train("synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=0")
        m.destroy()


def _tick_until_breach(deadline_s):
    from rafiki_tpu.obs.perf import slo

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        state = slo.engine.tick()
        if any(st.get("breaching") for st in state.values()):
            return True
        time.sleep(0.05)
    return False


def _profile_via_cli(log_dir):
    """The real operator command from docs/perf.md, JSON mode. The
    smoke runs on the CPU, which has no peak on record, so the MFU join
    is exercised against a stated ``--peak-flops`` basis."""
    proc = _run([sys.executable, "-m", "rafiki_tpu.obs", "--dir", log_dir,
                 "--json", "profile", "--peak-flops", "1e12"])
    if proc.returncode != 0:
        raise RuntimeError(f"obs profile exited {proc.returncode}: "
                           f"{proc.stderr.strip()[:200]}")
    return json.loads(proc.stdout)["programs"]


def main() -> int:
    # Pinned before any detector exists — see module docstring.
    os.environ.setdefault("RAFIKI_PERF_K", "6")
    os.environ.pop("RAFIKI_CHAOS", None)  # phase 2 must be uninjected

    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()

    from rafiki_tpu import chaos
    from rafiki_tpu.obs.journal import journal

    t0 = time.monotonic()
    problems = []
    # Export the smoke's wider default instead of reading with a
    # different fallback than the library (RF016): every reader in
    # this process (and any child) now agrees on the width.
    os.environ.setdefault("RAFIKI_TRIAL_PACK", "4")
    pack = max(2, int(os.environ["RAFIKI_TRIAL_PACK"]))
    with tempfile.TemporaryDirectory(prefix="rafiki-perfsmoke-") as tmp:
        bench = check_bench_gate(problems, tmp)

        # -- phase 2: quiet ------------------------------------------------
        quiet_dir = os.path.join(tmp, "quiet")
        _fresh_stores(quiet_dir, tick_s=0.05)
        chaos.reset_from_env()  # RAFIKI_CHAOS popped above -> inert
        n = run_packed_round(pack)
        if n != pack:
            problems.append(f"packed round ran {n}/{pack} trials")
        _tick_until_breach(0.6)  # give the engine real ticks to NOT fire
        quiet = _read_perf(quiet_dir)
        if not quiet["costs"]:
            problems.append("quiet run captured no perf/cost record")
        if len(quiet["steps"]) < 2:
            problems.append(f"quiet run journaled {len(quiet['steps'])} "
                            "perf/step records, expected >= 2")
        for kind_name in ("anomalies", "breaches", "flights"):
            if quiet[kind_name]:
                problems.append(f"uninjected run produced "
                                f"{len(quiet[kind_name])} {kind_name}: "
                                f"{str(quiet[kind_name][0])[:150]}")
        packed_rows = []
        try:
            packed_rows = [r for r in _profile_via_cli(quiet_dir)
                           # lint: disable=RF014 — obs profile CLI rows keyed by program kind, not journal records
                           if r.get("kind") == "packed"]
        except (RuntimeError, ValueError, KeyError) as e:
            problems.append(f"obs profile failed on quiet dir: {e}")
        if not packed_rows:
            problems.append("obs profile reported no packed program")
        elif not (packed_rows[0].get("achieved_flops_s")
                  and packed_rows[0].get("mfu_vs_peak") is not None):
            problems.append(f"packed program row lacks MFU join: "
                            f"{str(packed_rows[0])[:200]}")

        # -- phase 3: injected ---------------------------------------------
        injected_dir = os.path.join(tmp, "injected")
        _fresh_stores(injected_dir, tick_s=0.05)
        os.environ["RAFIKI_CHAOS"] = "train.epoch:delay:delay=0.25:after=15"
        try:
            chaos.reset_from_env()
            run_serial_trials(4)
            breached = _tick_until_breach(2.5)
        finally:
            os.environ.pop("RAFIKI_CHAOS", None)
            chaos.reset_from_env()
        injected = _read_perf(injected_dir)
        if not injected["anomalies"]:
            problems.append("injected 0.25s epoch delay raised no "
                            "perf/anomaly record")
        if not breached or not injected["breaches"]:
            problems.append(f"anomaly-rate SLO never breached "
                            f"(tick saw breach={breached}, journal "
                            f"breaches={len(injected['breaches'])})")
        if not injected["flights"]:
            problems.append("SLO breach dumped no flight record")

        out = {
            "bench_gate": bench,
            "quiet": {k: len(v) for k, v in quiet.items()},
            "packed_join_vs_stated_peak": (
                packed_rows[0].get("mfu_vs_peak") if packed_rows else None),
            "injected": {k: len(v) for k, v in injected.items()},
            # lint: disable=RF007 — smoke artifact wall-clock
            "wall_s": round(time.monotonic() - t0, 3),
        }
        journal.close()
        os.environ.pop("RAFIKI_LOG_DIR", None)
        if problems:
            out["problems"] = problems
        print(json.dumps(out))
        return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
