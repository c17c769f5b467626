"""bench.py contract: ONE parseable JSON line on stdout, always.

The driver parses bench.py's stdout; an early round failed with
`parsed: null` when backend init hung. These tests pin the hardened
contract: success, forced failure, a run that finds no chip, and
watchdog deadline all still emit the JSON line (with an "error" field
and partial detail on the failure paths).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = str(Path(__file__).resolve().parent.parent / "bench.py")


def _run(env_extra: dict, timeout: int = 600):
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=timeout, env=env)
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line, got: {r.stdout!r}"
    return r.returncode, json.loads(lines[0])


def test_bench_smoke_cpu():
    rc, out = _run({"RAFIKI_BENCH_PLATFORM": "cpu", "RAFIKI_BENCH_TRIALS": "3"})
    assert rc == 0
    assert out["metric"] == "cifar10_automl_trials_per_hour"
    assert out["value"] > 0
    assert out["vs_baseline"] > 0
    assert "error" not in out
    d = out["detail"]
    # the headline is the measured real-loop number, compile-inclusive
    assert d["measured_trials"] == 3
    assert d["measured_trials_per_hour"] == out["value"]
    assert d["job_status"] == "COMPLETED"
    assert d["programs_compiled"] >= 1
    # trials beyond the shape buckets must hit the program cache
    assert d["program_cache_hits"] >= 1
    assert d["advisor_s_per_trial_at_30obs"] >= 0
    assert "estimate" in d["baseline_basis"].lower()
    # the accuracy clause is calibrated + gated on TPU; on a plain CPU
    # smoke run a 3-trial sweep misses the target by seed noise, so a
    # miss stays ADVISORY (top1_note, rc 0)
    assert d["best_top1"] is not None
    if d["top1_miss"]:
        assert "below smoke target" in d["top1_note"]
    else:
        assert d["best_top1"] >= d["top1_target"]
    assert d["top1_ceiling"] < 0.9  # flip-noise ceiling, not a saturating task
    # goodput ledger present, wall decomposed per trial (docs/observability.md)
    g = d["goodput"]
    assert g["total"]["step_s"] > 0
    assert g["goodput"] >= 0.0
    assert any(e.startswith("trial:") for e in g["entities"])
    # acceptance config 5 is an actual k>=2 ensemble, stacked path engaged
    assert d["serving_k"] == 2
    assert d["serving_path"] == "stacked"
    assert d["serving_qps_stacked"] > 0
    assert d["serving_qps_per_worker"] > 0
    # GP-vs-random lift from real tiny trials, >=3 seeds + dispersion
    assert "advisor_lift" in d
    assert len(d["advisor_lift_per_seed"]) >= 3
    assert d["advisor_lift_spread"] >= 0
    assert isinstance(d["advisor_lift_significant"], bool)
    # honesty details
    assert d["n_workers"] == 1
    # steady = trials started after the last cold compile; may be null
    # on a short smoke run where every trial overlapped a compile
    if d["steady_trial_s"] is not None:
        assert 0 < d["steady_trial_s"] <= d["slowest_trial_s"]
        assert d["steady_trials_n"] >= 1
    assert "whole-program" in d["mfu_basis"]
    # MFU vs a TPU peak is meaningless off-TPU: must be null, not 0.0
    assert d["mfu_vs_bf16_peak"] is None
    assert d["mfu_model_flops"] is None
    # time-to-target: positive wall-clock when some trial crossed the
    # target, null (never a zero) on an advisory miss
    if not d["top1_miss"]:
        assert d["wall_s_to_top1_target"] > 0
    else:
        assert d["wall_s_to_top1_target"] is None


def test_bench_top1_gate_turns_red():
    """An unreachable target must flip the bench to an error exit: the
    accuracy clause is falsifiable, not decorative."""
    rc, out = _run({"RAFIKI_BENCH_PLATFORM": "cpu", "RAFIKI_BENCH_TRIALS": "3",
                    "RAFIKI_BENCH_TOP1_TARGET": "0.99"})
    assert rc == 1
    assert "below target" in out["error"]
    assert out["detail"]["top1_miss"] is True
    assert out["value"] > 0  # the measured headline still reported


def test_bench_without_chip_or_cpu_request_fails():
    """No explicit CPU request and no chip: the bench must NOT fall back
    and measure the CPU — it errors out, rc 1, headline zero."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "RAFIKI_BENCH_PLATFORM")}
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=120, env=env)
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    out = json.loads(lines[0])
    assert r.returncode == 1
    assert "needs a tpu device" in out["error"]
    assert out["value"] == 0.0
    assert "measured_trials" not in out["detail"]


def test_bench_forced_failure_still_emits_json():
    rc, out = _run({"RAFIKI_BENCH_SELFTEST_FAIL": "1"}, timeout=120)
    assert rc == 1
    assert "error" in out and "forced backend failure" in out["error"]
    assert out["metric"] == "cifar10_automl_trials_per_hour"
    assert out["value"] == 0.0


def test_bench_deadline_watchdog_emits_json():
    # The selftest stall (after backend init) guarantees the 8s
    # watchdog fires mid-run regardless of cache warmth (8s leaves a
    # loaded box room to import jax before the stall begins).
    rc, out = _run({"RAFIKI_BENCH_PLATFORM": "cpu",
                    "RAFIKI_BENCH_DEADLINE_S": "8",
                    "RAFIKI_BENCH_SELFTEST_SLEEP_S": "30"}, timeout=120)
    assert rc == 3
    assert "deadline exceeded" in out["error"]
    # partial detail survived
    assert "device" in out["detail"]
