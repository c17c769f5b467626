"""Perf sentinel (rafiki_tpu/obs/perf/, docs/perf.md): the EWMA+MAD
anomaly detector, the multi-window SLO burn-rate engine (driven on a
fake clock — no sleeps), the breach -> journal -> flight-record chain,
and the scripts/bench_report.py regression gate.

The full live chain (train loop -> profiler -> anomaly -> SLO breach
under injected chaos) is exercised end to end by scripts/perf_smoke.py;
these tests pin the pieces it composes."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.obs import journal as journal_mod
from rafiki_tpu.obs.journal import journal
from rafiki_tpu.obs.perf.anomaly import EwmaMad
from rafiki_tpu.obs.perf.slo import SloEngine, SloSpec, _specs_from_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_REPORT = os.path.join(REPO, "scripts", "bench_report.py")


@pytest.fixture
def journaled(tmp_path):
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()


@pytest.fixture
def counters():
    telemetry.reset()
    try:
        yield
    finally:
        telemetry.reset()


# -- EwmaMad -----------------------------------------------------------------


def test_ewma_quiet_on_steady_series():
    d = EwmaMad(warmup=4)
    # +-5% deterministic jitter around 1.0 stays inside the 10% MAD
    # floor band at any k >= 1.
    for i in range(64):
        assert d.observe(1.0 + 0.05 * (-1) ** i) is None


def test_ewma_flags_spike_and_reports_ratio():
    d = EwmaMad(warmup=4, k=4.0)
    for _ in range(10):
        assert d.observe(1.0) is None
    report = d.observe(3.0)
    assert report is not None
    assert report["ratio"] == pytest.approx(3.0)
    assert report["value"] == 3.0
    assert report["threshold"] < 3.0
    assert report["mean"] == pytest.approx(1.0)


def test_ewma_never_flags_during_warmup():
    d = EwmaMad(warmup=8)
    assert d.observe(1.0) is None
    for _ in range(6):  # n stays below warmup for these
        assert d.observe(50.0) is None


def test_ewma_absorbs_anomalies_slowly():
    """A flagged spike moves the mean at a quarter learning rate: one
    outlier must not drag the baseline up to itself."""
    d = EwmaMad(warmup=4, alpha=0.25)
    for _ in range(10):
        d.observe(1.0)
    assert d.observe(10.0) is not None
    assert d.mean < 2.0


def test_ewma_sustained_shift_rebaselines_eventually():
    d = EwmaMad(warmup=4, alpha=0.25)
    for _ in range(10):
        d.observe(1.0)
    flagged = sum(d.observe(3.0) is not None for _ in range(200))
    assert 0 < flagged < 200  # alerts on the shift, then adopts it
    assert d.observe(3.0) is None


def test_ewma_env_knobs(monkeypatch):
    monkeypatch.setenv("RAFIKI_PERF_K", "9.5")
    monkeypatch.setenv("RAFIKI_PERF_WARMUP", "3")
    d = EwmaMad()
    assert d.k == 9.5 and d.warmup == 3
    monkeypatch.setenv("RAFIKI_PERF_K", "not-a-number")
    assert EwmaMad().k == 4.0  # malformed env falls back to default


# -- SloEngine ---------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _engine(spec, clock):
    return SloEngine(specs=[spec], tick_s=0.0, clock=clock)


def test_slo_fresh_process_never_alarms(counters):
    clk = _Clock()
    eng = _engine(SloSpec("r", "counter:perf_test.x", 0.0,
                          windows=(10.0,)), clk)
    telemetry.inc("perf_test.x", 100)  # huge, but no window of history
    for t in (0.0, 1.0, 5.0):
        clk.now = t
        assert eng.tick()["r"]["breaching"] == 0


def test_slo_rate_breach_after_window_covered(counters, journaled):
    clk = _Clock()
    eng = _engine(SloSpec("r", "counter:perf_test.x", 0.0,
                          windows=(10.0,)), clk)
    eng.tick()
    telemetry.inc("perf_test.x", 5)
    clk.now = 11.0
    st = eng.tick()["r"]
    assert st["breaching"] == 1
    assert st["value"] == pytest.approx(5.0 / 11.0)


def test_slo_breach_requires_every_window(counters):
    """Multi-window burn rule: the long window must also be covered
    AND burning before the spec alarms."""
    clk = _Clock()
    eng = _engine(SloSpec("r", "counter:perf_test.x", 0.0,
                          windows=(10.0, 100.0)), clk)
    eng.tick()
    telemetry.inc("perf_test.x", 5)
    clk.now = 11.0
    assert eng.tick()["r"]["breaching"] == 0  # 100s window not evaluable
    telemetry.inc("perf_test.x", 5)
    clk.now = 101.0
    assert eng.tick()["r"]["breaching"] == 1  # both windows burning


def test_slo_rate_recovers_when_counter_goes_flat(counters, journaled):
    clk = _Clock()
    eng = _engine(SloSpec("r", "counter:perf_test.x", 0.0,
                          windows=(10.0,)), clk)
    eng.tick()
    telemetry.inc("perf_test.x", 5)
    clk.now = 11.0
    assert eng.tick()["r"]["breaching"] == 1
    for t in (20.0, 30.0, 45.0):  # counter flat -> short-window rate 0
        clk.now = t
        st = eng.tick()["r"]
    assert st["breaching"] == 0
    assert telemetry.snapshot()["counters"].get("slo.recoveries") == 1
    kinds = [(r["kind"], r["name"]) for r in journal_mod.read_dir(journal.log_dir)]
    assert ("slo", "recover") in kinds


def test_slo_level_mode_requires_sustained_violation(counters):
    clk = _Clock()
    eng = _engine(SloSpec("g", "gauge:perf_test.depth", 2.0,
                          windows=(10.0,)), clk)
    telemetry.set_gauge("perf_test.depth", 5.0)
    for t in (0.0, 4.0, 8.0):
        clk.now = t
        assert eng.tick()["g"]["breaching"] == 0  # window not covered
    clk.now = 12.0
    assert eng.tick()["g"]["breaching"] == 1  # > 2.0 for a full window
    telemetry.set_gauge("perf_test.depth", 1.0)  # one in-window dip
    clk.now = 14.0
    assert eng.tick()["g"]["breaching"] == 0


def test_slo_ratio_mode(counters):
    clk = _Clock()
    eng = _engine(SloSpec("s", "ratio:perf_test.shed/"
                               "perf_test.shed+perf_test.ok", 0.05,
                          windows=(10.0,)), clk)
    telemetry.inc("perf_test.ok", 1)
    eng.tick()
    telemetry.inc("perf_test.shed", 2)
    telemetry.inc("perf_test.ok", 8)
    clk.now = 11.0
    st = eng.tick()["s"]
    assert st["breaching"] == 1
    assert st["value"] == pytest.approx(0.2)


def test_slo_min_wall_s_gates_young_engines(counters):
    clk = _Clock()
    eng = _engine(SloSpec("r", "counter:perf_test.x", 0.0,
                          windows=(10.0,), min_wall_s=100.0), clk)
    eng.tick()
    telemetry.inc("perf_test.x", 5)
    clk.now = 50.0
    assert eng.tick()["r"]["breaching"] == 0  # burning, but too young
    telemetry.inc("perf_test.x", 5)
    clk.now = 120.0
    assert eng.tick()["r"]["breaching"] == 1


def test_slo_breach_journals_counts_and_dumps_flight(counters, journaled):
    clk = _Clock()
    eng = _engine(SloSpec("perf_test_burn", "counter:perf_test.x", 0.0,
                          windows=(10.0,)), clk)
    eng.tick()
    telemetry.inc("perf_test.x", 3)
    clk.now = 11.0
    assert eng.tick()["perf_test_burn"]["breaching"] == 1

    assert telemetry.snapshot()["counters"].get("slo.breaches") == 1
    records = journal_mod.read_dir(journal.log_dir)
    breaches = [r for r in records
                if r["kind"] == "slo" and r["name"] == "breach"]
    assert len(breaches) == 1
    assert breaches[0]["slo"] == "perf_test_burn"
    assert breaches[0]["source"] == "counter:perf_test.x"
    flights = list(Path(journaled).glob("flight-*.json"))
    assert len(flights) == 1
    bundle = json.loads(flights[0].read_text())
    assert bundle["reason"] == "slo:perf_test_burn"
    # Re-breach without recovery must not re-fire (edge-triggered).
    telemetry.inc("perf_test.x", 3)
    clk.now = 12.0
    eng.tick()
    assert telemetry.snapshot()["counters"].get("slo.breaches") == 1


def test_slo_maybe_tick_honors_interval(counters):
    clk = _Clock()
    eng = SloEngine(specs=[SloSpec("r", "counter:perf_test.x", 0.0)],
                    tick_s=5.0, clock=clk)
    clk.now = 1.0
    assert eng.maybe_tick() is None  # < tick_s since construction tick
    clk.now = 6.0
    assert eng.maybe_tick() is not None


def test_slo_spec_mode_derivation():
    assert SloSpec("a", "counter:x", 1.0).mode == "rate"
    assert SloSpec("b", "ratio:x/y", 1.0).mode == "ratio"
    assert SloSpec("c", "gauge:x", 1.0).mode == "level"
    assert SloSpec("d", "hist_p99:x", 1.0).mode == "level"
    assert SloSpec("e", "ledger:goodput", 1.0).mode == "level"
    assert SloSpec("f", "ledger:downtime_s", 1.0).mode == "rate"
    with pytest.raises(ValueError):
        SloSpec("g", "counter:x", 1.0, op=">=")
    with pytest.raises(ValueError):
        SloSpec("h", "counter:x", 1.0, windows=())


def test_slo_specs_from_env(monkeypatch, journaled):
    monkeypatch.delenv("RAFIKI_SLO", raising=False)
    assert _specs_from_env() is None  # unset -> engine uses defaults
    monkeypatch.setenv("RAFIKI_SLO", "off")
    assert _specs_from_env() == []
    monkeypatch.setenv("RAFIKI_SLO", json.dumps(
        [{"name": "x", "source": "counter:a.b", "threshold": 1.5,
          "windows": [5, 30]}]))
    specs = _specs_from_env()
    assert [s.name for s in specs] == ["x"]
    assert specs[0].windows == (5.0, 30.0) and specs[0].mode == "rate"
    monkeypatch.setenv("RAFIKI_SLO", "[{malformed")
    assert _specs_from_env() is None  # falls back to defaults...
    errors = [r for r in journal_mod.read_dir(journal.log_dir)
              if r["kind"] == "slo" and r["name"] == "config_error"]
    assert errors  # ...and says so in the journal


# -- profiler collector ------------------------------------------------------


def test_profiler_collector_joins_cost_and_steps(counters, journaled):
    from rafiki_tpu.obs.perf import profiler

    profiler.reset()
    try:
        key = ("test_prog", "x")
        profiler.note_epoch(key, 0.5, cold=True)
        for _ in range(4):
            profiler.note_epoch(key, 0.012, feed_s=0.002)
        snap = telemetry.snapshot()
        assert "perf" in snap  # registered collector rides the snapshot
        progs = snap["perf"]["programs"]
        summary = progs[profiler.key_hash(key)]
        assert summary["epochs"] == 4 and summary["cold_epochs"] == 1
        assert summary["step_p50_s"] == pytest.approx(0.010)
        steps = [r for r in journal_mod.read_dir(journal.log_dir)
                 if r["kind"] == "perf" and r["name"] == "step"]
        assert len(steps) == 5
        assert sum(r["cold"] for r in steps) == 1
    finally:
        profiler.reset()


def test_profiler_anomaly_charges_badput(counters, journaled):
    from rafiki_tpu.obs import ledger as ledger_mod
    from rafiki_tpu.obs.perf import profiler

    profiler.reset()
    try:
        key = ("test_prog", "badput")
        for _ in range(12):
            profiler.note_epoch(key, 0.01)
        report = profiler.note_epoch(key, 0.5)
        assert report is not None and report["ratio"] > 10
        snap = telemetry.snapshot()
        assert snap["counters"].get("perf.anomalies") == 1
        assert snap["goodput"]["total"].get("badput_s", 0.0) == pytest.approx(
            0.49, abs=0.01)
        anomalies = [r for r in journal_mod.read_dir(journal.log_dir)
                     if r["kind"] == "perf" and r["name"] == "anomaly"]
        assert len(anomalies) == 1 and anomalies[0]["phase"] == "step"
        assert "badput_s" in ledger_mod.BUCKETS
    finally:
        profiler.reset()


# -- bench_report gate -------------------------------------------------------


def _round(n, headline, error=None):
    payload = {"metric": "m", "value": headline.get("trials_per_hour"),
               "headline": headline}
    if error:
        payload["error"] = error
    return {"n": n, "cmd": "bench", "rc": 1 if error else 0,
            "tail": [], "parsed": payload}


def _run_report(tmp_path, rounds, extra_args=()):
    paths = []
    for doc in rounds:
        p = tmp_path / f"BENCH_r{doc['n']:02d}.json"
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    proc = subprocess.run(
        [sys.executable, BENCH_REPORT, *paths, *extra_args],
        capture_output=True, text=True, timeout=60)
    return proc.returncode, json.loads(proc.stdout)


HEAD = {"trials_per_hour": 1200.0, "canonical_trial_s": 3.0,
        "compile_s": 12.0, "train_img_per_s": 45000.0}


def test_bench_report_flat_history_passes(tmp_path):
    drift = dict(HEAD, trials_per_hour=1150.0)  # within 10% band
    rc, rep = _run_report(tmp_path, [_round(1, HEAD), _round(2, drift)])
    assert rc == 0
    assert rep["verdict"] == "ok"
    assert rep["metrics"]["trials_per_hour"]["verdict"] == "flat"


def test_bench_report_gates_on_regression(tmp_path):
    bad = dict(HEAD, trials_per_hour=400.0, canonical_trial_s=9.0)
    rc, rep = _run_report(tmp_path, [_round(1, HEAD), _round(2, bad)])
    assert rc == 1
    assert rep["verdict"] == "regressed"
    assert set(rep["regressed"]) == {"trials_per_hour", "canonical_trial_s"}
    assert rep["metrics"]["trials_per_hour"]["delta_frac"] == pytest.approx(
        2.0 / 3.0, abs=1e-3)


def test_bench_report_lower_better_improvement(tmp_path):
    better = dict(HEAD, canonical_trial_s=2.0, compile_s=13.0)
    rc, rep = _run_report(tmp_path, [_round(1, HEAD), _round(2, better)])
    assert rc == 0
    assert rep["metrics"]["canonical_trial_s"]["verdict"] == "improved"
    assert rep["metrics"]["compile_s"]["verdict"] == "flat"


def test_bench_report_error_rounds_are_no_data(tmp_path):
    """r03-r05 shape: an error payload with value 0.0 must not read as
    a 100% regression against the one real round."""
    dead = _round(3, {"trials_per_hour": 0.0}, error="backend unavailable")
    rc, rep = _run_report(tmp_path, [_round(1, HEAD), dead])
    assert rc == 0
    assert rep["metrics"]["trials_per_hour"]["verdict"] == "single-point"
    assert rep["rounds"][1]["has_data"] is False


def test_bench_report_backfills_pre_schema_artifacts(tmp_path):
    """A round with no headline block (schema 1) trends via the
    value/detail fallbacks — r02's real shape."""
    old = {"n": 1, "cmd": "bench", "rc": 0, "tail": [], "parsed": {
        "metric": "m", "value": 1200.0,
        "detail": {"canonical_trial_s": 3.0, "compile_s": 12.0,
                   "train_img_per_s": 45000.0}}}
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps(old))
    new = tmp_path / "BENCH_r02.json"
    new.write_text(json.dumps(_round(2, dict(HEAD, trials_per_hour=390.0))))
    proc = subprocess.run(
        [sys.executable, BENCH_REPORT, str(p), str(new)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    rep = json.loads(proc.stdout)
    assert "trials_per_hour" in rep["regressed"]


def test_bench_report_tolerance_flag(tmp_path):
    bad = dict(HEAD, trials_per_hour=700.0)  # -42%
    rc, _ = _run_report(tmp_path, [_round(1, HEAD), _round(2, bad)],
                        extra_args=("--tolerance", "0.5"))
    assert rc == 0
