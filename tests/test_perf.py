"""Perf sentinel (rafiki_tpu/obs/perf/, docs/perf.md): the EWMA+MAD
anomaly detector, the multi-window SLO burn-rate engine (driven on a
fake clock — no sleeps), the breach -> journal -> flight-record chain,
and the full live chain in both polarities: a quiet packed round
profiles with no anomaly, and an injected ``train.epoch`` delay lands
anomaly -> SLO breach -> flight record."""

import glob
import json
import os
import time
from pathlib import Path

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.obs import journal as journal_mod
from rafiki_tpu.obs.journal import journal
from rafiki_tpu.obs.perf.anomaly import EwmaMad
from rafiki_tpu.obs.perf.slo import SloEngine, SloSpec, _specs_from_env


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


# -- the live chain, both polarities -----------------------------------------

_PERF_MODEL_SRC = b"""
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


@pytest.fixture
def live_sentinel(tmp_path, monkeypatch):
    """The process-global sentinel under a fresh journal dir, with one
    anomaly-rate SLO on short windows. ``RAFIKI_PERF_K=6``: the injected
    spike is ~100x the warm mean, so a wider band costs no sensitivity
    there while making the quiet polarity's zero-anomaly assertion
    robust to scheduler jitter on sub-millisecond steps."""
    from rafiki_tpu import chaos
    from rafiki_tpu.obs.perf import profiler, slo

    monkeypatch.setenv("RAFIKI_PERF_K", "6")
    monkeypatch.delenv("RAFIKI_CHAOS", raising=False)
    monkeypatch.setenv("RAFIKI_LOG_DIR", str(tmp_path))
    chaos.reset_from_env()
    journal.configure(tmp_path, role="perftest")
    telemetry.reset()
    profiler.reset()
    slo.configure([SloSpec(name="step_anomaly_rate",
                           source="counter:perf.anomalies",
                           threshold=0.0, windows=(0.4, 1.2))], tick_s=0.05)
    try:
        yield tmp_path
    finally:
        monkeypatch.delenv("RAFIKI_CHAOS", raising=False)
        chaos.reset_from_env()
        journal.close()
        slo.configure_from_env()
        profiler.reset()
        telemetry.reset()


def _read_perf(log_dir):
    recs = journal_mod.read_dir(log_dir)

    def of(kind, name):
        return [r for r in recs if r["kind"] == kind and r["name"] == name]

    return {"costs": of("perf", "cost"), "steps": of("perf", "step"),
            "anomalies": of("perf", "anomaly"),
            "breaches": of("slo", "breach"),
            "flights": glob.glob(os.path.join(log_dir, "flight-*.json"))}


def _tick_until_breach(deadline_s):
    from rafiki_tpu.obs.perf import slo

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        state = slo.engine.tick()
        if any(st.get("breaching") for st in state.values()):
            return True
        time.sleep(0.05)
    return False


def test_quiet_packed_round_profiles_with_no_anomaly(live_sentinel, capsys):
    """One uninjected packed TrainWorker round: cost capture and step
    sampling land in the journal, ``obs profile`` joins them into an
    achieved-FLOP/s + MFU row for the *packed* program, and the sentinel
    does not cry wolf (no anomaly, no breach, no flight record)."""
    from rafiki_tpu.advisor import AdvisorService
    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.obs import cli
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import InProcAdvisorHandle, TrainWorker

    pack = 4
    train = "synthetic://images?classes=4&n=512&w=8&h=8&c=1&seed=0"
    val = "synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=1"
    log_dir = str(live_sentinel)
    store = MetaStore(os.path.join(log_dir, "meta.sqlite3"))
    params = ParamsStore(os.path.join(log_dir, "params"))
    cls = load_model_class(_PERF_MODEL_SRC, "PerfFF")
    model = store.create_model("perfff", "IMAGE_CLASSIFICATION", None,
                               _PERF_MODEL_SRC, "PerfFF")
    budget = {"MODEL_TRIAL_COUNT": pack}
    job = store.create_train_job("perf", "IMAGE_CLASSIFICATION", None,
                                 train, val, budget)
    sub = store.create_sub_train_job(job["id"], model["id"])
    advisors = AdvisorService()
    aid = advisors.create_advisor(cls.get_knob_config(), kind="random")
    worker = TrainWorker(store, params, sub["id"], cls,
                         InProcAdvisorHandle(advisors, aid), train, val,
                         budget, async_persist=False, trial_pack=pack)
    assert worker.run() == pack
    assert not _tick_until_breach(0.6)  # real ticks in which NOT to fire

    quiet = _read_perf(log_dir)
    assert quiet["costs"]
    assert len(quiet["steps"]) >= 2
    assert (quiet["anomalies"], quiet["breaches"], quiet["flights"]) == (
        [], [], [])
    # The CPU has no peak on record: the MFU join runs against a stated
    # --peak-flops basis.
    capsys.readouterr()
    assert cli.main(["--dir", log_dir, "--json", "profile",
                     "--peak-flops", "1e12"]) == 0
    rows = json.loads(capsys.readouterr().out)["programs"]
    packed = [r for r in rows if r.get("kind") == "packed"]
    assert packed
    assert packed[0]["achieved_flops_s"]
    assert packed[0]["mfu_vs_peak"] is not None


def test_injected_epoch_delay_lands_anomaly_breach_flight(live_sentinel,
                                                          monkeypatch):
    """The chaos plane delays ``train.epoch`` 0.25 s from its 16th hit
    (a >100x step inflation) under serial trials sharing one program
    key: the detector fires (``perf/anomaly``), the burn-rate engine
    breaches the anomaly-rate SLO (``slo/breach``), and the breach dumps
    a flight record."""
    from rafiki_tpu import chaos
    from rafiki_tpu.models.ff import FeedForward

    monkeypatch.setenv("RAFIKI_CHAOS", "train.epoch:delay:delay=0.25:after=15")
    chaos.reset_from_env()
    for i in range(4):
        m = FeedForward(hidden_layers=1, hidden_units=32,
                        learning_rate=1e-3 * (1 + i),
                        batch_size=32, epochs=5, seed=0)
        m.train("synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=0")
        m.destroy()
    breached = _tick_until_breach(2.5)

    injected = _read_perf(str(live_sentinel))
    assert injected["anomalies"]
    assert breached and injected["breaches"]
    assert injected["flights"]
