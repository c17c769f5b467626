"""Digital-twin capacity plane (rafiki_tpu/obs/twin/, docs/twin.md).

What is being verified, layer by layer:

* determinism — one seed reproduces a simulation's event log and
  every headline metric bit-for-bit; different seeds diverge;
* queueing physics — at low utilization with exponential service the
  engine reproduces the M/M/1 closed-form mean sojourn;
* drift-proofing — the twin's admission/quorum/breaker constants ARE
  the live gateway/predictor objects (import identity), shed fires at
  exactly the live max_queue bound, breakers trip at exactly
  breaker_failures;
* calibration — missing journal kinds fail loudly listing every one;
  the scaled() mis-calibration knob rejects unknown segments;
* validation — the predicted-vs-measured gate passes a faithful
  calibration and fails a deliberately halved forward time;
* planning — replayed arrivals preserve per-bucket counts, the sweep
  is deterministic, the fleet search finds the smallest compliant
  worker count, and the chaos pre-gate forecasts only serving specs.
"""

from __future__ import annotations

import dataclasses
import json
import random
import threading
import time

import pytest

from rafiki_tpu.gateway.gateway import (DEADLINE_RESERVE_FRAC,
                                        GatewayConfig, LATENCY_EWMA_ALPHA)
from rafiki_tpu.obs.twin import load as load_mod
from rafiki_tpu.obs.twin import pregate, whatif
from rafiki_tpu.obs.twin.calibration import (Calibration, CalibrationError,
                                             SAMPLED_SEGMENTS)
from rafiki_tpu.obs.twin.engine import (TwinConfig, result_fingerprint,
                                        simulate)


def _open_cal(forward, workers=1, **segments):
    """A calibration with wide-open gateway knobs so only the segment
    physics under test shape the result."""
    return Calibration(
        segments=dict({"forward": sorted(forward)}, **segments),
        gateway={"max_inflight": 10 ** 6, "max_queue": 10 ** 6,
                 "default_deadline_s": 10 ** 6, "min_replies": None,
                 "hedge_grace_s": 0.0, "policy": "replicate-all",
                 "breaker_failures": 3, "breaker_cooldown_s": 5.0},
        workers=workers)


# -- determinism -----------------------------------------------------------


def test_same_seed_bit_identical():
    cal = Calibration.nominal(forward_ms=5.0, workers=2)
    cfg = TwinConfig.from_calibration(cal)
    arr = load_mod.synthesize("spike", qps=50, duration_s=4, seed=11)
    a = simulate(cal, cfg, arr, seed=3, record_events=True)
    b = simulate(cal, cfg, arr, seed=3, record_events=True)
    assert a["events"] == b["events"]
    assert result_fingerprint(a) == result_fingerprint(b)


def test_different_seed_diverges():
    cal = Calibration.nominal(forward_ms=5.0, workers=2)
    cfg = TwinConfig.from_calibration(cal)
    arr = load_mod.synthesize("constant", qps=50, duration_s=4, seed=11)
    a = simulate(cal, cfg, arr, seed=3)
    b = simulate(cal, cfg, arr, seed=4)
    assert a["event_log_sha1"] != b["event_log_sha1"]


def test_chaos_same_seed_deterministic():
    cal = Calibration.nominal(forward_ms=5.0, workers=2)
    cfg = TwinConfig.from_calibration(cal)
    arr = load_mod.synthesize("constant", qps=40, duration_s=3, seed=1)
    spec = "seed=5;inference.forward:delay:p=0.3:delay=0.05"
    a = simulate(cal, cfg, arr, seed=9, chaos_spec=spec)
    b = simulate(cal, cfg, arr, seed=9, chaos_spec=spec)
    assert result_fingerprint(a) == result_fingerprint(b)
    assert a["chaos_fired"] > 0
    assert a["p99_ms"] > simulate(cal, cfg, arr, seed=9)["p99_ms"]


def test_load_shapes_deterministic_and_sorted():
    for shape in load_mod.SHAPES:
        a = load_mod.synthesize(shape, qps=30, duration_s=5, seed=2)
        b = load_mod.synthesize(shape, qps=30, duration_s=5, seed=2)
        assert a == b and a == sorted(a) and len(a) > 0
    with pytest.raises(ValueError):
        load_mod.synthesize("sawtooth", qps=30, duration_s=5)


# -- queueing physics ------------------------------------------------------


def test_mm1_mean_sojourn_matches_closed_form():
    """Single worker, batch size 1, exponential service, Poisson
    arrivals at rho=0.2: mean sojourn must be ~1/(mu - lambda)."""
    mu, rho = 100.0, 0.2
    lam = rho * mu
    rng = random.Random(5)
    service = [rng.expovariate(mu) for _ in range(4000)]
    cal = _open_cal(service)
    cfg = TwinConfig.from_calibration(cal, workers=1, worker_batch=1)
    arr, t = [], 0.0
    arng = random.Random(6)
    while len(arr) < 2400:
        t += arng.expovariate(lam)
        arr.append(t)
    res = simulate(cal, cfg, arr, seed=1)
    assert res["shed"] == 0 and res["errors"] == 0
    expected_ms = 1000.0 / (mu - lam)
    assert res["mean_ms"] == pytest.approx(expected_ms, rel=0.15)


def test_worker_microbatching_coalesces():
    """Simultaneous queries must share one forward (pop_queries
    drains the queue), so 16 same-instant requests on one worker take
    ~2 service times (one in-flight batch + one drained batch), not
    16."""
    cal = _open_cal([0.010])
    cfg = TwinConfig.from_calibration(cal, workers=1)
    res = simulate(cal, cfg, [0.0] * 16, seed=0)
    assert res["ok"] == 16
    assert res["p99_ms"] < 3 * 10.0


def test_gateway_batch_former_coalesces_and_reports():
    """With the gateway batch former on (max_batch > 1), same-instant
    requests ride ONE fan-out: the result grows the microbatch block,
    flush count stays below request count, and the former is inside
    the bit-deterministic replay surface. max_batch=1 (batching off)
    must not grow the block at all."""
    cal = _open_cal([0.010])
    cfg = TwinConfig.from_calibration(cal, workers=1, max_batch=8,
                                      max_batch_wait_s=0.002)
    res = simulate(cal, cfg, [0.0] * 16, seed=0)
    assert res["ok"] == 16
    mb = res["microbatch"]
    assert sum(mb["flushes"].values()) < 16
    assert mb["mean_size"] > 1.0
    assert set(mb["flushes"]) <= {"size", "deadline", "drain"}
    assert result_fingerprint(res) == result_fingerprint(
        simulate(cal, cfg, [0.0] * 16, seed=0))
    off = simulate(cal, TwinConfig.from_calibration(cal, workers=1),
                   [0.0] * 16, seed=0)
    assert "microbatch" not in off


# -- drift-proofing against the live serving constants ---------------------


def test_twin_constants_are_live_imports():
    import rafiki_tpu.obs.twin.engine as eng
    from rafiki_tpu.gateway import breaker as live_breaker
    from rafiki_tpu.predictor import predictor as live_predictor
    assert eng.default_quorum is live_predictor.default_quorum
    assert eng.CircuitBreaker is live_breaker.CircuitBreaker
    assert eng.DEADLINE_RESERVE_FRAC is DEADLINE_RESERVE_FRAC
    assert eng.LATENCY_EWMA_ALPHA is LATENCY_EWMA_ALPHA


def test_twinconfig_mirrors_gatewayconfig_defaults():
    g = GatewayConfig()
    t = TwinConfig.from_gateway(g, workers=2)
    assert t.max_inflight == g.max_inflight
    assert t.max_queue == g.max_queue
    assert t.min_replies == g.min_replies
    assert t.hedge_grace_s == g.hedge_grace_s
    assert t.policy == g.policy
    assert t.breaker_failures == g.breaker_failures
    assert t.breaker_cooldown_s == g.breaker_cooldown_s


def test_shed_at_exactly_max_queue():
    """One slot in flight, max_queue waiters: the (2 + max_queue)-th
    simultaneous request is the first to shed, with the live reason."""
    cal = _open_cal([1.0])
    cfg = TwinConfig.from_calibration(cal, workers=1, max_inflight=1,
                                      max_queue=4, deadline_s=10 ** 6,
                                      worker_batch=1)
    res = simulate(cal, cfg, [0.0] * 10, seed=0)
    assert res["shed_reasons"] == {"queue_full": 10 - 1 - 4}
    assert res["shed_rate"] == pytest.approx(5 / 10)


def test_breaker_opens_at_exactly_failure_threshold():
    """Kill one of two workers; every later request counts one failed
    fan-out for it. The open transition must land after exactly
    breaker_failures failures — and never with a huge threshold."""
    cal = _open_cal([0.010], workers=2)
    spec = "seed=1;inference.forward:kill:times=1"
    arr = [i * 0.05 for i in range(30)]
    for threshold in (2, 4):
        cfg = TwinConfig.from_calibration(cal, workers=2,
                                          breaker_failures=threshold)
        res = simulate(cal, cfg, arr, seed=0, chaos_spec=spec)
        opens = [t for t in res["breaker_transitions"] if t[3] == "open"]
        assert res["workers_dead"] and opens, (threshold, res)
        first_open = opens[0][0]
        failures_before = sum(
            1 for e in simulate(cal, cfg, arr, seed=0, chaos_spec=spec,
                                record_events=True)["events"]
            if e[1] == "done" and e[0] <= first_open)
        assert failures_before >= threshold
    cfg = TwinConfig.from_calibration(cal, workers=2, breaker_failures=99)
    res = simulate(cal, cfg, arr, seed=0, chaos_spec=spec)
    assert not res["breaker_transitions"]


# -- calibration -----------------------------------------------------------


def test_calibration_missing_kinds_listed():
    with pytest.raises(CalibrationError) as ei:
        Calibration.from_records([], source="empty")
    assert set(ei.value.missing) == {"serving/hops", "gateway/config"}
    msg = str(ei.value)
    assert "serving/hops" in msg and "gateway/config" in msg


def test_calibration_roundtrip_and_scale():
    cal = Calibration.nominal(forward_ms=4.0, workers=3)
    clone = Calibration.from_dict(
        json.loads(json.dumps(cal.to_dict())))
    assert clone.segments.keys() == cal.segments.keys()
    assert clone.workers == 3
    half = cal.scaled({"forward": 0.5})
    assert max(half.segments["forward"]) == pytest.approx(
        max(cal.segments["forward"]) * 0.5)
    with pytest.raises(ValueError):
        cal.scaled({"admission_wait": 0.5})   # emergent: not scalable
    assert "admission_wait" not in SAMPLED_SEGMENTS


def test_calibration_version_gate():
    d = Calibration.nominal().to_dict()
    d["calibration_version"] = 999
    with pytest.raises(ValueError):
        Calibration.from_dict(d)


# -- validation ------------------------------------------------------------


def _fake_capture(tmp_path, n=60, gap_s=0.05, forward_s=0.020):
    """Journal files for a synthetic captured run: hop chains (for
    calibration), the gateway/config knobs, and serving/request rows
    whose e2e is forward + small wiring overhead."""
    overhead = 0.002
    recs = []
    recs.append({"kind": "gateway", "name": "config", "ts": 0.0, "pid": 1,
                 "max_inflight": 8, "max_queue": 32,
                 "default_deadline_s": 2.0, "min_replies": None,
                 "hedge_grace_s": 0.0, "policy": "replicate-all",
                 "breaker_failures": 3, "breaker_cooldown_s": 5.0})
    for i in range(n):
        t0 = 100.0 + i * gap_s
        marks = [["admit", t0, 1], ["queue", t0 + 1e-4, 1],
                 ["enq", t0 + 2e-4, 1], ["deq", t0 + 3e-4, 2],
                 ["fwds", t0 + 4e-4, 2],
                 ["fwd", t0 + 4e-4 + forward_s, 2],
                 ["reply", t0 + 5e-4 + forward_s, 2],
                 ["dec", t0 + 6e-4 + forward_s, 1]]
        recs.append({"kind": "serving", "name": "hops", "ts": t0, "pid": 1,
                     "chains": {"w0": marks}})
        recs.append({"kind": "serving", "name": "request", "ts": t0,
                     "pid": 1, "queries": 1, "ok": True, "hedged": 0,
                     "timeouts": 0,
                     "e2e_s": round(forward_s + overhead, 6)})
    path = tmp_path / "journal-gateway-1.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return tmp_path


def test_validate_passes_faithful_and_fails_halved(tmp_path):
    from rafiki_tpu.obs.twin import validate as validate_mod
    log_dir = _fake_capture(tmp_path)
    good = validate_mod.validate(log_dir, seed=0)
    assert good["ok"] is True
    assert good["p50_err"] <= good["tolerance"]
    assert good["measured"]["requests"] == 60
    bad = validate_mod.validate(log_dir, seed=0,
                                scales={"forward": 0.5})
    assert bad["ok"] is False
    assert bad["p50_err"] > bad["tolerance"]


def test_validate_needs_enough_requests(tmp_path):
    from rafiki_tpu.obs.twin import validate as validate_mod
    log_dir = _fake_capture(tmp_path, n=5)
    with pytest.raises(ValueError, match="serving/request"):
        validate_mod.validate(log_dir, seed=0)


# -- planning: replay, sweep, fleet, pre-gate ------------------------------


def test_replay_preserves_bucket_counts():
    rows = [{"bucket": 40, "span_s": 1.0, "requests": 3},
            {"bucket": 42, "span_s": 1.0, "requests": 2}]
    arr = load_mod.replay_from_ts(rows, seed=0)
    assert len(arr) == 5 and arr == sorted(arr)
    assert sum(1 for t in arr if t < 1.0) == 3
    assert sum(1 for t in arr if 2.0 <= t < 3.0) == 2
    assert load_mod.replay_from_ts(rows, seed=0) == arr


def test_sweep_deterministic_rows_and_grid_guard():
    cal = Calibration.nominal(forward_ms=5.0, workers=2)
    base = TwinConfig.from_calibration(cal)
    arr = load_mod.synthesize("constant", qps=40, duration_s=3, seed=0)
    grid = {"workers": [1, 2], "queries_per_request": [1, 4]}
    a = whatif.sweep(cal, base, arr, grid, seed=5)
    b = whatif.sweep(cal, base, arr, grid, seed=5)
    assert a == b and len(a) == 4
    assert all(r["first_saturating"] for r in a)
    with pytest.raises(ValueError):
        whatif.sweep(cal, base, arr, {"flux_capacitor": [1]}, seed=5)


def test_fleet_search_smallest_compliant(monkeypatch):
    monkeypatch.delenv("RAFIKI_SLO", raising=False)
    cal = _open_cal([0.05])
    base = TwinConfig.from_calibration(
        cal, policy="least-loaded", worker_batch=1, max_inflight=64,
        max_queue=16, deadline_s=2.0)
    # Long enough that an under-provisioned fleet's backlog actually
    # breaches the 2s deadline — over a short horizon a 1.5x-overloaded
    # pair of workers can ride out the whole run inside the budget.
    arr = load_mod.synthesize("constant", qps=60, duration_s=12, seed=2)
    out = whatif.fleet_search(cal, base, arr, seed=0)
    assert out["satisfied"] is True
    assert out["targets"] == {"p99_ms": 2000.0, "shed_rate": 0.05}
    # 50ms serial service at 60 qps needs >= 3 workers for stability.
    assert out["workers"] >= 3
    assert len(out["scanned"]) == out["workers"]
    again = whatif.fleet_search(cal, base, arr, seed=0)
    assert again == out


def test_pregate_serving_specs_only_and_deterministic():
    delay = "seed=1;inference.forward:delay:p=1.0:delay=0.05"
    a = pregate.forecast(delay, seed=3)
    b = pregate.forecast(delay, seed=3)
    assert a == b
    assert a["delta_p99_ms"] > 0
    assert pregate.forecast("seed=1;checkpoint.save:error:p=1.0") is None


def test_pregate_fleet_covers_match_filtered_worker_ids():
    # A spec pinned to the third replica (w2) must fire against the
    # forecast fleet even though the nominal calibration has 2 workers —
    # otherwise the forecast silently simulates the fault never landing.
    spec = "seed=7;inference.forward:delay:delay=3:match=w2"
    assert pregate._min_fleet_for(spec) == 3
    f = pregate.forecast(spec, seed=0)
    assert f["chaos_fired"] > 0
    assert f["delta_p99_ms"] > 0


def test_scenario_report_carries_forecast_field():
    from rafiki_tpu.chaos.runner import ScenarioReport
    rep = ScenarioReport(name="x", passed=True, checks=[], schedule=[],
                         duration_s=0.1, twin_forecast={"spec": "s"})
    assert rep.to_dict()["twin_forecast"] == {"spec": "s"}


def test_queries_per_request_rides_arrival_tuples():
    cal = Calibration.nominal(forward_ms=2.0, workers=2)
    cfg = TwinConfig.from_calibration(cal)
    res = simulate(cal, cfg, [(0.0, 3), (0.1, 1)], seed=0)
    assert res["requests"] == 2 and res["ok"] == 2


# -- a live capture, end to end ---------------------------------------------


class _SlowStub:
    """A 20 ms forward: it dominates the ~ms wiring overheads, so the
    mis-calibrated polarity below is a ~50% latency error, not noise."""

    def predict(self, queries):
        time.sleep(0.020)
        return [[0.6, 0.4] for _ in queries]


def _capture_live_run(log_dir, workers=2, clients=4, per_client=25):
    """Closed-loop load (a client fires its next request only after the
    last one answered) against the real Gateway + PredictorApp stack over
    stub workers on the in-proc bus, journaled under ``log_dir``."""
    from werkzeug.test import Client

    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.obs.anatomy import exemplars
    from rafiki_tpu.obs.journal import journal
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.predictor.app import PredictorApp
    from rafiki_tpu.worker.inference import InferenceWorker

    journal.configure(log_dir, role="gateway")
    bus, stop = InProcBus(), threading.Event()
    threads = [threading.Thread(
        target=InferenceWorker(bus, "twin", f"tw{i}", _SlowStub(),
                               stop_event=stop).run, daemon=True)
               for i in range(workers)]
    for th in threads:
        th.start()
    try:
        deadline = time.monotonic() + 10
        while len(bus.get_workers("twin")) < workers:
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.005)
        gateway = Gateway(Predictor(bus, "twin", timeout_s=2.0),
                          GatewayConfig(max_inflight=4, max_queue=8,
                                        hedge_grace_s=0.02))
        wsgi = Client(PredictorApp(gateway))
        payload = {"queries": [[1.0]] * 4, "deadline_s": 2.0}
        statuses = []

        def client():
            for _ in range(per_client):
                statuses.append(wsgi.post("/predict", json=payload).status_code)

        loops = [threading.Thread(target=client, daemon=True)
                 for _ in range(clients)]
        for th in loops:
            th.start()
        for th in loops:
            th.join()
        # A short run would otherwise journal nothing: close the
        # time-series bucket and the exemplar window.
        gateway.rollup.flush()
        exemplars.ring.flush()
        return statuses
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=2)
        journal.close()


def test_twin_calibrates_and_validates_against_a_live_capture(
        tmp_path, monkeypatch, capsys):
    """Capture -> calibrate -> validate (both polarities) -> sweep, on
    journals a live run wrote rather than synthetic ones: the bundle
    comes from the capture; replaying the captured arrivals lands
    predicted-vs-measured p50/p99 inside tolerance (exit 0) and FAILS
    with the forward time halved (exit 1) — a twin that cannot detect a
    halved forward validates nothing; the what-if sweep over the live
    calibration is byte-identical under one seed, names each row's first
    saturating resource, and its ``--suggest-slo`` set round-trips
    through the live burn-rate engine's own parser."""
    from rafiki_tpu import telemetry
    from rafiki_tpu.obs import cli
    from rafiki_tpu.obs.perf.slo import _specs_from_env

    monkeypatch.delenv("RAFIKI_SLO", raising=False)
    log_dir = str(tmp_path / "obs")
    telemetry.reset()
    try:
        statuses = _capture_live_run(log_dir)
    finally:
        telemetry.reset()
    assert statuses.count(200) >= 50 and 500 not in statuses

    cal = Calibration.from_journal_dir(log_dir)
    assert Calibration.from_dict(json.loads(json.dumps(cal.to_dict()))) \
        .segments.keys() == cal.segments.keys()

    twin = ["--dir", log_dir, "--json", "twin"]
    assert cli.main(twin + ["validate", "--seed", "7"]) == 0
    good = json.loads(capsys.readouterr().out)
    assert good["ok"] is True and good["p50_err"] <= good["tolerance"]
    assert cli.main(twin + ["validate", "--seed", "7",
                            "--scale", "forward=0.5"]) == 1
    assert json.loads(capsys.readouterr().out)["ok"] is False

    sweep = twin + ["sweep", "--seed", "7", "--qps", "60", "--duration", "4",
                    "--grid", "workers=1,2,4", "--fleet", "--suggest-slo"]
    assert cli.main(sweep) == 0
    first = capsys.readouterr().out
    assert cli.main(sweep) == 0
    assert capsys.readouterr().out == first
    doc = json.loads(first)
    assert len(doc["rows"]) == 3
    assert all(r["first_saturating"] for r in doc["rows"])
    assert doc["fleet"]["workers"] is not None
    specs = doc["suggested_slo"]
    assert len(specs) == 2
    monkeypatch.setenv("RAFIKI_SLO", json.dumps(specs))
    assert [(sp.name, sp.threshold) for sp in _specs_from_env()] == [
        (d["name"], d["threshold"]) for d in specs]
