"""Observability plane (rafiki_tpu/obs/, docs/observability.md):
trace propagation through bus envelopes, the bounded on-disk journal
ring, the goodput ledger, the flight recorder, and the Prometheus
exposition (golden-file pinned).

Most tests pin the in-process mechanics; the last one stitches one
pinned trace across three real processes (a gateway and two spawned
inference workers) and line-parses the live ``/metrics?format=prom``.
"""

import json
import os
import re
import time
from pathlib import Path

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.obs import context as trace_context
from rafiki_tpu.obs import journal as journal_mod
from rafiki_tpu.obs.journal import Journal, journal

GOLDEN = Path(__file__).parent / "data" / "prom_golden.txt"


@pytest.fixture
def journaled(tmp_path):
    """The process-global journal, configured into a tmp dir and
    guaranteed back to the unconfigured no-op afterwards."""
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()


# -- trace propagation -------------------------------------------------------


class _StubModel:
    def predict(self, queries):
        return [[0.6, 0.4] for _ in queries]


def test_trace_propagates_through_bus_envelope(journaled):
    """One traced predict batch: the SAME trace id must appear on the
    predictor's fan-out hop, the worker's pop hop, and the worker's
    forward span — the envelope carries it, not shared thread state."""
    import threading

    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.worker.inference import InferenceWorker

    bus = InProcBus()
    stop = threading.Event()
    worker = InferenceWorker(bus, "tp", "w1", _StubModel(), stop_event=stop)
    th = threading.Thread(target=worker.run, daemon=True)
    th.start()
    try:
        tid = "cafe" * 8
        with trace_context.trace(tid):
            out = Predictor(bus, "tp", timeout_s=5.0).predict([[1.0]])
        assert out and "error" not in str(out[0])
    finally:
        stop.set()
        th.join(timeout=5)

    records = journal_mod.read_dir(journaled)
    traced = [r for r in records if r.get("trace_id") == tid]
    names = {(r["kind"], r["name"]) for r in traced}
    assert ("bus", "add_query") in names
    assert ("bus", "pop_query") in names
    assert ("span", "inference.forward") in names
    # the stitched view is time-ordered and self-identifying
    for r in traced:
        assert r["pid"] == os.getpid()
        assert r["role"] == "test"
        assert r["ts"] > 0


def test_untraced_messages_stay_bare_tuples():
    """No active trace → 2-tuple envelopes (wire back-compat) and no
    journal side channel needed to serve."""
    from rafiki_tpu.bus import InProcBus

    bus = InProcBus()
    bus.add_worker("tp", "w1")
    assert trace_context.current_trace_id() is None
    bus.add_query("w1", "q1", [1.0])
    items = bus.pop_queries("w1", timeout=1.0)
    assert items == [("q1", [1.0])]


def test_trace_context_nesting_and_process_default():
    with trace_context.trace("a" * 32):
        assert trace_context.current_trace_id() == "a" * 32
        with trace_context.trace():  # inherits, does not mint
            assert trace_context.current_trace_id() == "a" * 32
    assert trace_context.current_trace_id() is None
    trace_context.set_process_trace("b" * 32)
    try:
        assert trace_context.current_trace_id() == "b" * 32
        with trace_context.trace("c" * 32):  # thread-local wins
            assert trace_context.current_trace_id() == "c" * 32
    finally:
        trace_context.set_process_trace(None)


# -- journal ring ------------------------------------------------------------


def test_journal_ring_rotates_and_stays_bounded(tmp_path):
    j = Journal(tmp_path, role="ring", max_records=10)
    try:
        for i in range(25):
            j.record("event", f"e{i}")
        live = j.path
        old = live.with_name(live.name + ".1")
        assert old.exists()
        n_live = sum(1 for _ in open(live))
        n_old = sum(1 for _ in open(old))
        # disk never holds more than 2x max lines, and exactly one
        # rotated generation exists (the older one was overwritten)
        assert n_live <= 10 and n_old <= 10
        assert len(list(tmp_path.glob("journal-*"))) == 2
        # the SURVIVING window is the newest records, across both files
        merged = journal_mod.read_dir(tmp_path)
        assert [r["name"] for r in merged] == [f"e{i}" for i in range(10, 25)]
        assert [r["name"] for r in j.tail(5)] == [f"e{i}" for i in range(20, 25)]
    finally:
        j.close()


def test_journal_unconfigured_is_noop_and_reader_skips_torn_lines(tmp_path):
    j = Journal()
    j.record("event", "dropped")  # must not raise, must not write
    assert j.path is None
    # a crashed writer leaves a torn final line; readers skip it
    p = tmp_path / "journal-x-1.jsonl"
    p.write_text(json.dumps({"ts": 1.0, "name": "ok"}) + "\n" + '{"ts": 2.0, "na')
    assert [r["name"] for r in journal_mod.read_dir(tmp_path)] == ["ok"]


def test_spans_flush_into_journal(journaled):
    with telemetry.span("obs.test_phase"):
        pass
    recs = [r for r in journal_mod.read_dir(journaled)
            if r["kind"] == "span" and r["name"] == "obs.test_phase"]
    assert len(recs) == 1
    assert recs[0]["dur_s"] >= 0


# -- goodput ledger ----------------------------------------------------------


def test_ledger_entities_and_goodput_rollup():
    from rafiki_tpu.obs.ledger import ledger

    ledger.reset()
    try:
        with ledger.entity("trial:t1"):
            ledger.add("compile_s", 3.0)
            ledger.add("step_s", 1.0)
        ledger.add("downtime_s", 2.0, entity="job:j1")
        snap = ledger.snapshot()
        t1 = snap["entities"]["trial:t1"]
        assert t1["compile_s"] == 3.0 and t1["step_s"] == 1.0
        assert t1["wall_s"] > 0
        assert snap["entities"]["job:j1"]["downtime_s"] == 2.0
        assert snap["total"]["compile_s"] == 3.0
        assert snap["goodput"] == pytest.approx(
            1.0 / snap["total"]["wall_s"], rel=1e-3)
        # rides along in every telemetry snapshot (GET /metrics)
        assert telemetry.snapshot()["goodput"]["total"]["step_s"] == 1.0
    finally:
        ledger.reset()


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_dump(journaled):
    from rafiki_tpu.obs import recorder

    journal.record("event", "before_crash")
    with trace_context.trace("d" * 32):
        path = recorder.dump("test_reason", extra={"detail": "x"})
    assert path is not None and path.exists()
    payload = json.loads(path.read_text())
    assert payload["reason"] == "test_reason"
    assert payload["role"] == "test"
    assert payload["trace_id"] == "d" * 32
    assert payload["detail"] == "x"
    assert any(r["name"] == "before_crash" for r in payload["journal_tail"])
    assert "counters" in payload["telemetry"]
    # the dump itself is journaled, so `obs tail` shows the crash marker
    assert any(r["kind"] == "flight" for r in journal.tail(8))


def test_flight_recorder_without_log_dir_is_noop(tmp_path, monkeypatch):
    from rafiki_tpu.obs import recorder

    monkeypatch.delenv(journal_mod.ENV_VAR, raising=False)
    assert journal.log_dir is None or not journal.configured
    if journal.log_dir is None:
        assert recorder.dump("nowhere") is None


# -- CLI ---------------------------------------------------------------------


def test_obs_cli_trace_and_tail(journaled, capsys):
    from rafiki_tpu.obs import cli

    tid = "beef" * 8
    with trace_context.trace(tid):
        journal.record("event", "hop1")
        journal.record("event", "hop2")
    journal.record("event", "unrelated")

    assert cli.main(["--dir", str(journaled), "trace", tid]) == 0
    out = capsys.readouterr().out
    assert "hop1" in out and "hop2" in out and "unrelated" not in out
    assert "2 records" in out

    # prefix match works (operators paste truncated ids)
    assert cli.main(["--dir", str(journaled), "--json", "trace", tid[:8]]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert {r["trace_id"] for r in lines} == {tid}

    assert cli.main(["--dir", str(journaled), "tail", "-n", "1"]) == 0
    assert "unrelated" in capsys.readouterr().out

    # unknown trace: exit 1, message on stderr
    assert cli.main(["--dir", str(journaled), "trace", "f" * 32]) == 1


# -- Prometheus exposition ---------------------------------------------------

#: A fixed, fully-populated snapshot: every branch of the renderer —
#: counters, gauges, histogram summaries, span aggregates, collector
#: flattening (numeric kept, strings dropped), name sanitization and
#: label escaping.
_SNAPSHOT = {
    "ts": 1700000000.0,
    "counters": {"gateway.shed": 3, "bus.queries_added": 12.0,
                 "serving.microbatch.flush_size": 2,
                 "gateway.blackout_retries": 1.0,
                 "serving.tenant.admitted": 20,
                 "serving.tenant.shed": 5,
                 "serving.tenant.shed_batch": 5,
                 "tenant.accounting_evictions": 1,
                 "tenancy.residency_hits": 7,
                 "tenancy.residency_misses": 3,
                 "tenancy.residency_evictions": 2,
                 "tenancy.host_queries": 10.0,
                 "tenancy.jobs_admitted": 2,
                 "tenancy.jobs_rejected": 1},
    "gauges": {"bus.queue_depth": 2, "serving.qps": 18.0,
               "serving.tenant.burn": 0.4765,
               "tenancy.residency_used_bytes": 160},
    "histograms": {
        "predictor.gather_s": {"count": 4, "sum": 0.5, "p50": 0.1,
                               "p90": 0.2, "p99": 0.25},
        "serving.hop.forward_s": {"count": 9, "sum": 0.09, "p50": 0.01,
                                  "p90": 0.012, "p99": 0.02},
        "serving.fanout_cost_s": {"count": 4, "sum": 0.02, "p50": 0.004,
                                  "p90": 0.006, "p99": 0.008},
        "serving.microbatch.size": {"count": 2, "sum": 6.0, "p50": 3.0,
                                    "p90": 4.0, "p99": 4.0},
        "serving.microbatch.fill_ratio": {"count": 2, "sum": 1.5,
                                          "p50": 0.75, "p90": 1.0,
                                          "p99": 1.0},
        "serving.hop.gateway_batch_wait_s": {"count": 4, "sum": 0.012,
                                             "p50": 0.003, "p90": 0.005,
                                             "p99": 0.006},
    },
    "spans": {
        'trial "quoted"': {"count": 2, "total_s": 1.5},
        "worker.epoch": {"count": 8, "total_s": 4.0},
    },
    "goodput": {
        "total": {"step_s": 1.0, "wall_s": 4.0},
        "goodput": 0.25,
        "note": "strings have no prometheus representation",
    },
    "perf": {
        "n_programs": 1,
        "programs": {"8c2d3ca7df": {"k": 1, "epochs": 4,
                                    "step_p50_s": 0.005, "mfu": 0.41,
                                    "kind": "strings are dropped"}},
    },
    "slo": {
        "specs": 2,
        "breaching": 1,
        "state": {"step_anomaly_rate": {"breaching": 1, "threshold": 0.05,
                                        "value": 0.2, "burn": 4.0}},
    },
    "health": {
        "divergences": 1,
        "capsules": 1,
        "evictions": 0,
        "contained": 1,
        "badput_charged_s": 2.25,
    },
    "serving": {
        "buckets_flushed": 3,
        "last": {"bucket": 1700000000, "requests": 18, "qps": 18.0,
                 "p50_ms": 11.5, "p99_ms": 40.25, "shed_rate": 0.0,
                 "context_note": "strings are dropped"},
    },
    "serving_exemplars": {
        "retained": 2,
        "offered": 18,
        "windows_flushed": 1,
        "cap": 8,
        "window_s": 30.0,
        "slowest_s": 0.040251,
    },
    "search": {
        "n_proposed": 12,
        "n_scored": 9,
        "n_doomed": 2,
        "n_pending": 1,
        "scored_wall_s": 54.0,
        "doomed_wall_s": 6.0,
        "elapsed_s": 60.0,
        "effective_trials_per_hour": 540.0,
        "regret": 0.0834,
        "best_score": 0.91,
        "n_killed": 2,
        "n_false_kills": 0,
        "n_speculations": 3,
        "n_corrections": 2,
    },
}


def test_prometheus_exposition_matches_golden():
    from rafiki_tpu.obs import prom

    rendered = prom.to_prometheus(_SNAPSHOT)
    assert rendered == GOLDEN.read_text(), (
        "Prometheus exposition drifted from tests/data/prom_golden.txt — "
        "if the change is intentional, regenerate the golden file:\n"
        "  python -c 'from tests.test_obs import _SNAPSHOT; "
        "from rafiki_tpu.obs import prom; "
        "print(prom.to_prometheus(_SNAPSHOT), end=\"\")' "
        "> tests/data/prom_golden.txt")


def test_prometheus_exposition_is_deterministic_and_parseable():
    import re

    from rafiki_tpu.obs import prom

    telemetry.reset()
    try:
        telemetry.inc("obs.test_counter", 2)
        with telemetry.span("obs.prom_span"):
            pass
        text = prom.to_prometheus(telemetry.snapshot())
        assert text == prom.to_prometheus(telemetry.snapshot())
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+-]+$')
        for line in text.splitlines():
            assert line.startswith("# TYPE ") or sample.match(line), line
        assert "rafiki_obs_test_counter 2" in text
    finally:
        telemetry.reset()


# -- one trace across three processes ---------------------------------------

# Prometheus text exposition: comments, or `name[{labels}] value`.
_PROM_COMMENT = re.compile(r"^# (TYPE|HELP) ")
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+-]+(\s+[0-9]+)?$')


def _wait_until(cond, timeout_s, what, every_s=0.05):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(every_s)


def test_pinned_trace_stitches_across_three_processes(tmp_path, monkeypatch,
                                                      capsys):
    """Train one tiny trial, serve it from TWO spawned inference worker
    processes over the mp bus (one journal file a process under a
    shared RAFIKI_LOG_DIR), send one query with a pinned
    ``X-Rafiki-Trace-Id`` through the gateway's WSGI app: the reader
    (``obs trace <id>``) must stitch records of >= 3 distinct processes
    with a bus hop among them, and ``/metrics?format=prom`` must
    line-parse."""
    import multiprocessing as mp
    import uuid

    import numpy as np
    from werkzeug.test import Client

    from rafiki_tpu.bus import make_mp_bus
    from rafiki_tpu.chaos.scenarios import VAL, _make_job, _train_env
    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.model.dataset import dataset_utils
    from rafiki_tpu.obs import cli
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.predictor.app import PredictorApp
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.worker.inference import run_inference_worker_process

    log_dir = tmp_path / "obs"
    # The spawn env is the propagation channel: children inherit
    # RAFIKI_LOG_DIR and open their own journal files under it.
    monkeypatch.setenv("RAFIKI_LOG_DIR", str(log_dir))
    journal.configure(log_dir, role="gateway")
    store, params, model = _train_env(tmp_path)
    job = _make_job(store, model, {"MODEL_TRIAL_COUNT": 1})
    best = LocalScheduler(store, params).run_train_job(
        job["id"], n_workers=1, advisor_kind="random").best_trials[0]
    meta, blobs = str(tmp_path / "meta.sqlite3"), str(tmp_path / "params")

    ctx = mp.get_context("spawn")
    manager = ctx.Manager()
    bus = make_mp_bus(manager)
    procs = [ctx.Process(target=run_inference_worker_process,
                         args=(bus, meta, blobs, best["id"], "obs-job",
                               f"ow-{i}"), daemon=True) for i in range(2)]
    try:
        for p in procs:
            p.start()

        def registered():
            assert all(p.is_alive() for p in procs), "a worker died"
            return len(bus.get_workers("obs-job")) == len(procs)

        _wait_until(registered, 120, "both workers to register")
        predictor = Predictor(bus, "obs-job", timeout_s=10.0, worker_ttl_s=3.0)
        wsgi = Client(PredictorApp(Gateway(predictor,
                                           GatewayConfig(min_replies=2))))
        payload = {"queries": np.asarray(
            dataset_utils.load(VAL).x[:1], np.float32).tolist()}

        def warm():
            r = wsgi.post("/predict", json=payload)
            preds = (r.get_json() or {}).get("predictions") or []
            return r.status_code == 200 and preds and not any(
                isinstance(p, dict) and "error" in p for p in preds)

        _wait_until(warm, 120, "both workers to answer", every_s=0.5)

        # THE traced query: pin the id, like a caller would.
        tid = uuid.uuid4().hex
        r = wsgi.post("/predict", json=payload,
                      headers={"X-Rafiki-Trace-Id": tid})
        assert r.status_code == 200
        assert r.get_json()["trace_id"] == tid
        capsys.readouterr()

        records = []

        def stitched():
            # Worker journal writes are line-buffered, but the
            # pop -> journal hop may trail the reply by a beat.
            assert cli.main(["--dir", str(log_dir), "--json", "trace",
                             tid]) == 0
            records[:] = [json.loads(line) for line in
                          capsys.readouterr().out.splitlines() if line]
            return len({(rec.get("role"), rec.get("pid"))
                        for rec in records}) >= 3

        _wait_until(stitched, 20, "three processes in the trace",
                    every_s=0.25)
        assert {rec["trace_id"] for rec in records} == {tid}
        assert any(rec.get("kind") == "bus" for rec in records)

        pr = wsgi.get("/metrics?format=prom")
        assert pr.status_code == 200
        lines = pr.get_data(as_text=True).splitlines()
        bad = [ln for ln in lines if ln and not _PROM_COMMENT.match(ln)
               and not _PROM_SAMPLE.match(ln)]
        assert bad == []
        assert any(ln.startswith("rafiki_predictor_queries") for ln in lines)
    finally:
        for p in procs:
            if p.is_alive():
                p.kill()
        for p in procs:
            p.join(timeout=10)
        manager.shutdown()
        journal.close()
