"""Request-anatomy plane (docs/serving_anatomy.md): hop-mark envelope
back-compat, segment math and hop-sum reconciliation, the exemplar
ring's bounds, the serving rollup's determinism, and waterfall
stitching across processes via the real CLI readers — first over
hand-written journals, then (the file's last tests) over what REAL
spawned stub workers on the multiprocess bus wrote under closed-loop
load: clean, through the stacked route's microbatch, and with an
injected forward delay that must be both localised and alarmed."""

import contextlib
import json
import threading
import time

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.bus import InProcBus
from rafiki_tpu.obs import context as trace_context
from rafiki_tpu.obs import journal as journal_mod
from rafiki_tpu.obs.anatomy import hops
from rafiki_tpu.obs.anatomy.exemplars import ExemplarRing
from rafiki_tpu.obs.anatomy.timeseries import ServingRollup
from rafiki_tpu.obs.journal import journal


@pytest.fixture
def journaled(tmp_path):
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- envelope back-compat ----------------------------------------------------


def test_untraced_messages_keep_bare_tuple_shapes():
    bus = InProcBus()
    bus.add_worker("job", "w0")
    bus.add_query("w0", "q1", [1.0])
    (item,) = bus.pop_queries("w0", max_n=4, timeout=0.5)
    assert item == ("q1", [1.0])  # no trace -> no third element
    bus.put_prediction("q1", "w0", [0.5])
    (reply,) = bus.get_predictions("q1", n=1, timeout=0.5)
    assert reply == ("w0", [0.5])


def test_traced_envelope_carries_gateway_prefix_plus_enq_mark():
    bus = InProcBus()
    bus.add_worker("job", "w0")
    hops.begin()
    hops.add("admit")
    hops.add("queue")
    try:
        with trace_context.trace("t-anatomy-1"):
            bus.add_query("w0", "q1", [1.0])
    finally:
        hops.clear()
    (item,) = bus.pop_queries("w0", max_n=4, timeout=0.5)
    assert item[0] == "q1" and len(item) == 3
    marks = item[2]["hops"]
    assert [m[0] for m in marks] == ["admit", "queue", "enq"]
    # [code, monotonic ts, pid]: timestamps ordered, pid stamped
    assert marks[0][1] <= marks[-1][1]
    assert all(isinstance(m[2], int) for m in marks)
    # clear() closed the prefix: the next add is a no-op
    assert hops.add("admit") is None and hops.prefix_marks() == []


def test_explicit_trace_dict_is_not_mutated_by_envelope():
    bus = InProcBus()
    bus.add_worker("job", "w0")
    shared = {"trace_id": "t-shared"}
    bus.add_query("w0", "q1", [1.0], trace=shared)
    assert "hops" not in shared  # caller-owned dict copied, not annotated
    (item,) = bus.pop_queries("w0", max_n=4, timeout=0.5)
    assert item[2]["trace_id"] == "t-shared"
    assert [m[0] for m in item[2]["hops"]] == ["enq"]


def test_reply_hops_ride_as_optional_third_element():
    bus = InProcBus()
    bus.add_worker("job", "w0")
    bus.add_worker("job", "w1")
    chain = [hops.mark("enq"), hops.mark("deq"), hops.mark("reply")]
    bus.put_prediction("q1", "w0", [0.5], hops=chain)
    bus.put_prediction("q1", "w1", [0.4])
    replies = sorted(bus.get_predictions("q1", n=2, timeout=0.5),
                     key=lambda item: item[0])
    # Mixed shapes gather together: consumers index, never destructure.
    assert [len(item) for item in replies] == [3, 2]
    assert replies[0][2] is chain


# -- segment math + reconciliation -------------------------------------------


def _chain(pid, *steps):
    """Build a mark chain from (code, ts) steps with a fixed pid."""
    return [[code, float(ts), pid] for code, ts in steps]


FULL = (("admit", 0.0), ("queue", 0.010), ("enq", 0.012), ("deq", 0.020),
        ("fwds", 0.021), ("fwd", 0.071), ("reply", 0.072), ("dec", 0.080))


def test_segments_name_every_gap_and_sum_to_chain_total():
    marks = _chain(42, *FULL)
    segs = hops.segments(marks)
    assert [s for s, _ in segs] == ["admission_wait", "route", "bus_queue",
                                    "batch_wait", "forward", "reply_publish",
                                    "gather_decide"]
    assert sum(d for _, d in segs) == pytest.approx(
        hops.chain_total_s(marks), abs=1e-9)


def test_unknown_mark_breaks_reconciliation_loudly():
    # A foreign mark advances the clock but names no segment: the
    # hop-sum must fall SHORT of the end-to-end span, never silently
    # absorb the gap into a neighbor.
    marks = _chain(42, ("enq", 0.0), ("mystery", 0.5), ("dec", 0.6))
    segs = hops.segments(marks)
    assert [s for s, _ in segs] == ["gather_decide"]
    assert sum(d for _, d in segs) == pytest.approx(0.1, abs=1e-9)
    assert hops.chain_total_s(marks) == pytest.approx(0.6, abs=1e-9)


def test_absorb_feeds_hop_histograms_and_fanout_cost(journaled):
    fast = _chain(7, *FULL)
    slow = _chain(8, ("enq", 0.012), ("deq", 0.020), ("fwds", 0.021),
                  ("fwdc", 0.171), ("reply", 0.172), ("dec", 0.180))
    total = hops.absorb("q-abs", {"w0": fast, "w1": slow})
    assert total == pytest.approx(0.180 - 0.012)
    hists = telemetry.snapshot()["histograms"]
    assert hists["serving.hop.forward_s"]["count"] == 1
    assert hists["serving.hop.forward_cold_s"]["count"] == 1
    assert hists["serving.hop.bus_queue_s"]["count"] == 2
    # fan-out cost = slowest chain total minus slowest device forward
    fan = hists[hops.FANOUT_METRIC]
    assert fan["count"] == 1
    assert fan["p50"] == pytest.approx((0.180 - 0.012) - 0.150, abs=1e-6)
    recs = [r for r in journal_mod.read_dir(journaled)
            if r["kind"] == "serving" and r["name"] == "hops"]
    assert len(recs) == 1 and recs[0]["query_id"] == "q-abs"
    assert set(recs[0]["chains"]) == {"w0", "w1"}


# -- exemplar ring ------------------------------------------------------------


def test_exemplar_ring_keeps_slowest_n_and_rolls_windows(journaled):
    clock = _Clock()
    ring = ExemplarRing(cap=3, window_s=10.0, clock=clock)
    for i, total in enumerate([0.05, 0.9, 0.1, 0.7, 0.3]):
        ring.offer(total, {"query_id": f"q{i}", "chains": {},
                           "trace_id": f"t{i}"})
    col = ring.collector()
    assert col["retained"] == 3 and col["offered"] == 5
    assert col["slowest_s"] == pytest.approx(0.9)
    # All-numeric leaves: the prom flattener must keep every field.
    assert all(isinstance(v, (int, float)) for v in col.values())

    # Window roll: the NEXT offer past window_s journals the retained
    # slowest-first, with the trace id captured at OFFER time.
    clock.t = 11.0
    ring.offer(0.2, {"query_id": "q5", "chains": {}, "trace_id": "t5"})
    recs = [r for r in journal_mod.read_dir(journaled)
            if r["kind"] == "serving" and r["name"] == "exemplar"]
    assert [r["query_id"] for r in recs] == ["q1", "q3", "q4"]
    assert [r["rank"] for r in recs] == [0, 1, 2]
    assert [r["trace_id"] for r in recs] == ["t1", "t3", "t4"]
    assert ring.collector()["retained"] == 1  # the new window's offer
    assert ring.flush() == 1
    assert ring.collector()["windows_flushed"] == 2


# -- serving rollup -----------------------------------------------------------


def test_rollup_rows_are_deterministic_under_a_fake_clock(journaled):
    clock = _Clock(100.2)
    ctx = {"queue_depth": 3, "inflight": 2}
    rollup = ServingRollup(bucket_s=1.0, clock=clock, context_fn=lambda: ctx)
    for lat in (0.010, 0.020, 0.030, 0.250):
        rollup.observe(latency_s=lat)
    rollup.observe(outcome="shed")
    rollup.observe(outcome="error")
    clock.t = 101.2  # next bucket: first observe there closes the last
    rollup.observe(latency_s=0.005)
    rollup.flush()
    rows = [r for r in journal_mod.read_dir(journaled)
            if r["kind"] == "serving" and r["name"] == "ts"]
    assert len(rows) == 2
    first = rows[0]
    assert (first["bucket"], first["requests"], first["ok"], first["shed"],
            first["errors"]) == (100, 6, 4, 1, 1)
    assert first["qps"] == pytest.approx(6.0)
    # nearest-rank on [10, 20, 30, 250]ms: round(0.5 * 3) = idx 2
    assert first["p50_ms"] == pytest.approx(30.0)
    assert first["p99_ms"] == pytest.approx(250.0)
    assert first["shed_rate"] == pytest.approx(1 / 6, abs=1e-4)
    assert first["queue_depth"] == 3 and first["inflight"] == 2
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["serving.qps"] == pytest.approx(1.0)  # the flushed bucket
    col = rollup.collector()
    assert col["buckets_flushed"] == 2
    assert col["last"]["requests"] == 1


def test_rollup_empty_bucket_journals_nothing(journaled):
    rollup = ServingRollup(bucket_s=1.0, clock=_Clock())
    assert rollup.flush() is None
    assert [r for r in journal_mod.read_dir(journaled)
            if r["kind"] == "serving"] == []


# -- waterfall stitching across processes (the CLI readers) -------------------


def _write_journal(tmp_path, name, records):
    with open(tmp_path / name, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_waterfall_stitches_three_pids_and_reconciles(tmp_path, capsys):
    from rafiki_tpu.obs import cli

    # Hand-written journals from three processes: the gateway journaled
    # the hops record (absorb runs in the gateway/predictor process)
    # with chains whose marks were stamped by gateway pid 100 and the
    # two worker pids 101/102.
    chain_a = (_chain(100, ("admit", 0.0), ("queue", 0.010), ("enq", 0.012))
               + _chain(101, ("deq", 0.020), ("fwds", 0.021), ("fwd", 0.071),
                        ("reply", 0.072))
               + _chain(100, ("dec", 0.080)))
    chain_b = (_chain(100, ("admit", 0.0), ("queue", 0.010), ("enq", 0.012))
               + _chain(102, ("deq", 0.025), ("fwds", 0.026), ("fwd", 0.076),
                        ("reply", 0.077))
               + _chain(100, ("dec", 0.080)))
    _write_journal(tmp_path, "journal-gateway-100.jsonl", [
        {"ts": 1.0, "pid": 100, "kind": "serving", "name": "hops",
         "trace_id": "feedface01", "query_id": "q-wf",
         "chains": {"w0": chain_a, "w1": chain_b}, "total_s": 0.08},
        {"ts": 1.1, "pid": 100, "kind": "serving", "name": "request",
         "trace_id": "feedface01", "queries": 1, "e2e_s": 0.081, "ok": True},
    ])
    _write_journal(tmp_path, "journal-infer-101.jsonl", [
        {"ts": 0.9, "pid": 101, "kind": "bus", "name": "pop_query",
         "trace_id": "feedface01", "query_id": "q-wf"},
    ])

    assert cli.cmd_waterfall(str(tmp_path), "feedface", as_json=True) == 0
    doc = json.loads(capsys.readouterr().out)
    (q,) = doc["queries"]
    assert q["n_hops"] == 8
    assert q["pids"] == [100, 101, 102]
    assert q["max_reconcile_err"] <= 1e-9
    assert doc["e2e_s"] == pytest.approx(0.081)

    # Tail attribution over the same records reconciles fleet-wide.
    assert cli.cmd_tails(str(tmp_path), as_json=True, check=True,
                         tolerance=0.10) == 0
    tails = json.loads(capsys.readouterr().out)
    assert tails["reconcile"]["ok"] is True
    assert {s["segment"] for s in tails["segments"]} >= {"forward",
                                                         "bus_queue"}


def test_waterfall_unknown_trace_exits_nonzero(tmp_path, capsys):
    from rafiki_tpu.obs import cli

    assert cli.cmd_waterfall(str(tmp_path), "nope", as_json=True) == 1
    assert "no serving hop records" in capsys.readouterr().err


# -- prom exposition ----------------------------------------------------------


def test_hop_histograms_flatten_into_prom_exposition(journaled):
    from rafiki_tpu.obs import prom

    hops.absorb("q-prom", {"w0": _chain(7, *FULL)})
    text = prom.to_prometheus(telemetry.snapshot())
    assert 'rafiki_serving_hop_forward_s{quantile="0.99"}' in text
    assert "rafiki_serving_hop_forward_s_count 1" in text
    assert "rafiki_serving_hop_admission_wait_s_count 1" in text


# -- live: spawned workers on the mp bus -------------------------------------


class _StubModel:
    """Fixed service time, fixed output — no jax, no compile. Module
    level so a spawned process can unpickle it."""

    def __init__(self, service_ms):
        self.service_ms = service_ms

    def predict(self, queries):
        time.sleep(self.service_ms / 1000.0)
        return [[0.6, 0.4] for _ in queries]


def _stub_worker_process(bus, worker_id, service_ms):
    """Spawn target: one stub inference worker as its OWN process, the
    dance run_inference_worker_process does (platform pin first, then
    the obs plane) minus the model store."""
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu import obs

    obs.configure_from_env(role="infer")
    from rafiki_tpu.worker.inference import InferenceWorker

    InferenceWorker(bus, "anat", worker_id, _StubModel(service_ms)).run()


@contextlib.contextmanager
def _mp_serving_stack(log_dir, monkeypatch, n_workers, min_replies,
                      max_batch=1):
    """The real Gateway + PredictorApp over ``n_workers`` spawned stub
    workers; every process journals under ``log_dir`` (the spawn env is
    the propagation channel). Yields ``post(payload, trace_id=None)``."""
    import multiprocessing as mp

    from werkzeug.test import Client

    from rafiki_tpu.bus import make_mp_bus
    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.obs.anatomy import exemplars
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.predictor.app import PredictorApp

    monkeypatch.setenv("RAFIKI_LOG_DIR", str(log_dir))
    journal.configure(log_dir, role="gateway")
    ctx = mp.get_context("spawn")
    manager = ctx.Manager()
    bus = make_mp_bus(manager)
    procs = [ctx.Process(target=_stub_worker_process,
                         args=(bus, f"aw{i}", 1.0), daemon=True)
             for i in range(n_workers)]
    try:
        for p in procs:
            p.start()
        deadline = time.monotonic() + 60
        while len(bus.get_workers("anat")) < n_workers:
            assert all(p.is_alive() for p in procs), "a stub worker died"
            assert time.monotonic() < deadline, "workers never registered"
            time.sleep(0.01)
        gateway = Gateway(Predictor(bus, "anat", timeout_s=2.0),
                          GatewayConfig(max_inflight=4, max_queue=8,
                                        min_replies=min_replies,
                                        hedge_grace_s=0.02,
                                        max_batch=max_batch,
                                        max_batch_wait_ms=5.0))
        wsgi = Client(PredictorApp(gateway))

        def post(payload, trace_id=None):
            headers = {"X-Rafiki-Trace-Id": trace_id} if trace_id else None
            return wsgi.post("/predict", json=payload,
                             headers=headers).status_code

        yield post
        # A short run would otherwise journal nothing: close the
        # time-series bucket and the exemplar window.
        gateway.rollup.flush()
        exemplars.ring.flush()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.join(timeout=5)
        manager.shutdown()
        journal.close()


def _closed_loop(post, clients, per_client, queries_per_request):
    """Each client fires its next request only after the last answered."""
    payload = {"queries": [[1.0]] * queries_per_request, "deadline_s": 2.0}
    statuses = []

    def client():
        for _ in range(per_client):
            statuses.append(post(payload))

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return payload, statuses


def _pin(post, payload, trace_id):
    """One traced request AFTER the load: a known id with a full
    waterfall (retried — the pinned trace is the evidence, not a
    sample)."""
    for _ in range(20):
        if post(payload, trace_id) == 200:
            return
        time.sleep(0.05)
    raise AssertionError("the pinned request never answered 200")


def _waterfall(log_dir, trace_id, capsys):
    from rafiki_tpu.obs import cli

    capsys.readouterr()
    assert cli.cmd_waterfall(str(log_dir), trace_id, as_json=True) == 0
    queries = json.loads(capsys.readouterr().out)["queries"]
    assert queries
    return {"min_hops": min(q["n_hops"] for q in queries),
            "pids": {p for q in queries for p in q["pids"]},
            "max_reconcile_err": max(q["max_reconcile_err"]
                                     for q in queries),
            "segments": {s["segment"] for q in queries
                         for v in q.get("chains", {}).values()
                         for s in v.get("segments", [])}}


def _forward_breaches(log_dir):
    return [r for r in journal_mod.read_dir(log_dir)
            if r.get("kind") == "slo" and r.get("name") == "breach"
            and r.get("slo") == "serving_forward_p99"]


def test_live_waterfall_crosses_three_processes_and_reconciles(
        tmp_path, monkeypatch, capsys):
    """Replicated route, two spawned workers, quorum 2: the pinned trace
    reconstructs with >= 4 hops over >= 3 pids, every chain's hop sums
    reconciling with its span within 10% (``tails --check`` holds the
    same fleet-wide), the time series journaled rows, and the default
    1 s forward budget did NOT breach on millisecond forwards — the
    no-false-positive control of the injected test below."""
    from rafiki_tpu.obs import cli

    pin = "cafe0bet4p5"
    with _mp_serving_stack(tmp_path, monkeypatch, n_workers=2,
                           min_replies=2) as post:
        payload, statuses = _closed_loop(post, clients=4, per_client=12,
                                         queries_per_request=4)
        _pin(post, payload, pin)
    assert 200 in statuses and not [s for s in statuses
                                    if s not in (200, 429)]
    assert "serving.hop.forward_s" in telemetry.snapshot()["histograms"]
    w = _waterfall(tmp_path, pin, capsys)
    assert w["min_hops"] >= 4 and len(w["pids"]) >= 3
    assert w["max_reconcile_err"] <= 0.10
    assert cli.cmd_tails(str(tmp_path), as_json=True, check=True,
                         tolerance=0.10) == 0
    assert cli.cmd_serving(str(tmp_path), 20, as_json=True) == 0
    assert capsys.readouterr().out.strip()
    assert _forward_breaches(tmp_path) == []


def test_live_waterfall_stitches_across_the_microbatch(
        tmp_path, monkeypatch, capsys):
    """Stacked route: ONE spawned worker stands in for the whole top-k
    ensemble and the gateway microbatches into it. The pinned trace
    stitches ACROSS the microbatch (member prefix + shared batch leg +
    worker leg + decide): >= 5 hops, >= 2 pids, a named
    ``gateway_batch_wait`` segment, reconciling within 10%; and the
    collapsed route's fan-out cost stays under 15 ms, a fraction of what
    the replicated mp fan-out pays in wire tax alone."""
    from rafiki_tpu.obs.anatomy import hops as hops_mod

    pin = "cafe0bet4p5st"
    with _mp_serving_stack(tmp_path, monkeypatch, n_workers=1,
                           min_replies=1, max_batch=8) as post:
        payload, statuses = _closed_loop(post, clients=4, per_client=12,
                                         queries_per_request=4)
        _pin(post, payload, pin)
    assert 200 in statuses and not [s for s in statuses
                                    if s not in (200, 429)]
    hists = telemetry.snapshot()["histograms"]
    assert "serving.hop.gateway_batch_wait_s" in hists
    assert hists[hops_mod.FANOUT_METRIC]["p50"] < 0.015
    w = _waterfall(tmp_path, pin, capsys)
    assert w["min_hops"] >= 5 and len(w["pids"]) >= 2
    assert "gateway_batch_wait" in w["segments"]
    assert w["max_reconcile_err"] <= 0.10


def test_injected_forward_delay_is_localised_and_alarmed(
        tmp_path, monkeypatch, capsys):
    """The chaos plane delays ``inference.forward`` by 250 ms on ~20% of
    batches in both spawned workers, under a forward-p99 budget tightened
    to 150 ms ticking every 100 ms: ``obs tails`` must attribute the tail
    to the ``forward`` hop, and the journals must carry the
    ``slo/breach`` record. The load is shaped so attribution is crisp:
    one closed-loop client with one query a request makes every
    micro-batch a single query, so both replicas' chaos RNG streams
    (seeded, advanced once per hit) stay aligned and a delayed request
    delays BOTH replicas — the partner chain never mirrors the delay
    into its gather_decide wait, and p=0.2 keeps it out of the p50."""
    from rafiki_tpu.obs import cli
    from rafiki_tpu.obs.perf import slo

    monkeypatch.setenv("RAFIKI_CHAOS",
                       "seed=7;inference.forward:delay:delay=0.25:p=0.2")
    slo.configure([slo.SloSpec(
        name="serving_forward_p99", source="hist_p99:serving.hop.forward_s",
        threshold=0.15, windows=(0.4, 1.0))], tick_s=0.1)
    try:
        with _mp_serving_stack(tmp_path, monkeypatch, n_workers=2,
                               min_replies=2) as post:
            _payload, statuses = _closed_loop(post, clients=1, per_client=80,
                                              queries_per_request=1)
    finally:
        slo.configure_from_env()
    assert statuses.count(200) >= 60
    capsys.readouterr()
    assert cli.cmd_tails(str(tmp_path), as_json=True, check=False,
                         tolerance=0.10) == 0
    assert json.loads(capsys.readouterr().out)["dominant"].startswith(
        "forward")
    assert _forward_breaches(tmp_path)
