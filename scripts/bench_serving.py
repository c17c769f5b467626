#!/usr/bin/env python
"""Closed-loop serving load generator for the predict path (schema v2).

Three modes:

  * ``--url http://host:port`` — drive a LIVE predictor endpoint
    (``predictor_host`` from the inference-job row) with N closed-loop
    clients for a fixed request count, measuring end-to-end latency
    through the serving gateway.
  * ``--smoke`` (default when no --url) — fully in-process and
    deterministic: stub-model workers on the in-proc bus behind a real
    Gateway + PredictorApp WSGI stack, exercised through the werkzeug
    test client. No sockets, no sleeps beyond the stub service time —
    the tier-1 wiring in scripts/check_tier1.sh runs this variant.
  * ``--smoke --mp`` — same stack, but the stub workers are REAL
    spawned processes on the multiprocess bus, so the hop waterfall
    crosses >=3 pids (scripts/serving_obs_smoke.py drives this).

``--tenants`` runs a skewed two-tenant closed loop (a gold tenant vs a
``--skew``x batch aggressor) against a tenant-aware gateway and emits
per-tenant p50/p99/shed plus the TENANT_r*.json headline keys
(docs/multitenancy.md).

``--route`` picks the serving shape (docs/serving.md): ``replicated``
(default) is the k-replica fan-out — one stub worker per trial, every
request fanned to all of them; ``stacked`` is the collapsed route —
ONE worker holds the whole ensemble, the gateway microbatches into it
(``--max-batch``, default 8 on this route); ``both`` runs the two
back to back with a telemetry reset in between and emits a combined
artifact: the stacked headline at top level (that is the route the PR
ships) plus a ``routes`` block carrying each per-route report, so one
SERVING_r*.json shows the before/after of the fan-out collapse.

Output: one JSON object on stdout (``schema_version: 2``):

  {"schema_version": 2, "qps": ..., "p50_ms": ..., "p99_ms": ...,
   "shed_rate": ..., "requests": ..., "ok": ..., "shed": ...,
   "errors": ..., "hops": {"forward": {"count": ..., "p50_ms": ...,
   "p99_ms": ...}, ...}, "ensemble_fanout_cost_ms": ...}

The ``hops`` block is the per-segment anatomy from the request-anatomy
plane (docs/serving_anatomy.md) and ``ensemble_fanout_cost_ms`` is the
chain total minus the slowest device forward — the overhead the
k-replica fan-out adds on top of the model, i.e. the number the
vmapped-ensemble bet must shrink. ``--pin-trace ID`` sends one extra
traced request after the load so a known trace id has a full
waterfall (``obs waterfall ID``).

Closed-loop means each client fires its next request only after the
previous one answered (or was shed) — offered load adapts to service
rate, the standard arrangement for latency benchmarking. Shed (429)
responses count toward shed_rate, not latency percentiles.

Exit code: 0 on a sane run; 1 when the run itself misbehaved (5xx
responses, zero completed requests) — that makes the smoke variant a
CI gate, not just a number printer.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA_VERSION = 2


def percentile(sorted_xs, p):
    if not sorted_xs:
        return None
    last = len(sorted_xs) - 1
    return sorted_xs[min(last, int(last * p / 100))]


class _StubModel:
    """Fixed service time, fixed output — no jax, no compile. Module
    level so multiprocessing spawn targets can pickle it."""

    def __init__(self, service_ms):
        self.service_ms = service_ms

    def predict(self, queries):
        time.sleep(self.service_ms / 1000.0)
        return [[0.6, 0.4] for _ in queries]


def _mp_stub_worker(bus, worker_id, service_ms):
    """Spawn target: one stub inference worker as its OWN process, the
    same dance run_inference_worker_process does (platform pin first,
    then the obs plane) minus the model store."""
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    from rafiki_tpu import obs

    obs.configure_from_env(role="infer")
    from rafiki_tpu.worker.inference import InferenceWorker

    InferenceWorker(bus, "bench", worker_id,
                    _StubModel(service_ms)).run()


class ClosedLoopClient:
    """One closed-loop worker: POST, record, repeat."""

    def __init__(self, post, n_requests, payload, record):
        self._post = post          # (payload) -> status_code
        self._n = n_requests
        self._payload = payload
        self._record = record

    def run(self):
        for _ in range(self._n):
            t0 = time.monotonic()
            try:
                status = self._post(self._payload)
            except Exception:
                status = -1
            # lint: disable=RF007 — the delta IS the datum: the client-observed request latency this bench reports
            self._record(status, time.monotonic() - t0)


class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.latencies_s = []
        self.ok = 0
        self.shed = 0
        self.errors = 0

    def record(self, status, latency_s):
        with self._lock:
            if status == 200:
                self.ok += 1
                self.latencies_s.append(latency_s)
            elif status == 429:
                self.shed += 1
            else:
                self.errors += 1

    def report(self, elapsed_s):
        with self._lock:
            xs = sorted(self.latencies_s)
            total = self.ok + self.shed + self.errors
            return {
                "requests": total,
                "ok": self.ok,
                "shed": self.shed,
                "errors": self.errors,
                "qps": round(total / elapsed_s, 2) if elapsed_s else None,
                "p50_ms": (None if not xs
                           else round(percentile(xs, 50) * 1000, 3)),
                "p99_ms": (None if not xs
                           else round(percentile(xs, 99) * 1000, 3)),
                "shed_rate": round(self.shed / total, 4) if total else None,
            }


def run_load(post, n_clients, requests_per_client, payload):
    recorder = Recorder()
    clients = [ClosedLoopClient(post, requests_per_client, payload,
                                recorder.record)
               for _ in range(n_clients)]
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # lint: disable=RF007 — the delta IS the datum: total load-generation wall used as the qps denominator
    return recorder.report(time.monotonic() - t0)


def _hops_block():
    """The per-segment anatomy block from this process's telemetry
    registry (the predictor absorbs chains in-process, so the
    histograms live here)."""
    from rafiki_tpu import telemetry
    from rafiki_tpu.obs.anatomy import hops as _hops

    hists = telemetry.snapshot().get("histograms", {})
    prefix = "serving.hop."
    hops = {}
    for name in sorted(hists):
        if not name.startswith(prefix):
            continue
        h = hists[name]
        seg = name[len(prefix):-2]  # strip prefix and the "_s" unit
        hops[seg] = {"count": h.get("count"),
                     "p50_ms": (None if h.get("p50") is None
                                else round(h["p50"] * 1000, 3)),
                     "p99_ms": (None if h.get("p99") is None
                                else round(h["p99"] * 1000, 3))}
    fan = hists.get(_hops.FANOUT_METRIC)
    fanout_ms = (None if not fan or fan.get("p50") is None
                 else round(fan["p50"] * 1000, 3))
    return hops or None, fanout_ms


def run_url_mode(args):
    import requests

    url = args.url.rstrip("/") + "/predict"
    session = requests.Session()

    def post(payload):
        resp = session.post(url, json=payload, timeout=args.deadline_s + 5)
        return resp.status_code

    payload = {"queries": [[1.0]] * args.queries_per_request,
               "deadline_s": args.deadline_s}
    return run_load(post, args.clients, args.requests_per_client, payload)


def run_smoke_mode(args, route="replicated"):
    from werkzeug.test import Client

    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.predictor.app import PredictorApp
    from rafiki_tpu.worker.inference import InferenceWorker

    # The stacked route collapses the fan-out: ONE worker stands in for
    # the whole top-k ensemble (the stub's fixed service time is paid
    # once per forward either way — exactly the vmap bet), quorum is 1,
    # and the gateway microbatches into it.
    stacked = route == "stacked"
    n_workers = 1 if stacked else args.workers
    wprefix = "sbw" if stacked else "bw"
    max_batch = (args.max_batch if args.max_batch is not None
                 else (8 if stacked else 1))
    min_replies = 1 if stacked else args.min_replies

    stop = threading.Event()
    threads = []
    procs = []
    manager = None
    if args.mp:
        import multiprocessing as mp

        from rafiki_tpu.bus.queues import make_mp_bus

        ctx = mp.get_context("spawn")
        manager = ctx.Manager()
        bus = make_mp_bus(manager)
        for i in range(n_workers):
            pr = ctx.Process(target=_mp_stub_worker,
                             args=(bus, f"{wprefix}{i}", args.service_ms),
                             daemon=True)
            procs.append(pr)
            pr.start()
    else:
        from rafiki_tpu.bus import InProcBus

        bus = InProcBus()
        for i in range(n_workers):
            w = InferenceWorker(bus, "bench", f"{wprefix}{i}",
                                _StubModel(args.service_ms), stop_event=stop)
            th = threading.Thread(target=w.run, daemon=True)
            threads.append(th)
            th.start()
    deadline = time.monotonic() + (30 if args.mp else 10)
    while len(bus.get_workers("bench")) < n_workers:
        if time.monotonic() > deadline:
            raise RuntimeError("bench workers never registered")
        time.sleep(0.005)

    predictor = Predictor(bus, "bench", timeout_s=args.deadline_s)
    gateway = Gateway(predictor, GatewayConfig(
        max_inflight=args.max_inflight, max_queue=args.max_queue,
        min_replies=min_replies, hedge_grace_s=0.02,
        max_batch=max_batch, max_batch_wait_ms=args.max_batch_wait_ms))
    wsgi = Client(PredictorApp(gateway))

    def post(payload):
        return wsgi.post("/predict", json=payload).status_code

    payload = {"queries": [[1.0]] * args.queries_per_request,
               "deadline_s": args.deadline_s}
    try:
        report = run_load(post, args.clients, args.requests_per_client,
                          payload)
        if args.pin_trace:
            # One traced request AFTER the load: a known trace id with
            # a full waterfall for `obs waterfall <id>` (retried — the
            # pinned trace is the smoke's evidence, not a sample).
            status = None
            for _ in range(20):
                status = wsgi.post(
                    "/predict", json=payload,
                    headers={"X-Rafiki-Trace-Id": args.pin_trace},
                ).status_code
                if status == 200:
                    break
                time.sleep(0.05)
            report["pinned_trace"] = args.pin_trace
            report["pinned_status"] = status
        # Short runs would otherwise journal nothing: force the
        # time-series bucket and the exemplar window closed.
        gateway.rollup.flush()
        from rafiki_tpu.obs.anatomy import exemplars

        exemplars.ring.flush()
        return report
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=2)
        for pr in procs:
            pr.terminate()
            pr.join(timeout=5)
        if manager is not None:
            manager.shutdown()


def run_tenants_mode(args):
    """Skewed two-tenant closed loop against a tenant-aware gateway.

    A gold tenant at 1x clients and a batch tenant at ``--skew``x
    clients share one gateway built over a TenantFabric — weighted
    admission, per-tenant quotas, per-tenant accounting. The artifact
    carries a per-tenant latency/shed report plus flat headline keys
    (``gold_p99_ms``, ``gold_shed_rate``, ``batch_qps``) for the
    TENANT_r*.json trend gate in bench_report --tenants: the number
    that must not regress is the PROTECTED tenant's tail while the
    aggressor keeps making proportional progress.
    """
    from werkzeug.test import Client

    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.predictor import Predictor
    from rafiki_tpu.predictor.app import PredictorApp
    from rafiki_tpu.tenancy import TenantDirectory, TenantFabric
    from rafiki_tpu.worker.inference import InferenceWorker

    GOLD, BATCH = "gold_t", "batch_t"
    stop = threading.Event()
    bus = InProcBus()
    threads = []
    for i in range(args.workers):
        w = InferenceWorker(bus, "bench", f"tw{i}",
                            _StubModel(args.service_ms), stop_event=stop)
        th = threading.Thread(target=w.run, daemon=True)
        threads.append(th)
        th.start()
    deadline = time.monotonic() + 10
    while len(bus.get_workers("bench")) < args.workers:
        if time.monotonic() > deadline:
            raise RuntimeError("bench workers never registered")
        time.sleep(0.005)

    fabric = TenantFabric(TenantDirectory(
        tiers={GOLD: "gold", BATCH: "batch"}))
    predictor = Predictor(bus, "bench", timeout_s=args.deadline_s)
    gateway = Gateway(predictor, GatewayConfig(
        max_inflight=args.max_inflight, max_queue=args.max_queue,
        min_replies=1, hedge_grace_s=0.02), tenancy=fabric)
    wsgi = Client(PredictorApp(gateway))
    payload = {"queries": [[1.0]] * args.queries_per_request,
               "deadline_s": args.deadline_s}

    recorders = {GOLD: Recorder(), BATCH: Recorder()}

    def _post_as(tenant):
        def post(p):
            return wsgi.post("/predict", json=p,
                             headers={"X-Rafiki-Tenant": tenant}
                             ).status_code
        return post

    clients = (
        [ClosedLoopClient(_post_as(GOLD), args.requests_per_client,
                          payload, recorders[GOLD].record)
         for _ in range(args.clients)]
        + [ClosedLoopClient(_post_as(BATCH), args.requests_per_client,
                            payload, recorders[BATCH].record)
           for _ in range(args.clients * args.skew)])
    pool = [threading.Thread(target=c.run, daemon=True) for c in clients]
    t0 = time.monotonic()
    try:
        for th in pool:
            th.start()
        for th in pool:
            th.join()
        # lint: disable=RF007 — the delta IS the datum: load wall-clock, the per-tenant qps denominator
        elapsed = time.monotonic() - t0
        gateway.drain(timeout=5.0)  # flushes the tenant/summary journal
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=2)

    tiers = {GOLD: "gold", BATCH: "batch"}
    tenants = {t: dict(recorders[t].report(elapsed), tier=tiers[t])
               for t in (GOLD, BATCH)}
    total = sum(tenants[t]["requests"] for t in tenants)
    report = {
        "mode": "smoke-tenants",
        "skew": args.skew,
        "tenants": tenants,
        "requests": total,
        "ok": sum(tenants[t]["ok"] for t in tenants),
        "shed": sum(tenants[t]["shed"] for t in tenants),
        "errors": sum(tenants[t]["errors"] for t in tenants),
        "qps": round(total / elapsed, 2) if elapsed else None,
        # Flat headline keys for the TENANT_r*.json polarity gate.
        "gold_p50_ms": tenants[GOLD]["p50_ms"],
        "gold_p99_ms": tenants[GOLD]["p99_ms"],
        "gold_shed_rate": tenants[GOLD]["shed_rate"],
        "batch_p99_ms": tenants[BATCH]["p99_ms"],
        "batch_qps": tenants[BATCH]["qps"],
    }
    return report


def main(argv=None):
    # Platform pin FIRST: this process may import jax transitively via
    # the worker/model stack, and an explicit CPU request is applied
    # before the first backend use. Neither this process nor its --mp
    # stub workers initialise a backend, so no chip is held here.
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", help="live predictor base URL; omit for the "
                                  "in-process smoke run")
    ap.add_argument("--smoke", action="store_true",
                    help="force the in-process deterministic run")
    ap.add_argument("--mp", action="store_true",
                    help="smoke mode with REAL spawned worker processes "
                         "on the mp bus (cross-process waterfalls)")
    ap.add_argument("--route", choices=("replicated", "stacked", "both"),
                    default="replicated",
                    help="serving shape: k-replica fan-out, collapsed "
                         "stacked worker + gateway microbatching, or "
                         "both back to back (combined artifact)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="gateway microbatch size (default: 1 on the "
                         "replicated route, 8 on the stacked route)")
    ap.add_argument("--max-batch-wait-ms", type=float, default=5.0,
                    help="gateway microbatch deadline-bounded wait")
    ap.add_argument("--pin-trace", default=None,
                    help="send one extra request under this trace id "
                         "after the load (obs waterfall target)")
    ap.add_argument("--tenants", action="store_true",
                    help="skewed two-tenant run against a tenant-aware "
                         "gateway: per-tenant p50/p99/shed plus the "
                         "TENANT_r*.json headline keys "
                         "(docs/multitenancy.md)")
    ap.add_argument("--skew", type=int, default=3,
                    help="batch-tenant client multiple in --tenants "
                         "mode (gold gets --clients, batch gets "
                         "--clients * skew)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests-per-client", type=int, default=25)
    ap.add_argument("--queries-per-request", type=int, default=4)
    ap.add_argument("--deadline-s", type=float, default=2.0)
    ap.add_argument("--workers", type=int, default=2,
                    help="stub inference workers (smoke mode)")
    ap.add_argument("--service-ms", type=float, default=1.0,
                    help="stub model service time (smoke mode)")
    ap.add_argument("--max-inflight", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=8)
    ap.add_argument("--min-replies", type=int, default=None,
                    help="gather quorum override (default ceil(k/2))")
    args = ap.parse_args(argv)

    # Journal under RAFIKI_LOG_DIR when set: the serving/ts, serving/
    # hops and slo records are this bench's durable side channel.
    from rafiki_tpu import obs

    obs.configure_from_env(role="gateway")

    def _run_route(route):
        rep = run_smoke_mode(args, route=route)
        rep["mode"] = "smoke-mp" if args.mp else "smoke"
        rep["route"] = route
        hops, fanout_ms = _hops_block()
        rep["hops"] = hops
        rep["ensemble_fanout_cost_ms"] = fanout_ms
        return rep

    if args.tenants:
        report = run_tenants_mode(args)
        unhealthy = [report]
    elif args.url and not args.smoke:
        report = run_url_mode(args)
        report["mode"] = "url"
        hops, fanout_ms = _hops_block()
        report["hops"] = hops
        report["ensemble_fanout_cost_ms"] = fanout_ms
        unhealthy = [report]
    elif args.route == "both":
        from rafiki_tpu import telemetry

        replicated = _run_route("replicated")
        telemetry.reset()  # per-route hops/fanout, not a blended view
        stacked = _run_route("stacked")
        # Stacked headline at top level (the route the PR ships), the
        # per-route before/after under ``routes`` for the trend gate.
        report = dict(stacked)
        report["route"] = "both"
        report["routes"] = {"replicated": replicated, "stacked": stacked}
        unhealthy = [replicated, stacked]
    else:
        report = _run_route(args.route)
        unhealthy = [report]

    report["schema_version"] = SCHEMA_VERSION

    print(json.dumps(report, indent=2))

    bad = [r for r in unhealthy if r["errors"] or not r["ok"]]
    if bad:
        for r in bad:
            print(f"bench_serving: unhealthy {r.get('route', 'url')} run "
                  f"({r['errors']} errors, {r['ok']} ok)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
