"""Multi-tenant serving fabric (docs/multitenancy.md): QoS directory,
weighted-fair admission with per-tenant quotas, bounded accounting,
HBM-budgeted program residency, co-hosted multi-model workers, the
twin's per-tenant validation, and the job-admission arbiter.

Most tests pin the unit semantics of one layer. The end-to-end ones
close the file: one worker co-hosting two models under a budget that
fits one, and the isolation proof (victim p99 inside its budget under
an aggressor flood, from per-tenant journals alone) — the
``noisy-neighbor-shed`` chaos scenario in BOTH polarities.
"""

import json
import threading
import time

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.obs import journal as journal_mod
from rafiki_tpu.obs.journal import journal
from rafiki_tpu.tenancy import (
    ANON_TENANT, BoundedTenantMap, ProgramHost, ProgramSpec,
    ResidencyManager, TenantAccounting, TenantAdmissionController,
    TenantDirectory, TIERS, wrap_query)
from rafiki_tpu.tenancy.arbiter import (
    JobAdmissionGate, JobRejected, ModelUnvalidated)


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


def _directory(**kw):
    kw.setdefault("tiers", {"alice": "gold", "bob": "batch"})
    return TenantDirectory(**kw)


# -- qos -------------------------------------------------------------------


def test_directory_resolves_tiers_and_defaults():
    d = _directory(default_tier="std")
    assert d.tier_of("alice").name == "gold"
    assert d.tier_of("bob").name == "batch"
    assert d.tier_of("stranger").name == "std"
    assert d.tier_of(None).name == "std"
    tiers = TIERS()
    assert tiers["gold"].weight > tiers["std"].weight > tiers["batch"].weight
    assert tiers["gold"].p99_budget_ms < tiers["batch"].p99_budget_ms


def test_unweighted_knob_flattens_weights(monkeypatch):
    monkeypatch.setenv("RAFIKI_TENANT_UNWEIGHTED", "1")
    tiers = TIERS()
    assert tiers["gold"].weight == tiers["batch"].weight == 1.0
    d = _directory()
    assert d.unweighted and d.quota_frac == 1.0


# -- admission -------------------------------------------------------------


def test_quota_shed_charged_to_the_flooder():
    """A tenant beyond its queue quota sheds with ``tenant_quota``
    while the other tenant still admits — the noisy-neighbor core."""
    from rafiki_tpu.gateway.admission import ShedError

    ctl = TenantAdmissionController(_directory(quota_frac=0.5),
                                    max_inflight=2, max_queue=4)
    deadline = time.monotonic() + 5.0
    # bob fills his inflight quota (1 of 2 slots) ...
    ctl.admit(deadline, tenant="bob")
    # ... then his queue quota (ceil(4*0.5) = 2 waiters).
    waits = []
    started = threading.Barrier(3)

    def waiter():
        started.wait()
        waits.append(ctl.admit(time.monotonic() + 5.0, tenant="bob"))

    ths = [threading.Thread(target=waiter, daemon=True) for _ in range(2)]
    for th in ths:
        th.start()
    started.wait()
    deadline2 = time.monotonic() + 2.0
    while ctl.tenant_waiting("bob") < 2:
        assert time.monotonic() < deadline2, "waiters never queued"
        time.sleep(0.005)
    with pytest.raises(ShedError) as ei:
        ctl.admit(time.monotonic() + 5.0, tenant="bob")
    assert ei.value.reason == "tenant_quota"
    # alice is untouched by bob's quota exhaustion: she rides the
    # shared queue straight through (her own quota is empty).
    ctl.admit(time.monotonic() + 5.0, tenant="alice")
    assert ctl.tenant_inflight("alice") == 1
    ctl.release(tenant="alice")
    # bob's inflight quota is ONE slot, so his waiters drain strictly
    # one release at a time.
    deadline3 = time.monotonic() + 5.0
    for want in (1, 2):
        ctl.release(tenant="bob")
        while len(waits) < want:
            assert time.monotonic() < deadline3, "waiter never admitted"
            time.sleep(0.005)
    ctl.release(tenant="bob")
    for th in ths:
        th.join(timeout=5.0)
    assert len(waits) == 2


def test_weighted_grant_prefers_lower_charge_per_weight():
    """With one slot freed and both tenants waiting at equal inflight,
    the gold tenant (weight 4) is chosen over batch (weight 1) —
    inflight/weight charge, not FIFO age, decides."""
    ctl = TenantAdmissionController(_directory(quota_frac=1.0),
                                    max_inflight=2, max_queue=8)
    ctl.admit(time.monotonic() + 5.0, tenant="alice")
    ctl.admit(time.monotonic() + 5.0, tenant="bob")
    order = []
    started = threading.Barrier(3)

    def waiter(tenant):
        started.wait()
        ctl.admit(time.monotonic() + 5.0, tenant=tenant)
        order.append(tenant)

    # bob queues FIRST: under FIFO he'd win the freed slot.
    tb = threading.Thread(target=waiter, args=("bob",), daemon=True)
    ta = threading.Thread(target=waiter, args=("alice",), daemon=True)
    tb.start(), ta.start()
    started.wait()
    deadline = time.monotonic() + 2.0
    while ctl.tenant_waiting("alice") + ctl.tenant_waiting("bob") < 2:
        assert time.monotonic() < deadline, "waiters never queued"
        time.sleep(0.005)
    # Free alice's slot: both tenants now at inflight 0 vs 1... alice
    # charge 0/4, bob would be 1/1 — alice must be chosen even though
    # bob waited longer.
    ctl.release(tenant="alice")
    ta.join(timeout=5.0)
    assert order == ["alice"]
    ctl.release(tenant="bob")
    tb.join(timeout=5.0)
    assert sorted(order) == ["alice", "bob"]
    ctl.release(tenant="alice"), ctl.release(tenant="bob")


def test_admission_state_stays_bounded():
    d = _directory(tiers={}, max_tenants=8)
    ctl = TenantAdmissionController(d, max_inflight=4, max_queue=4)
    for i in range(100):
        t = f"rotating-{i}"
        ctl.admit(time.monotonic() + 1.0, tenant=t)
        ctl.release(tenant=t)
    assert len(ctl._slots) <= 8


# -- accounting ------------------------------------------------------------


def test_bounded_tenant_map_evicts_lru():
    m = BoundedTenantMap(cap=3, factory=dict)
    for t in ("a", "b", "c"):
        m.get(t)
    m.get("a")                      # refresh a's recency
    m.get("d")                      # evicts b (LRU), not a
    assert "a" in m and "d" in m and "b" not in m
    assert len(m) == 3
    assert telemetry.get_counter("tenant.accounting_evictions") == 1


def test_accounting_burn_and_summary_flush(journaled):
    acc = TenantAccounting(_directory())
    for _ in range(20):
        acc.admitted("alice", waited_s=0.0)
        acc.completed("alice", e2e_s=0.01, ok=True)    # 10ms ≪ 200ms gold
    acc.shed("bob", "tenant_quota")
    assert acc.burn("alice") < 1.0
    per = acc.per_tenant()
    assert per["alice"]["admitted"] == 20
    assert per["bob"]["shed"] == 1
    acc.flush()
    journal.close()
    recs = journal_mod.read_dir(journaled)
    summaries = [r for r in recs if r.get("kind") == "tenant"
                 and r.get("name") == "summary"]
    assert summaries and summaries[-1]["tenants"]["alice"]["admitted"] == 20
    sheds = [r for r in recs if r.get("kind") == "tenant"
             and r.get("name") == "shed"]
    assert [r["tenant"] for r in sheds] == ["bob"]


# -- residency + hosting ---------------------------------------------------


class _TagModel:
    def __init__(self, tag):
        self.tag = tag
        self.destroyed = False

    def predict(self, queries):
        return [f"{self.tag}:{q}" for q in queries]

    def destroy(self):
        self.destroyed = True


def test_residency_lru_swap_journaled(journaled):
    rm = ResidencyManager(budget_bytes=100)
    a, b = _TagModel("A"), _TagModel("B")
    assert rm.activate("jobA", 80, lambda: a) is a
    assert rm.activate("jobA", 80, lambda: a) is a          # hit
    assert rm.activate("jobB", 80, lambda: b) is b          # evicts A
    assert a.destroyed and not b.destroyed
    assert rm.used_bytes() <= 100
    with pytest.raises(MemoryError):
        rm.activate("huge", 101, lambda: _TagModel("X"))
    journal.close()
    events = [r["event"] for r in journal_mod.read_dir(journaled)
              if r.get("kind") == "tenancy" and r.get("name") == "residency"]
    assert events == ["activate", "hit", "evict", "activate"]


def test_program_host_routes_by_program_tag(journaled):
    host = ProgramHost([
        ProgramSpec("jobA", lambda: _TagModel("A"), 60),
        ProgramSpec("jobB", lambda: _TagModel("B"), 60),
    ], residency=ResidencyManager(budget_bytes=200))
    out = host.predict([wrap_query("jobA", "x"), wrap_query("jobB", "y"),
                        wrap_query("jobA", "z")])
    assert out == ["A:x", "B:y", "A:z"]
    assert telemetry.get_counter("tenancy.host_queries") == 3


# -- twin: per-tenant model + validation -----------------------------------


def _tenant_capture(tmp_path, per_tenant=30, gap_s=0.02, forward_s=0.010):
    """Synthetic --tenants capture: hop chains + gateway/config for
    calibration, tenant-tagged serving/request rows, tenant/admit
    rows carrying each tenant's tier."""
    overhead = 0.002
    recs = [{"kind": "gateway", "name": "config", "ts": 0.0, "pid": 1,
             "max_inflight": 8, "max_queue": 32,
             "default_deadline_s": 2.0, "min_replies": None,
             "hedge_grace_s": 0.0, "policy": "replicate-all",
             "breaker_failures": 3, "breaker_cooldown_s": 5.0}]
    for i in range(per_tenant * 2):
        tenant = "gold_t" if i % 2 == 0 else "batch_t"
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
                     "timeouts": 0, "tenant": tenant,
                     "e2e_s": round(forward_s + overhead, 6)})
        recs.append({"kind": "tenant", "name": "admit", "ts": t0, "pid": 1,
                     "tenant": tenant,
                     "tier": "gold" if tenant == "gold_t" else "batch",
                     "waited_s": 0.0})
    path = tmp_path / "journal-gateway-1.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return tmp_path


def test_tenant_simulation_is_deterministic_and_isolating():
    from rafiki_tpu.obs.twin.calibration import Calibration
    from rafiki_tpu.obs.twin.engine import TwinConfig, simulate

    cal = Calibration.nominal(forward_ms=5.0, workers=2)
    cfg = TwinConfig.from_calibration(
        cal, workers=2, max_inflight=2, max_queue=8,
        tenants={"v": {"weight": 4.0}, "agg": {"weight": 1.0}})
    arrivals = ([(i * 0.02, 1, "v") for i in range(40)]
                + [(0.1 + i * 0.002, 1, "agg") for i in range(200)])
    r1 = simulate(cal, cfg, arrivals, seed=0)
    r2 = simulate(cal, cfg, arrivals, seed=0)
    assert r1["event_log_sha1"] == r2["event_log_sha1"]
    blocks = r1["tenants"]
    # The flooder is the one who sheds; the victim is fully served and
    # its caller-observed p99 is reported alongside post-admission.
    assert blocks["agg"]["shed"] > 0
    assert blocks["agg"]["shed_reasons"].get("tenant_quota", 0) > 0
    assert blocks["v"]["shed"] == 0 and blocks["v"]["ok"] == 40
    assert blocks["v"]["full_p99_ms"] >= blocks["v"]["p99_ms"]


def test_validate_tenants_passes_faithful_fails_doctored(tmp_path):
    from rafiki_tpu.obs.twin import validate as validate_mod

    log_dir = _tenant_capture(tmp_path)
    good = validate_mod.validate_tenants(log_dir, seed=0)
    assert good["ok"] is True and good["gated_tenants"] == 2
    assert set(good["tenants"]) == {"gold_t", "batch_t"}
    assert good["tenants"]["gold_t"]["tier"] == "gold"
    bad = validate_mod.validate_tenants(log_dir, seed=0,
                                        scales={"forward": 0.4})
    assert bad["ok"] is False


# -- arbiter ---------------------------------------------------------------


def _nominal_gate(existing, workers=1, forward_ms=50.0, **kw):
    from rafiki_tpu.obs.twin.calibration import Calibration
    from rafiki_tpu.obs.twin.engine import TwinConfig

    cal = Calibration.nominal(forward_ms=forward_ms, workers=workers)
    cfg = TwinConfig.from_calibration(cal, workers=workers)
    return JobAdmissionGate(cal, cfg, existing=existing, horizon_s=2.0,
                            seed=0, **kw)


def test_gate_rejects_saturating_job_and_journals_verdicts(journaled):
    gate = _nominal_gate({"alice": ("gold", 5.0)})
    ok = gate.admit_job("job-small", "carol", "batch", expected_qps=1.0)
    assert ok["admit"] is True
    assert gate.existing["carol"] == ("batch", 1.0)
    # 25 qps sits in the saturation window: admitted-within-quota load
    # that genuinely overruns capacity (an even bigger flood would be
    # quota-shed back under budget — that's isolation, not admission).
    with pytest.raises(JobRejected) as ei:
        gate.admit_job("job-big", "bob", "std", expected_qps=25.0)
    breaches = ei.value.detail["breaches"]
    assert breaches and breaches[0]["tenant"] == "alice"
    assert breaches[0]["forecast_p99_ms"] > breaches[0]["budget_ms"]
    # A rejected job must NOT join the tracked load.
    assert "bob" not in gate.existing
    journal.close()
    verdicts = [r for r in journal_mod.read_dir(journaled)
                if r.get("kind") == "tenancy" and r.get("name") == "arbiter"]
    assert [v["admit"] for v in verdicts] == [True, False]
    assert telemetry.get_counter("tenancy.jobs_admitted") == 1
    assert telemetry.get_counter("tenancy.jobs_rejected") == 1


def test_gate_from_capture_validates_first(tmp_path):
    log_dir = _tenant_capture(tmp_path)
    gate = JobAdmissionGate.from_capture(log_dir, seed=0)
    assert set(gate.existing) == {"gold_t", "batch_t"}
    assert gate.existing["gold_t"][0] == "gold"
    assert all(qps > 0 for _, qps in gate.existing.values())
    # An absurd tolerance turns the same capture into an unvalidated
    # model — the gate must refuse rather than forecast with it.
    with pytest.raises(ModelUnvalidated):
        JobAdmissionGate.from_capture(log_dir, seed=0, tolerance=1e-6)


def test_tenant_pressure_tracks_worst_component():
    from rafiki_tpu.tenancy.arbiter import tenant_pressure

    p, reason = tenant_pressure({"tenant_burn": 2.0, "queue_frac": 0.1,
                                 "tenant_shed_rate": 0.05})
    assert (p, reason) == (2.0, "tenant_burn")
    p, reason = tenant_pressure({"tenant_burn": 0.1, "queue_frac": 0.2,
                                 "tenant_shed_rate": 0.09})
    assert reason == "tenant_shed" and p == pytest.approx(0.9)


# -- end to end: co-hosting and isolation -----------------------------------


def test_one_worker_cohosts_two_models_under_budget(journaled):
    """ONE InferenceWorker serves TWO distinct models (jobA/jobB) behind
    a ProgramHost whose residency budget fits only one: every
    cross-program query forces an LRU swap, the swaps are in the
    ``tenancy/residency`` journal, and no record is over the budget."""
    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.worker.inference import InferenceWorker

    # 100-byte budget vs two 80-byte programs: a program switch MUST
    # evict the other — the swap is forced, not incidental.
    host = ProgramHost([ProgramSpec("jobA", lambda: _TagModel("A"), 80),
                        ProgramSpec("jobB", lambda: _TagModel("B"), 80)],
                       residency=ResidencyManager(budget_bytes=100))
    bus = InProcBus()
    stop = threading.Event()
    worker = InferenceWorker(bus, "jobA", "w0", host, stop_event=stop,
                             extra_job_ids=["jobB"])
    th = threading.Thread(target=worker.run, daemon=True)
    th.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not (
                bus.get_workers("jobA") == bus.get_workers("jobB") == ["w0"]):
            time.sleep(0.01)
        assert bus.get_workers("jobA") == bus.get_workers("jobB") == ["w0"]
        pa = Predictor(bus, "jobA", timeout_s=5.0, program="jobA")
        pb = Predictor(bus, "jobB", timeout_s=5.0, program="jobB")
        answers = [pa.predict(["x"])[0], pb.predict(["y"])[0],
                   pa.predict(["z"])[0]]
    finally:
        stop.set()
        th.join(timeout=5)
        host.destroy()
    assert answers == ["A:x", "B:y", "A:z"]
    journal.close()
    recs = [r for r in journal_mod.read_dir(journaled)
            if r.get("kind") == "tenancy" and r.get("name") == "residency"]
    events = [r.get("event") for r in recs]
    assert events.count("activate") >= 3 and events.count("evict") >= 2
    assert [r for r in recs if r.get("used_bytes", 0) > 100] == []


@pytest.mark.parametrize("unweighted", [False, True],
                         ids=["weighted-holds", "unweighted-caught"])
def test_noisy_neighbor_isolation_both_polarities(unweighted, monkeypatch):
    """Weighted admission + per-tenant quotas keep the gold victim's p99
    inside budget while the flooding batch aggressor sheds: the scenario
    PASSES. The same scenario under ``RAFIKI_TENANT_UNWEIGHTED=1`` (quota
    off, arbitration degraded to global FIFO — the pre-tenancy gateway)
    must FAIL, and by the ``victim_p99_within_budget`` check
    specifically: a gate that cannot catch unfair admission is not a
    gate. (The unweighted leg is slow by design: the victim really does
    queue behind the whole flood.)"""
    from rafiki_tpu.chaos.runner import format_report, run_scenario

    if unweighted:
        monkeypatch.setenv("RAFIKI_TENANT_UNWEIGHTED", "1")
    else:
        monkeypatch.delenv("RAFIKI_TENANT_UNWEIGHTED", raising=False)
    report = run_scenario("noisy-neighbor-shed")
    if not unweighted:
        assert report.passed, format_report(report)
        return
    assert not report.passed and report.error is None, format_report(report)
    p99 = next(c for c in report.checks
               if c.name == "victim_p99_within_budget")
    assert not p99.ok, format_report(report)
