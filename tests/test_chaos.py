"""Chaos plane: spec parsing, deterministic replay, inertness, hook
effects at real call sites, and the scenario runner — including the
ISSUE-5 acceptance scenario (kill-mid-pack-resume) end to end.
"""

import threading
import time

import pytest

from rafiki_tpu import chaos, telemetry
from rafiki_tpu.chaos import (
    ChaosError, ChaosSpecError, FaultPlane, install, uninstall)
from rafiki_tpu.chaos.runner import run_scenario
from rafiki_tpu.chaos.scenarios import SCENARIOS


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts and ends chaos-free; telemetry isolated."""
    telemetry.reset()
    uninstall()
    yield
    uninstall()
    telemetry.reset()


# ---------------------------------------------------------------------------
# Spec parsing
# ---------------------------------------------------------------------------


def test_spec_parses_sites_modes_and_options():
    plane = FaultPlane.from_spec(
        "seed=9;worker.epoch:kill:after=1:times=2:unless=-r;"
        "bus.add_query:drop:p=0.25;store.params_write:delay:delay=0.5:match=_ckpt_")
    assert plane.seed == 9
    assert len(plane.faults) == 3
    kill, drop, delay = plane.faults
    assert (kill.site, kill.mode, kill.after, kill.times, kill.unless) == \
        ("worker.epoch", "kill", 1, 2, "-r")
    assert (drop.site, drop.mode, drop.prob) == ("bus.add_query", "drop", 0.25)
    assert (delay.site, delay.delay_s, delay.match) == \
        ("store.params_write", 0.5, "_ckpt_")


@pytest.mark.parametrize("bad", [
    "",                            # nothing to inject
    "worker.epoch",                # no mode
    "worker.epoch:explode",        # unknown mode
    "worker.epoch:kill:after",     # option not k=v
    "worker.epoch:kill:nope=1",    # unknown option
    "worker.epoch:kill:p=lots",    # bad value
    "seed=seven;a.b:drop",         # bad seed
])
def test_bad_specs_fail_loudly(bad):
    with pytest.raises(ChaosSpecError):
        FaultPlane.from_spec(bad)


# ---------------------------------------------------------------------------
# Determinism + inertness (acceptance criteria)
# ---------------------------------------------------------------------------


def _drive(plane, hits=200):
    install(plane)
    for i in range(hits):
        chaos.decide("bus.add_query", key=f"w{i % 3}")
        chaos.decide("bus.heartbeat", key=f"w{i % 2}")
    uninstall()
    return plane.schedule()


def test_fixed_seed_replays_identical_schedule():
    spec = "seed=42;bus.add_query:drop:p=0.3;bus.heartbeat:skip:p=0.2:match=w1"
    first = _drive(FaultPlane.from_spec(spec))
    second = _drive(FaultPlane.from_spec(spec))
    assert first, "schedule empty — p gates never fired"
    assert first == second


def test_different_seed_changes_schedule():
    a = _drive(FaultPlane.from_spec("seed=1;bus.add_query:drop:p=0.3"))
    b = _drive(FaultPlane.from_spec("seed=2;bus.add_query:drop:p=0.3"))
    assert a != b


def test_per_site_streams_are_independent():
    """Interleaving extra traffic on one site must not shift another
    site's firing pattern (per-spec rng streams, one draw per hit)."""
    spec = "seed=7;bus.add_query:drop:p=0.5"

    plane_a = FaultPlane.from_spec(spec)
    install(plane_a)
    for i in range(50):
        chaos.decide("bus.add_query", key=f"w{i}")
    uninstall()

    plane_b = FaultPlane.from_spec(spec)
    install(plane_b)
    for i in range(50):
        chaos.decide("bus.heartbeat", key="noise")  # no spec on this site
        chaos.decide("bus.add_query", key=f"w{i}")
    uninstall()

    assert [s for s in plane_a.schedule()] == \
        [s for s in plane_b.schedule() if s[0] == "bus.add_query"]


def test_inert_when_unset(monkeypatch):
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    assert chaos.reset_from_env() is None
    assert chaos.active() is None
    assert chaos.hook("bus.add_query", "w0") is None
    assert chaos.decide("worker.epoch", "w0") is None
    # No telemetry churn on the inert path either.
    assert telemetry.get_counter("chaos.injected") == 0.0


def test_env_spec_installs_on_reset(monkeypatch):
    monkeypatch.setenv(chaos.ENV_VAR, "seed=3;bus.heartbeat:skip")
    plane = chaos.reset_from_env()
    assert plane is not None and plane.seed == 3
    assert chaos.hook("bus.heartbeat", "w0") == "skip"
    monkeypatch.delenv(chaos.ENV_VAR)
    assert chaos.reset_from_env() is None


# ---------------------------------------------------------------------------
# Gating options
# ---------------------------------------------------------------------------


def test_after_times_match_unless_gates():
    plane = FaultPlane.from_spec(
        "worker.epoch:kill:after=2:times=1:match=w0:unless=-r")
    install(plane)
    # unless filters the restarted incarnation entirely (no hit counted)
    assert chaos.decide("worker.epoch", "w0-r1") is None
    # match filters other workers
    assert chaos.decide("worker.epoch", "w1") is None
    # after=2: first two matching hits pass through
    assert chaos.decide("worker.epoch", "w0") is None
    assert chaos.decide("worker.epoch", "w0") is None
    fault = chaos.decide("worker.epoch", "w0")
    assert fault is not None and fault.mode == "kill"
    # times=1: exhausted
    assert chaos.decide("worker.epoch", "w0") is None
    assert plane.schedule() == [("worker.epoch", "kill", 3, "w0")]
    assert telemetry.get_counter("chaos.injected") == 1.0
    assert telemetry.get_counter("chaos.injected.worker.epoch.kill") == 1.0


def test_delay_and_error_modes_enact():
    install(FaultPlane.from_spec(
        "store.params_write:delay:delay=0.12:times=1;inference.forward:error"))
    t0 = time.monotonic()
    assert chaos.hook("store.params_write", "p1") == "delay"
    assert time.monotonic() - t0 >= 0.1
    with pytest.raises(ChaosError):
        chaos.hook("inference.forward", "w0")


# ---------------------------------------------------------------------------
# Hook effects at real call sites
# ---------------------------------------------------------------------------


def test_bus_drop_and_heartbeat_skip():
    from rafiki_tpu.bus import InProcBus

    bus = InProcBus()
    bus.add_worker("j", "w0")
    lease_before = bus.get_workers("j", max_age_s=10.0)
    assert lease_before == ["w0"]

    install(FaultPlane.from_spec("bus.add_query:drop;bus.heartbeat:skip"))
    bus.add_query("w0", "q1", [1.0])
    assert bus.pop_queries("w0", max_n=10, timeout=0.05) == []
    assert telemetry.get_counter("bus.queries_dropped_chaos") == 1.0
    # skipped heartbeat: the lease does NOT refresh
    time.sleep(0.15)
    bus.heartbeat("j", "w0")
    assert bus.get_workers("j", max_age_s=0.1) == []
    uninstall()
    bus.heartbeat("j", "w0")
    assert bus.get_workers("j", max_age_s=0.1) == ["w0"]


def test_store_write_fault_targets_checkpoints_only(tmp_path):
    from rafiki_tpu.store import ParamsStore

    params = ParamsStore(tmp_path / "p")
    install(FaultPlane.from_spec("store.params_write:error:match=_ckpt_"))
    pid = params.save(b"final-params")  # non-checkpoint write unaffected
    assert params.load(pid) == b"final-params"
    with pytest.raises(ChaosError):
        params.save_checkpoint("trial1", 0, b"snap")
    assert params.latest_checkpoint("trial1") is None  # nothing torn


def test_checkpoint_write_failure_does_not_error_trial(tmp_path):
    """The recovery gap this PR fixed: an injected checkpoint-write
    failure must cost resumability, not the trial."""
    from rafiki_tpu.model.base import BaseModel
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import TrainWorker

    class _Model(BaseModel):
        _sink = None

        @staticmethod
        def get_knob_config():
            return {}

        def set_checkpoint_sink(self, sink):
            self._sink = sink

        def train(self, uri):
            for epoch in range(2):
                self._sink(epoch, lambda: b"snap")

        def evaluate(self, uri):
            return 0.5

        def predict(self, queries):
            return []

        def dump_parameters(self):
            return b"params"

    store = MetaStore(tmp_path / "m.sqlite3")
    params = ParamsStore(tmp_path / "p")
    mrow = store.create_model("m", "T", None, b"x = 1", "X")
    job = store.create_train_job("app", "T", None, "t", "v", {})
    sub = store.create_sub_train_job(job["id"], mrow["id"])

    class _Advisor:
        def propose(self):
            return {}

        def feedback(self, score, knobs):
            pass

    install(FaultPlane.from_spec("store.params_write:error:match=_ckpt_"))
    worker = TrainWorker(store, params, sub["id"], _Model, _Advisor(),
                         "t", "v", {}, async_persist=False,
                         checkpoint_every=1)

    trial = worker.run_trial({})
    assert trial["status"] == "COMPLETED"
    assert telemetry.get_counter("worker.checkpoint_write_failed") == 2.0


def test_scheduler_preempt_decision():
    """scheduler.preempt is caller-enacted: decide() returns the fault,
    the supervise loop signals the subprocess."""
    install(FaultPlane.from_spec("scheduler.preempt:preempt:delay=1.5:times=1"))
    fault = chaos.decide("scheduler.preempt", "w0")
    assert fault is not None
    assert fault.mode == "preempt" and fault.delay_s == 1.5
    assert chaos.decide("scheduler.preempt", "w0") is None  # times=1


# ---------------------------------------------------------------------------
# Runner + scenarios
# ---------------------------------------------------------------------------


def test_catalog_has_the_required_scenarios():
    assert {"kill-mid-trial-resume", "kill-mid-pack-resume",
            "straggler-quorum", "drain-under-load",
            "predictor-outage-surfaces",
            "checkpoint-write-failure"} <= set(SCENARIOS)


def test_runner_rejects_unknown_scenario():
    with pytest.raises(KeyError):
        run_scenario("no-such-scenario")


def test_invariant_failures_actually_fail(monkeypatch):
    """A scenario whose invariant is violated must report FAIL — the
    runner can't be vacuously green."""
    from rafiki_tpu.chaos import runner as runner_mod
    from rafiki_tpu.chaos.scenarios import Scenario

    def always_wrong(tmp, check):
        check("impossible", False, "violated by construction")

    monkeypatch.setitem(
        SCENARIOS, "always-wrong",
        Scenario(name="always-wrong", description="x",
                 spec="bus.heartbeat:skip", fn=always_wrong))
    report = runner_mod.run_scenario("always-wrong")
    assert not report.passed
    assert [c.name for c in report.checks if not c.ok] == ["impossible"]

    def raises(tmp, check):
        raise RuntimeError("scenario body exploded")

    monkeypatch.setitem(
        SCENARIOS, "raises",
        Scenario(name="raises", description="x",
                 spec="bus.heartbeat:skip", fn=raises))
    report = runner_mod.run_scenario("raises")
    assert not report.passed and "exploded" in report.error

    def checks_nothing(tmp, check):
        pass

    monkeypatch.setitem(
        SCENARIOS, "vacuous",
        Scenario(name="vacuous", description="x",
                 spec="bus.heartbeat:skip", fn=checks_nothing))
    assert not runner_mod.run_scenario("vacuous").passed


def test_runner_restores_env_and_plane(monkeypatch):
    import os

    from rafiki_tpu.chaos import runner as runner_mod
    from rafiki_tpu.chaos.scenarios import Scenario

    monkeypatch.setenv(chaos.ENV_VAR, "bus.add_query:drop")
    seen = {}

    def body(tmp, check):
        seen["env"] = os.environ.get(chaos.ENV_VAR)
        seen["extra"] = os.environ.get("RAFIKI_CHAOS_TEST_EXTRA")
        check("ran", True)

    monkeypatch.setitem(
        SCENARIOS, "env-probe",
        Scenario(name="env-probe", description="x",
                 spec="seed=5;bus.heartbeat:skip", fn=body,
                 env={"RAFIKI_CHAOS_TEST_EXTRA": "1"}))
    report = runner_mod.run_scenario("env-probe")
    assert report.passed
    assert seen == {"env": "seed=5;bus.heartbeat:skip", "extra": "1"}
    assert os.environ[chaos.ENV_VAR] == "bus.add_query:drop"
    assert "RAFIKI_CHAOS_TEST_EXTRA" not in os.environ
    assert chaos.active() is None  # uninstalled on the way out


def test_straggler_quorum_scenario_passes():
    report = run_scenario("straggler-quorum")
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok)
    assert any(s[0] == "inference.forward" for s in report.schedule)


def test_predictor_outage_scenario_passes():
    report = run_scenario("predictor-outage-surfaces")
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok)


@pytest.mark.parametrize("name,fired", [
    # a subprocess worker SIGKILLs itself at epoch 1; the respawned
    # worker adopts and resumes from the epoch-1 checkpoint
    ("kill-mid-trial-resume", None),
    # gateway drain with injected frontend latency holding inflight
    # slots: flushes, then sheds as ``draining``
    ("drain-under-load", "gateway.predict"),
    # a 2-chip mesh sweep loses chip 1 mid-pack: re-pack onto the
    # survivor, loss and re-pack journaled, params bit-match serial
    ("mesh-chip-loss-repack", "scheduler.preempt"),
    # a width-2 sharded group loses a member mid-epoch: re-forms at
    # width 1, restore reshards 2 -> 1, params bit-match serial
    ("chip-loss-mid-sharded-trial", "scheduler.preempt"),
    # one slow replica breaches the serving-p99 SLO, the controller
    # scales the inference lane up, the breach clears
    ("load-spike-scale-up", "inference.forward"),
    # damped bounded vs undamped thrashing on a fake clock
    ("autoscale-flap-damping", "autoscale.sensor"),
])
def test_recovery_scenario_passes_and_its_fault_fired(name, fired):
    """The catalog's recovery scenarios, end to end. A scenario that
    injects through the plane must show the fault in its schedule: a
    vacuous pass (nothing injected, nothing recovered) fails."""
    report = run_scenario(name)
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok) \
        + (f"\n{report.error}" if report.error else "")
    if fired is not None:
        assert any(s[0] == fired for s in report.schedule), report.schedule


def test_kill_mid_pack_resume_acceptance():
    """ISSUE 5 acceptance: k=4 packed run SIGKILLed mid-trial resumes
    every member from its per-epoch slice checkpoint; no lost or
    duplicated rows; resumed final params bit-match an unfaulted
    serial run. Real subprocess workers on the CPU platform."""
    report = run_scenario("kill-mid-pack-resume")
    assert report.passed, "\n".join(
        f"{c.name}: {c.detail}" for c in report.checks if not c.ok) \
        + (f"\n{report.error}" if report.error else "")
    names = {c.name for c in report.checks}
    assert any(n.startswith("params_match_serial") for n in names)
    assert "all_trials_resumed_by_respawned_worker" in names
