"""Closed-loop elasticity (docs/autoscale.md): the controller's
decision table on a fake clock, the drain→reap→freed ordering contract
on a real bus, pre-warmed compiled packs, and the elastic mesh lane.

Everything here is deterministic by construction — injectable clocks,
explicit seeds, stub actuators where real capacity isn't the point."""

import json
import threading
import time

import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.autoscale.controller import (AutoscaleController, LaneSpec,
                                             inference_pressure,
                                             read_sensors, sweep_pressure)


class StubLane:
    def __init__(self, n=2):
        self.n = n
        self.calls = []

    def size(self):
        return self.n

    def scale_to(self, n):
        self.calls.append(n)
        self.n = n


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _controller(lane, sensor_fn, clock, **kw):
    kw.setdefault("seed", 0)
    kw.setdefault("tick_s", 1.0)
    kw.setdefault("tick_global_slo", False)
    return AutoscaleController(
        lanes=[LaneSpec("inference", min_size=1, max_size=8,
                        up_threshold=1.0, down_threshold=0.3,
                        up_cooldown_s=5.0, down_cooldown_s=30.0)],
        sensor_fn=sensor_fn,
        actuators={"inference": lane},
        clock=clock, **kw)


def _burn(level):
    return {"slo_breaching": ["x"] if level else [],
            "slo_burn": level, "queue_frac": 0.0, "shed_rate": 0.0}


# ---------------------------------------------------------------------------
# decision table
# ---------------------------------------------------------------------------


def test_hysteresis_band_holds_between_thresholds():
    lane, clock = StubLane(), FakeClock()
    ctl = _controller(lane, lambda: _burn(0.6), clock)
    (d,) = ctl.tick()
    assert d.direction == "hold" and d.reason == "in-band"
    assert lane.calls == []


def test_pressure_above_threshold_scales_up_one_step():
    lane, clock = StubLane(), FakeClock()
    ctl = _controller(lane, lambda: _burn(2.0), clock)
    (d,) = ctl.tick()
    assert d.direction == "up" and d.actuated and d.target == 3
    assert lane.calls == [3]


def test_idle_pressure_scales_down_one_step():
    lane, clock = StubLane(4), FakeClock()
    ctl = _controller(lane, lambda: _burn(0.0), clock)
    (d,) = ctl.tick()
    assert d.direction == "down" and d.actuated and d.target == 3


def test_clamped_at_bounds():
    lane, clock = StubLane(8), FakeClock()
    ctl = _controller(lane, lambda: _burn(2.0), clock)
    (d,) = ctl.tick()
    assert d.direction == "hold" and d.reason == "at-max"
    lane2 = StubLane(1)
    ctl2 = _controller(lane2, lambda: _burn(0.0), clock)
    (d2,) = ctl2.tick()
    assert d2.direction == "hold" and d2.reason == "at-min"
    assert lane.calls == lane2.calls == []


def test_same_direction_cooldown_blocks_then_releases():
    lane, clock = StubLane(), FakeClock()
    ctl = _controller(lane, lambda: _burn(2.0), clock)
    assert ctl.tick()[0].actuated
    clock.t = 2.0  # inside the 5s up cooldown
    (held,) = ctl.tick()
    assert held.direction == "hold" and held.reason == "cooldown"
    clock.t = 6.0  # past it
    assert ctl.tick()[0].actuated
    assert lane.calls == [3, 4]


def test_cooldowns_are_per_direction():
    """A fresh scale-up must not block a scale-down: each direction
    rate-limits itself (the flap GUARD is what gates the flip, and it
    has its own, shorter clock)."""
    lane, clock = StubLane(4), FakeClock()
    signal = {"v": 2.0}
    ctl = _controller(lane, lambda: _burn(signal["v"]), clock,
                      flap_guard_s=1.0)
    assert ctl.tick()[0].direction == "up"
    signal["v"] = 0.0
    clock.t = 2.0  # inside up's 5s cooldown, past the 1s flap guard
    (d,) = ctl.tick()
    assert d.direction == "down" and d.actuated, d.reason


def test_flap_damping_converges_where_undamped_oscillates():
    def square_wave():
        state = {"i": 0}

        def fn():
            state["i"] += 1
            return _burn(2.0 if state["i"] % 2 else 0.0)
        return fn

    def run(damping):
        lane, clock = StubLane(), FakeClock()
        ctl = AutoscaleController(
            lanes=[LaneSpec("inference", min_size=1, max_size=8,
                            up_threshold=1.0, down_threshold=0.3,
                            up_cooldown_s=1.0, down_cooldown_s=1.0)],
            sensor_fn=square_wave(),
            actuators={"inference": lane},
            clock=clock, seed=0, tick_s=2.0, damping=damping,
            flap_window_s=600.0, flap_flips=2, flap_backoff=2.0,
            flap_guard_s=2.0, flap_guard_cap_s=64.0,
            tick_global_slo=False)
        for _ in range(100):
            ctl.tick()
            clock.t += 2.0
        return len(lane.calls)

    undamped, damped = run(False), run(True)
    assert undamped >= 50, "square wave should thrash an undamped loop"
    assert damped <= undamped // 3
    assert damped <= 30


def test_twin_pregate_veto_blocks_actuation():
    lane, clock = StubLane(), FakeClock()
    seen = []

    def pregate(lane_name, current, target, sensors):
        seen.append((lane_name, current, target))
        return {"veto": True, "p99_ms_delta": +40.0}

    ctl = _controller(lane, lambda: _burn(2.0), clock, pregate_fn=pregate)
    (d,) = ctl.tick()
    assert d.vetoed and not d.actuated and d.direction == "up"
    assert d.forecast["p99_ms_delta"] == 40.0
    assert seen == [("inference", 2, 3)]
    assert lane.calls == []


def test_sensor_error_holds_every_lane():
    lane, clock = StubLane(), FakeClock()

    def broken():
        raise RuntimeError("sensor plane down")

    ctl = _controller(lane, broken, clock)
    before = telemetry.get_counter("autoscale.sensor_errors")
    (d,) = ctl.tick()
    assert d.direction == "hold" and d.reason == "sensor-error"
    assert lane.calls == []
    assert telemetry.get_counter("autoscale.sensor_errors") == before + 1


def test_decision_stream_is_byte_deterministic():
    """Same clock script, same seed, same sensors -> byte-identical
    decision dicts (the replay contract `obs autoscale` leans on)."""

    def run():
        lane, clock = StubLane(), FakeClock()
        state = {"i": 0}

        def sensors():
            state["i"] += 1
            return _burn([2.0, 0.0, 0.6, 2.0][state["i"] % 4])

        ctl = _controller(lane, sensors, clock)
        out = []
        for _ in range(12):
            out.extend(d.to_dict() for d in ctl.tick())
            clock.t += 3.0
        return json.dumps(out, sort_keys=True)

    assert run() == run()


def test_actuator_failure_still_arms_cooldown():
    class FailingLane(StubLane):
        def scale_to(self, n):
            raise RuntimeError("spawn failed")

    lane, clock = FailingLane(), FakeClock()
    ctl = _controller(lane, lambda: _burn(2.0), clock)
    (d,) = ctl.tick()
    assert not d.actuated and "spawn failed" in d.sensors["actuate_error"]
    clock.t = 2.0
    (held,) = ctl.tick()
    assert held.reason == "cooldown", \
        "a broken actuator retried every tick is its own flap"


def test_pressure_functions():
    p, why = inference_pressure({"slo_breaching": ["x"], "slo_burn": 1.4,
                                 "queue_frac": 0.2, "shed_rate": 0.01})
    assert p == 1.4 and why == "slo_burn"
    p, why = inference_pressure({"slo_breaching": [], "slo_burn": 9.0,
                                 "queue_frac": 0.2, "shed_rate": 0.0})
    assert p == 0.2 and why == "queue_frac", "burn only counts breaching"
    assert sweep_pressure({}) == (None, "no-target")


def test_sweep_pressure_from_env(monkeypatch):
    monkeypatch.setenv("RAFIKI_AUTOSCALE_TARGET_EPH", "100")
    assert sweep_pressure({"effective_trials_per_hour": None}) == \
        (None, "no-data")
    p, why = sweep_pressure({"effective_trials_per_hour": 50.0})
    assert p == 2.0 and why == "eph"


def test_lane_spec_from_env(monkeypatch):
    monkeypatch.setenv("RAFIKI_AUTOSCALE_MAX", "3")
    monkeypatch.setenv("RAFIKI_AUTOSCALE_UP_COOLDOWN_S", "9.5")
    spec = LaneSpec.from_env("inference", min_size=2)
    assert (spec.max_size, spec.up_cooldown_s, spec.min_size) == (3, 9.5, 2)


def test_read_sensors_merges_gateway_and_slo():
    from rafiki_tpu.obs.perf.slo import SloEngine, SloSpec

    engine = SloEngine([SloSpec("x", "gauge:autoscale.test_gauge", 1.0)],
                       tick_s=0.0)
    s = read_sensors(slo_engine=engine)
    assert not s["slo_breaching"] and s["slo_burn"] == 0.0
    assert "effective_trials_per_hour" in s


# ---------------------------------------------------------------------------
# drain→reap→freed ordering (the scale-down correctness contract)
# ---------------------------------------------------------------------------


class _SlowModel:
    """Holds each forward long enough that a drain provably overlaps
    inflight work."""

    def __init__(self, hold_s=0.2):
        self.hold_s = hold_s

    def predict(self, queries):
        time.sleep(self.hold_s)
        return [[0.5, 0.5] for _ in queries]


def _spawned_worker(bus, job, wid, model):
    from rafiki_tpu.worker.inference import InferenceWorker

    stop = threading.Event()
    w = InferenceWorker(bus, job, wid, model, stop_event=stop)
    th = threading.Thread(target=w.run, daemon=True)
    th.start()
    return w, th


def test_drain_flushes_inflight_then_reaps_then_frees():
    from rafiki_tpu.autoscale.actuators import InferenceWorkerLane
    from rafiki_tpu.bus import InProcBus

    bus, job = InProcBus(), "drainjob"
    lane = InferenceWorkerLane(
        bus, job,
        spawn_fn=lambda i: (f"as{i}",) + _spawned_worker(
            bus, job, f"as{i}", _SlowModel()))
    lane.scale_to(2)
    assert lane.size() == 2 and sorted(lane.worker_ids()) == ["as0", "as1"]
    # Park a query on the victim (newest = as1) and wait until its
    # serve loop has POPPED it — the drain now overlaps real inflight
    # work, not an empty queue.
    bus.add_query("as1", "q-inflight", [1.0])
    deadline = time.monotonic() + 5
    while bus.queue_depth("as1") > 0:
        assert time.monotonic() < deadline, "query never popped"
        time.sleep(0.005)
    lane.scale_to(1)
    # The inflight reply was published BEFORE the slot was counted
    # freed: the prediction must exist now, with zero further wait.
    preds = bus.get_predictions("q-inflight", 1, timeout=0.0)
    assert preds and preds[0][1] == [0.5, 0.5]
    assert [e for e in lane.events if e[1] == "as1"] == \
        [("drained", "as1"), ("reaped", "as1"), ("freed", "as1")]
    assert "as1" not in bus.get_workers(job)
    assert lane.size() == 1 and lane.worker_ids() == ["as0"]
    lane.scale_to(0)


def test_drain_timeout_on_stuck_worker_is_counted():
    """A victim whose lease never leaves the bus must not wedge the
    lane forever: the bounded wait expires, the timeout is counted,
    and the slot is still reclaimed (the janitor owns the corpse)."""
    from rafiki_tpu.autoscale.actuators import InferenceWorkerLane
    from rafiki_tpu.bus import InProcBus

    class _Corpse:
        def stop(self):
            pass  # ignores the drain — and holds no drained event

    bus, job = InProcBus(), "stuckjob"
    bus.add_worker(job, "w0")
    bus.add_worker(job, "w1")
    lane = InferenceWorkerLane(
        bus, job, spawn_fn=lambda i: (_ for _ in ()).throw(AssertionError),
        initial=[("w0", _Corpse(), None), ("w1", _Corpse(), None)],
        drain_timeout_s=0.2)
    before = telemetry.get_counter("autoscale.drain_timeouts")
    lane.scale_to(1)
    assert telemetry.get_counter("autoscale.drain_timeouts") == before + 1
    assert lane.size() == 1


# ---------------------------------------------------------------------------
# pre-warmed compiled packs
# ---------------------------------------------------------------------------


def test_probe_knobs_picks_midpoints():
    from rafiki_tpu.autoscale.prewarm import probe_knobs
    from rafiki_tpu.model.knobs import (CategoricalKnob, FixedKnob,
                                        FloatKnob, IntegerKnob)

    probe = probe_knobs({
        "fixed": FixedKnob(32),
        "cat": CategoricalKnob([8, 16]),
        "int": IntegerKnob(2, 10),
        "lin": FloatKnob(0.0, 1.0),
        "exp": FloatKnob(1e-4, 1e-2, is_exp=True),
    })
    assert probe["fixed"] == 32 and probe["cat"] == 8 and probe["int"] == 6
    assert probe["lin"] == pytest.approx(0.5)
    assert probe["exp"] == pytest.approx(1e-3)


@pytest.mark.slow
def test_prewarm_primes_the_program_cache(tmp_path, monkeypatch):
    """A prewarmed packing key must make the NEXT PackedTrainLoop for
    the same key a program-cache hit — that hit is the 12.8s compile
    scale-up no longer pays."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    from rafiki_tpu.autoscale.prewarm import prewarm_models, probe_knobs
    from rafiki_tpu.chaos.scenarios import FF_SOURCE, TRAIN
    from rafiki_tpu.model.base import load_model_class

    cls = load_model_class(FF_SOURCE, "ChaosFF")
    probe = probe_knobs(cls.get_knob_config())
    first = prewarm_models(cls, [probe, probe], TRAIN, k=2)
    assert first["errors"] == []
    assert first["warmed"] == 1 and first["keys"] == 1
    second = prewarm_models(cls, [probe, probe], TRAIN, k=2)
    assert second["errors"] == []
    assert second["cache_hits"] == 1, \
        "the second prewarm of the same packing key must hit the cache"


# ---------------------------------------------------------------------------
# elastic mesh lane
# ---------------------------------------------------------------------------


def test_elastic_handle_bookkeeping():
    from rafiki_tpu.scheduler.mesh import ElasticHandle

    h = ElasticHandle()
    h._set_live(2)
    assert h.desired() == 2 and h.live() == 2
    h.request(2)
    h.request(-1)
    assert h.desired() == 3
    assert h._take() == 1
    assert h._take() == 0, "the delta is consumed exactly once"
    h._set_live(3)
    h.request(-99)
    assert h.desired() == 0, "desired never goes negative"


def test_sweep_chip_lane_requests_deltas():
    from rafiki_tpu.autoscale.actuators import SweepChipLane
    from rafiki_tpu.scheduler.mesh import ElasticHandle

    h = ElasticHandle()
    h._set_live(2)
    lane = SweepChipLane(h)
    assert lane.size() == 2
    lane.scale_to(4)
    assert h.desired() == 4
    lane.scale_to(4)  # no-op: desired already matches
    assert h._take() == 2


@pytest.fixture()
def mesh_env(tmp_path):
    from rafiki_tpu.store import MetaStore, ParamsStore

    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    return store, params


def _mesh_job(store, budget):
    from rafiki_tpu.chaos.scenarios import FF_SOURCE, TRAIN, VAL

    model = store.create_model("chaosff", "IMAGE_CLASSIFICATION", None,
                               FF_SOURCE, "ChaosFF")
    job = store.create_train_job("scaleapp", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL, budget)
    store.create_sub_train_job(job["id"], model["id"])
    return job


@pytest.mark.slow
def test_mesh_sweep_grown_chip_is_a_first_class_survivor(mesh_env,
                                                         monkeypatch):
    """Grow mid-sweep, then lose the ORIGINAL chip: the grown chip must
    inherit the re-packed rows like any survivor — elastic capacity is
    not a second-class spectator."""
    from rafiki_tpu.chaos import FaultPlane, install, uninstall
    from rafiki_tpu.scheduler import MeshSweepScheduler
    from rafiki_tpu.scheduler.mesh import ElasticHandle

    store, params = mesh_env
    monkeypatch.setenv("RAFIKI_CHECKPOINT_EVERY", "1")
    job = _mesh_job(store, {"MODEL_TRIAL_COUNT": 2})
    telemetry.reset()
    elastic = ElasticHandle()
    elastic.request(1)  # armed before the run: applied at first poll
    install(FaultPlane.from_spec(
        "seed=11;scheduler.preempt:kill:after=2:times=1:match=chip0"))
    try:
        result = MeshSweepScheduler(store, params).run_sweep(
            job["id"], chips=1, trials_per_chip=2, advisor_kind="random",
            elastic=elastic)
    finally:
        uninstall()
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 2
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert telemetry.get_counter("mesh.chips_scaled_up") >= 1.0
    assert telemetry.get_counter("mesh.chips_lost") >= 1.0
    assert any(a["dir"] == "up" for a in elastic.applied)
    # The grown chip really trained: the dead chip's rows finished
    # under its worker id.
    assert any((t["worker_id"] or "").endswith("-mesh-c1")
               for t in result.trials)


@pytest.mark.slow
def test_mesh_sweep_shrinks_without_charging_downtime(mesh_env,
                                                      monkeypatch):
    """A voluntary scale-down is not a failure: the victim chip drains
    at its epoch boundary, its trials re-pack onto survivors, and
    neither ``mesh.chips_lost`` nor the downtime ledger is charged."""
    from rafiki_tpu.obs.ledger import ledger
    from rafiki_tpu.scheduler import MeshSweepScheduler
    from rafiki_tpu.scheduler.mesh import ElasticHandle

    store, params = mesh_env
    monkeypatch.setenv("RAFIKI_CHECKPOINT_EVERY", "1")
    job = _mesh_job(store, {"MODEL_TRIAL_COUNT": 4})
    telemetry.reset()
    ledger.reset()
    elastic = ElasticHandle()
    elastic.request(-1)
    result = MeshSweepScheduler(store, params).run_sweep(
        job["id"], chips=2, trials_per_chip=2, advisor_kind="random",
        elastic=elastic)
    assert result.status == "COMPLETED", result.errors
    assert len(result.trials) == 4, "shrink lost or duplicated trials"
    assert all(t["status"] == "COMPLETED" for t in result.trials)
    assert telemetry.get_counter("mesh.chips_scaled_down") >= 1.0
    assert telemetry.get_counter("mesh.chips_lost") == 0.0, \
        "a voluntary shrink must not masquerade as a chip loss"
    assert any(a["dir"] == "down" for a in elastic.applied)
    downtime = ledger.snapshot()["total"].get("downtime_s", 0.0)
    assert downtime == 0.0, \
        f"voluntary shrink charged {downtime}s downtime"


# ---------------------------------------------------------------------------
# gateway sensor surface + CLI replay
# ---------------------------------------------------------------------------


def test_gateway_sensors_shape():
    from rafiki_tpu.bus import InProcBus
    from rafiki_tpu.gateway import Gateway, GatewayConfig
    from rafiki_tpu.predictor import Predictor

    gw = Gateway(Predictor(InProcBus(), "sensorjob"),
                 GatewayConfig(max_queue=10))
    s = gw.sensors()
    assert s["queue_depth"] == 0 and s["queue_frac"] == 0.0
    assert s["inflight"] == 0 and s["shed_rate"] == 0.0
    assert s["draining"] is False and s["breakers_open"] == 0


def test_obs_autoscale_check_catches_undamped_flap(tmp_path, capsys):
    from rafiki_tpu.obs.cli import cmd_autoscale
    from rafiki_tpu.obs.journal import journal

    def run(damping, sub):
        d = tmp_path / sub
        journal.configure(d, role="test")
        try:
            lane, clock = StubLane(), FakeClock()
            state = {"i": 0}

            def sensors():
                state["i"] += 1
                return _burn(2.0 if state["i"] % 2 else 0.0)

            ctl = AutoscaleController(
                lanes=[LaneSpec("inference", min_size=1, max_size=8,
                                up_threshold=1.0, down_threshold=0.3,
                                up_cooldown_s=1.0, down_cooldown_s=1.0)],
                sensor_fn=sensors, actuators={"inference": lane},
                clock=clock, seed=0, tick_s=2.0, damping=damping,
                flap_window_s=600.0, flap_flips=2, flap_backoff=2.0,
                flap_guard_s=2.0, flap_guard_cap_s=64.0,
                tick_global_slo=False)
            for _ in range(60):
                ctl.tick()
                clock.t += 2.0
        finally:
            journal.close()
        return str(d)

    undamped = run(False, "undamped")
    damped = run(True, "damped")
    assert cmd_autoscale(undamped, 0, False, True, 60.0, 4) == 1
    assert "FLAPPING" in capsys.readouterr().err
    assert cmd_autoscale(damped, 0, False, True, 60.0, 4) == 0
    # An empty dir is an error, not a silent pass.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cmd_autoscale(str(empty), 0, False, True, 60.0, 4) == 1
