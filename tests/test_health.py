"""Numerics health plane (docs/health.md): in-graph sentinels,
divergence detection/containment, replay capsules, and the CLI surface.

The contract under test:
  * sentinels — the per-step health bundle is always in the trace, its
    epoch reduction names the first bad step, and stripping it keeps
    the caller-visible metric dict identical to the pre-health-plane
    shape;
  * detector — NaN/Inf trips immediately, grad-norm explosion trips
    only after warmup + hysteresis, and every knob has an env override
    including the kill switch;
  * containment — a serial trial fails fast with DivergenceError and a
    diagnosis; a packed trial evicts ONLY the sick member, and the
    survivors' final params stay bit-identical to an unfaulted run;
  * capsules — a divergence banks an atomic replay capsule whose
    re-execution reproduces the bad step bit-for-bit, through the
    in-proc API and the real ``obs replay`` CLI alike.
"""

import json
import math
import time

import numpy as np
import pytest

from rafiki_tpu import telemetry
from rafiki_tpu.chaos import FaultPlane, install, uninstall
from rafiki_tpu.models.ff import FeedForward
from rafiki_tpu.obs import health
from rafiki_tpu.obs.health import DivergenceError, HealthMonitor
from rafiki_tpu.obs.journal import journal

TRAIN = "synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=0"
VAL = "synthetic://images?classes=4&n=64&w=8&h=8&c=1&seed=1"


@pytest.fixture(autouse=True)
def _clean_plane():
    """Chaos-free and stat-isolated on both sides of every test."""
    uninstall()
    health.reset_stats()
    yield
    uninstall()
    health.reset_stats()


@pytest.fixture
def journaled(tmp_path):
    journal.configure(tmp_path, role="test")
    try:
        yield tmp_path
    finally:
        journal.close()


def _ff(seed=0, epochs=2):
    m = FeedForward(hidden_layers=1, hidden_units=32, learning_rate=1e-3,
                    batch_size=32, epochs=epochs, seed=0)
    m._seed = seed
    return m


def _healthy(gn=1.0):
    return {"health_grad_norm": gn, "health_update_norm": gn * 0.01,
            "health_param_norm": 10.0, "health_nonfinite": 0,
            "health_bad_step": 0, "health_bad_grad_norm": gn,
            "health_bad_update_norm": gn * 0.01, "health_bad_nonfinite": 0}


def _nan_epoch(bad_step=2):
    h = _healthy(gn=float("nan"))
    h.update(health_nonfinite=7, health_bad_step=bad_step,
             health_bad_nonfinite=7, health_bad_grad_norm=float("nan"))
    return h


def _observe(mon, h):
    return mon.observe(h, t0=time.monotonic(), epoch_seed=0,
                       idx=None, poison=None, snapshot=None)


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(np.array_equal(a, b))


def _tree_bits_equal(ta, tb):
    import jax

    return bool(jax.tree.all(jax.tree.map(_bits_equal, ta, tb)))


# -- detector unit behavior ---------------------------------------------------


def test_clean_epochs_never_trip():
    mon = HealthMonitor("t")
    for _ in range(20):
        assert _observe(mon, _healthy()) is None
    assert health.stats()["divergences"] == 0


def test_nonfinite_trips_immediately_with_diagnosis():
    mon = HealthMonitor("t")
    v = _observe(mon, _nan_epoch())
    assert v is not None
    assert v["divergence"] == "nonfinite"
    assert v["bad_step"] == 2
    assert "non-finite" in v["diagnosis"]
    assert health.stats()["divergences"] == 1


def test_nonfinite_grad_norm_without_count_still_trips():
    """An Inf grad norm with a zero non-finite count (overflow in the
    norm reduction itself) is still a nonfinite verdict."""
    mon = HealthMonitor("t")
    h = _healthy(gn=float("inf"))
    assert _observe(mon, h)["divergence"] == "nonfinite"


def test_explosion_needs_warmup_and_hysteresis():
    mon = HealthMonitor("t")
    assert mon.warmup == 3 and mon.hysteresis == 2
    # Too little history: even a wild norm cannot trip (no baseline).
    fresh = HealthMonitor("t2")
    assert _observe(fresh, _healthy(gn=1e9)) is None
    # Warmed up: first exploding epoch arms the streak, second trips.
    for _ in range(3):
        assert _observe(mon, _healthy(gn=1.0)) is None
    assert _observe(mon, _healthy(gn=1000.0)) is None
    v = _observe(mon, _healthy(gn=1000.0))
    assert v is not None and v["divergence"] == "explosion"
    assert "explosion" in v["diagnosis"]


def test_explosion_streak_resets_on_clean_epoch():
    mon = HealthMonitor("t")
    for _ in range(3):
        _observe(mon, _healthy(gn=1.0))
    assert _observe(mon, _healthy(gn=1000.0)) is None
    assert _observe(mon, _healthy(gn=1.0)) is None  # streak broken
    assert _observe(mon, _healthy(gn=1000.0)) is None
    assert health.stats()["divergences"] == 0


def test_exploding_epochs_not_absorbed_into_median():
    """A slow ramp must not normalize itself out of detection: epochs
    above the bar never enter the history the median is taken from."""
    mon = HealthMonitor("t")
    for _ in range(3):
        _observe(mon, _healthy(gn=1.0))
    _observe(mon, _healthy(gn=1000.0))
    assert all(g <= 1.0 for g in mon._members[0].history)


def test_env_knobs(monkeypatch):
    monkeypatch.setenv(health.ENV_K, "5")
    monkeypatch.setenv(health.ENV_WARMUP, "1")
    monkeypatch.setenv(health.ENV_HYSTERESIS, "1")
    mon = HealthMonitor("t")
    assert mon.explosion_k == 5.0
    _observe(mon, _healthy(gn=1.0))
    v = _observe(mon, _healthy(gn=6.0))  # 6 > 5x median 1, 1-epoch fuse
    assert v is not None and v["divergence"] == "explosion"


def test_kill_switch(monkeypatch):
    monkeypatch.setenv(health.ENV_ENABLE, "0")
    mon = HealthMonitor("t")
    assert _observe(mon, _nan_epoch()) is None
    assert health.stats()["divergences"] == 0


def test_capsule_switch_disables_snapshots(monkeypatch):
    monkeypatch.setenv(health.ENV_CAPSULE, "off")
    mon = HealthMonitor("t")
    assert mon.snapshot_state({"w": np.ones(2)}) is None
    v = _observe(mon, _nan_epoch())  # detection stays live
    assert v is not None and v["capsule"] is None


def test_divergence_charges_badput(journaled):
    from rafiki_tpu.obs.ledger import ledger

    before = ledger.snapshot()["total"].get("badput_s", 0.0)
    mon = HealthMonitor("t")
    _observe(mon, _healthy())  # banks some wall first
    v = mon.observe(_nan_epoch(), t0=time.monotonic() - 2.0, epoch_seed=0,
                    idx=None, poison=None, snapshot=None)
    assert v is not None and v["badput_s"] > 0.0
    after = ledger.snapshot()["total"].get("badput_s", 0.0)
    assert after - before == pytest.approx(v["badput_s"], abs=1e-3)
    assert health.stats()["badput_charged_s"] > 0.0


def test_tripped_member_not_reobserved():
    mon = HealthMonitor("t")
    assert _observe(mon, _nan_epoch()) is not None
    assert _observe(mon, _nan_epoch()) is None  # already contained
    assert health.stats()["divergences"] == 1


# -- in-graph sentinels -------------------------------------------------------


def test_sentinel_bundle_counts_nonfinite():
    import jax.numpy as jnp

    from rafiki_tpu.obs.health import sentinel

    grads = {"w": jnp.array([1.0, jnp.nan, jnp.inf]), "b": jnp.ones(2)}
    ups = {"w": jnp.ones(3), "b": jnp.ones(2)}
    b = sentinel.bundle(jnp.float32(0.5), grads, ups, ups)
    assert int(b["health_nonfinite"]) == 2
    b2 = sentinel.bundle(jnp.float32(jnp.nan), ups, ups, ups)
    assert int(b2["health_nonfinite"]) == 1  # the loss itself
    assert math.isfinite(float(b2["health_grad_norm"]))


def test_sentinel_reduce_epoch_locates_first_bad_step():
    import jax.numpy as jnp

    from rafiki_tpu.obs.health import sentinel

    nan = float("nan")
    series = {
        "health_grad_norm": jnp.array([1.0, 2.0, nan, 4.0]),
        "health_update_norm": jnp.array([0.1, 0.2, nan, 0.4]),
        "health_param_norm": jnp.array([9.0, 9.0, nan, nan]),
        "health_nonfinite": jnp.array([0, 0, 5, 3], dtype=jnp.int32),
    }
    out = {k: np.asarray(v) for k, v in sentinel.reduce_epoch(series).items()}
    assert int(out["health_bad_step"]) == 2
    assert int(out["health_bad_nonfinite"]) == 5
    assert int(out["health_nonfinite"]) == 8  # epoch total
    assert math.isnan(float(out["health_bad_grad_norm"]))
    # Clean series: bad_step sentinel is -1 and maxes are finite.
    clean = {k: jnp.nan_to_num(v) for k, v in series.items()}
    clean["health_nonfinite"] = jnp.zeros(4, jnp.int32)
    out = sentinel.reduce_epoch(clean)
    assert int(out["health_bad_step"]) == -1
    assert float(out["health_grad_norm"]) == 4.0  # max, not last


def test_sentinel_keys_stripped_from_metrics():
    """The JaxModel metrics contract predates the health plane: no
    ``health_*`` key may leak into the caller-visible epoch dict."""
    m = _ff()
    m.train(TRAIN)
    out = m._loop.run_epoch(m._prepared_dataset(TRAIN), m.batch_size,
                            epoch_seed=99)
    assert not any(k.startswith("health_") for k in out)
    assert "loss" in out
    m.destroy()


# -- serial containment + capsule replay --------------------------------------


def test_serial_divergence_fails_fast_with_capsule(journaled):
    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1"))
    m = _ff()
    with pytest.raises(DivergenceError) as ei:
        m.train(TRAIN)
    v = ei.value.verdict
    assert v["divergence"] == "nonfinite"
    assert v["bad_step"] == 2  # n_steps//2 of a 4-step epoch
    assert v["capsule"] is not None
    assert "non-finite" in str(ei.value)
    uninstall()

    from rafiki_tpu.obs.health import capsule

    cap = capsule.load(v["capsule"])
    assert cap["kind"] == "nonfinite" and cap["bad_step"] == 2
    assert cap["idx"].shape[0] == 3  # truncated at the bad step
    result = capsule.replay(v["capsule"])
    assert result["reproduced"], result["mismatches"]
    assert result["steps_replayed"] == 3
    assert result["poisoned"]
    m.destroy()


def test_divergence_journaled_and_flight_recorded(journaled):
    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1"))
    m = _ff()
    with pytest.raises(DivergenceError):
        m.train(TRAIN)
    uninstall()
    journal.close()
    from rafiki_tpu.obs import journal as journal_mod

    recs = journal_mod.read_dir(journaled)
    assert any(r.get("kind") == "health" and r.get("name") == "divergence"
               for r in recs)
    assert any(r.get("kind") == "health" and r.get("name") == "capsule"
               for r in recs)
    assert list(journaled.glob("flight-*.json"))
    m.destroy()


def test_clean_run_writes_no_capsules(journaled):
    m = _ff()
    m.train(TRAIN)
    assert not list(journaled.glob("capsule-*.rcap"))
    assert health.stats()["divergences"] == 0
    m.destroy()


# -- packed isolation ---------------------------------------------------------


def test_packed_member_divergence_isolated(journaled):
    """Member 2 of a k=4 pack diverges: it alone carries a verdict, and
    members 0/1/3 finish bit-identical to an unfaulted packed run."""
    from rafiki_tpu.model.base import JaxModel

    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1:match=@m2"))
    faulted = [_ff(seed=s) for s in range(4)]
    JaxModel.train_packed(faulted, TRAIN)
    uninstall()
    verdicts = [getattr(m, "_health_verdict", None) for m in faulted]
    assert [v is None for v in verdicts] == [True, True, False, True]
    assert verdicts[2]["divergence"] == "nonfinite"
    assert verdicts[2]["member"] == 2
    assert health.stats()["evictions"] == 1

    clean = [_ff(seed=s) for s in range(4)]
    JaxModel.train_packed(clean, TRAIN)
    for i in (0, 1, 3):
        assert _tree_bits_equal(faulted[i]._loop.params,
                                clean[i]._loop.params), f"member {i}"
    for m in faulted + clean:
        m.destroy()


def test_packed_capsule_replays_serially(journaled):
    """A packed member's capsule holds the member-sliced (serial-shape)
    state; its replay re-executes through a SERIAL program and must
    still reproduce bit-exactly — the pack/serial parity invariant is
    what makes cross-shape replay sound."""
    from rafiki_tpu.model.base import JaxModel

    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1:match=@m1"))
    models = [_ff(seed=s) for s in range(2)]
    JaxModel.train_packed(models, TRAIN)
    uninstall()
    caps = sorted(journaled.glob("capsule-*.rcap"))
    assert caps
    from rafiki_tpu.obs.health import capsule

    cap = capsule.load(caps[-1])
    assert cap["packed"] is True and cap["member"] == 1
    result = capsule.replay(caps[-1])
    assert result["reproduced"], result["mismatches"]
    for m in models:
        m.destroy()


# -- worker containment (serial + packed) -------------------------------------


class _ScriptedAdvisor:
    def __init__(self):
        self.fed = []

    def propose(self):
        return dict(hidden_layers=1, hidden_units=32, learning_rate=1e-3,
                    batch_size=32, epochs=2, seed=0)

    def propose_batch(self, n):
        return [self.propose() for _ in range(n)]

    def feedback(self, score, knobs):
        self.fed.append(round(float(score), 6))


def _mk_worker(tmp_path, n_trials, trial_pack=1):
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.worker.train import TrainWorker

    store = MetaStore(tmp_path / "meta.sqlite3")
    params = ParamsStore(tmp_path / "params")
    model = store.create_model("hff", "IMAGE_CLASSIFICATION", None,
                               b"", "FeedForward")
    job = store.create_train_job("app", "IMAGE_CLASSIFICATION", None,
                                 TRAIN, VAL,
                                 {"MODEL_TRIAL_COUNT": n_trials})
    sub = store.create_sub_train_job(job["id"], model["id"])
    adv = _ScriptedAdvisor()
    worker = TrainWorker(store, params, sub["id"], FeedForward, adv,
                         TRAIN, VAL, {"MODEL_TRIAL_COUNT": n_trials},
                         async_persist=False, trial_pack=trial_pack)
    return store, worker, adv, sub


def test_worker_serial_contains_divergence(tmp_path, journaled):
    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1"))
    store, worker, adv, sub = _mk_worker(tmp_path, n_trials=2)
    n = worker.run()
    uninstall()
    assert n == 2
    trials = store.get_trials_of_sub_train_job(sub["id"])
    statuses = sorted(t["status"] for t in trials)
    assert statuses == ["COMPLETED", "ERRORED"]
    bad = next(t for t in trials if t["status"] == "ERRORED")
    assert "diverged" in (bad["error"] or "")
    assert 0.0 in adv.fed  # floor score steered the advisor away
    assert health.stats()["contained"] == 1
    # The worker loop SURVIVED the divergence: trial 2 completed.
    good = next(t for t in trials if t["status"] == "COMPLETED")
    assert good["score"] is not None


def test_quiet_worker_round_gets_a_clean_bill(tmp_path, journaled, capsys):
    """The uninjected polarity, end to end: the sentinels are ON (they
    always are) but stay silent through a 2-trial serial worker round —
    no divergence, no capsule — ``obs health`` renders a clean bill and
    ``obs curves`` surfaces both trials' learning curves from the same
    journals."""
    from rafiki_tpu.obs import cli

    store, worker, adv, sub = _mk_worker(tmp_path, n_trials=2)
    assert worker.run() == 2
    journal.close()
    trials = store.get_trials_of_sub_train_job(sub["id"])
    assert [t["status"] for t in trials] == ["COMPLETED", "COMPLETED"]
    stats = health.stats()
    assert stats["divergences"] == 0 and stats["capsules"] == 0
    assert not list(journaled.glob("capsule-*.rcap"))

    assert cli.main(["--dir", str(journaled), "--json", "health"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert not report["divergences"] and not report["capsule_errors"]
    assert cli.main(["--dir", str(journaled), "--json", "curves"]) == 0
    curves = json.loads(capsys.readouterr().out)["trials"]
    assert len(curves) == 2 and all(len(v) >= 2 for v in curves.values())


def test_worker_packed_contains_divergence(tmp_path, journaled):
    from rafiki_tpu.model.knobs import knob_config_signature
    from rafiki_tpu.worker.train import PackedTrialRunner

    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1:match=@m1"))
    store, worker, adv, sub = _mk_worker(tmp_path, n_trials=2, trial_pack=2)
    knob_config = FeedForward.get_knob_config()
    rows = []
    for _ in range(2):
        kn = adv.propose()
        t = store.create_trial(sub["id"], "FeedForward", kn,
                               shape_sig=knob_config_signature(
                                   knob_config, kn),
                               budget_max=2)
        rows.append((t["id"], kn))
    n = PackedTrialRunner(worker, 2).run_assigned(rows, budget_max=2)
    uninstall()
    assert n == 2
    trials = {t["id"]: t for t in store.get_trials_of_sub_train_job(sub["id"])}
    t0, t1 = trials[rows[0][0]], trials[rows[1][0]]
    assert t0["status"] == "COMPLETED" and t0["score"] is not None
    assert t1["status"] == "ERRORED" and "diverged" in (t1["error"] or "")
    assert 0.0 in adv.fed
    assert health.stats()["contained"] == 1
    assert health.stats()["evictions"] == 1


# -- CLI surface --------------------------------------------------------------


def test_cli_health_curves_replay(tmp_path, journaled, capsys):
    from rafiki_tpu.obs import cli

    install(FaultPlane.from_spec("seed=3;train.nan:nan:times=1"))
    store, worker, adv, sub = _mk_worker(tmp_path, n_trials=2)
    worker.run()
    uninstall()
    journal.close()

    assert cli.main(["--dir", str(journaled), "health"]) == 0
    out = capsys.readouterr().out
    assert "divergences: 1" in out and "capsule" in out

    assert cli.main(["--dir", str(journaled), "curves"]) == 0
    out = capsys.readouterr().out
    assert "trial " in out and "epoch" in out

    caps = sorted(journaled.glob("capsule-*.rcap"))
    assert cli.main(["replay", str(caps[-1])]) == 0
    out = capsys.readouterr().out
    assert "reproduced" in out

    # Empty dir: health reports a clean bill (exit 0), curves miss (1).
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli.main(["--dir", str(empty), "health"]) == 0
    assert "clean" in capsys.readouterr().out
    assert cli.main(["--dir", str(empty), "curves"]) == 1


def test_cli_replay_rejects_garbage(tmp_path, capsys):
    from rafiki_tpu.obs import cli

    bad = tmp_path / "not-a-capsule.rcap"
    bad.write_bytes(b"\x80\x04N.")  # pickled None
    assert cli.main(["replay", str(bad)]) == 2
