"""Sharded-trial lane (docs/sharding.md): plan math, the width-
invariance contract of ShardedTrainLoop, and reshard-on-restore.

The load-bearing invariant everything downstream leans on (the chaos
scenario's unfaulted-run comparison, the GroupHandle re-form path):
the sharded loop is BIT-IDENTICAL to the serial loop at any width —
gather → serial scan body → reslice commutes with the sharding. These
tests pin that, plus the checkpoint manifest's failure modes: a
missing chunk and a doctored wrong-width chunk must fail loudly,
naming the chunk.
"""

import json
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rafiki_tpu.shard import (ShardPlan, ShardedTrainLoop, gather_state,
                              is_manifest, restore_sharded, save_sharded,
                              shard_axis, solve_width)
from rafiki_tpu.store.params import ParamsStore

BATCH = 8
EPOCHS = 2
SEED = 3


# ---------------------------------------------------------------------------
# plan math
# ---------------------------------------------------------------------------


def test_shard_axis_is_largest_divisible_axis():
    assert shard_axis((16, 4), 2) == 0
    assert shard_axis((4, 16), 2) == 1
    assert shard_axis((6, 8), 4) == 1     # 6 % 4 != 0
    assert shard_axis((3, 5), 2) is None  # nothing divisible
    assert shard_axis((), 2) is None      # scalar replicates
    assert shard_axis((16,), 1) is None   # width 1 shards nothing


def test_solve_width_smallest_power_of_two_under_ceiling(monkeypatch):
    from rafiki_tpu.obs.twin.calibration import HBM_BYTES_PER_CHIP

    monkeypatch.delenv("RAFIKI_SHARD_WIDTH", raising=False)
    assert solve_width(int(0.5 * HBM_BYTES_PER_CHIP)) == 1
    assert solve_width(int(1.5 * HBM_BYTES_PER_CHIP)) == 2
    assert solve_width(int(3.0 * HBM_BYTES_PER_CHIP)) == 4
    # the cap clamps even when the estimate wants more
    assert solve_width(int(100 * HBM_BYTES_PER_CHIP), cap=4) == 4
    # the env pin overrides the solve entirely
    monkeypatch.setenv("RAFIKI_SHARD_WIDTH", "2")
    assert solve_width(int(100 * HBM_BYTES_PER_CHIP)) == 2


def test_plan_specs_follow_the_axis_rule():
    from jax.sharding import PartitionSpec as P

    plan = ShardPlan(width=2, family="t")
    assert plan.spec_of((16, 4)) == P("shard")
    assert plan.spec_of((4, 16)) == P(None, "shard")
    assert plan.spec_of(()) == P()
    tree = {"w": jax.ShapeDtypeStruct((16, 4), jnp.float32),
            "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
    specs = plan.spec_tree(tree)
    assert specs["w"] == P("shard") and specs["b"] == P()


# ---------------------------------------------------------------------------
# the lane: width invariance + reshard round-trips
# ---------------------------------------------------------------------------


class _DS:
    def __init__(self, n=64, d=8, classes=4, seed=0):
        rng = np.random.default_rng(seed)
        self.x = rng.normal(size=(n, d)).astype(np.float32)
        self.y = rng.integers(0, classes, size=(n,)).astype(np.int32)
        self.size = n
        self.mask = None


def _loop_fns():
    import flax.linen as nn
    import optax

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))

    m = Mlp()

    def init_fn(rng):
        return m.init(rng, jnp.zeros((1, 8), jnp.float32))

    def apply_fn(p, x):
        return m.apply(p, x)

    def loss_fn(p, batch, rng=None):
        logits = apply_fn(p, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, {"acc": (logits.argmax(-1) == batch["y"]).mean()}

    return init_fn, apply_fn, loss_fn


def _flat(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        gather_state(state))]


def _bitmatch(a, b):
    la, lb = _flat(a), _flat(b)
    assert len(la) == len(lb)
    return all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def lane():
    """Loops at widths 1/2/4 plus the serial reference, all trained
    EPOCHS epochs on the same data/seed (one fixture — the compiles
    dominate, so every test shares them)."""
    init_fn, apply_fn, loss_fn = _loop_fns()
    ds = _DS()
    devs = jax.devices()
    loops = {}
    for w in (1, 2, 4):
        loop = ShardedTrainLoop(
            init_fn, apply_fn, loss_fn, devices=devs[:w], seed=SEED,
            plan=ShardPlan(width=w, family="mlp"),
            program_key=("test_shard", "mlp"))
        for ep in range(EPOCHS):
            metrics = loop.run_epoch(ds, BATCH, epoch_seed=SEED + ep)
        loops[w] = (loop, metrics)
    from rafiki_tpu.ops.train import TrainLoop

    serial = TrainLoop(init_fn, apply_fn, loss_fn, seed=SEED,
                       program_key=("test_shard", "mlp"))
    for ep in range(EPOCHS):
        serial_metrics = serial.run_epoch(ds, BATCH, epoch_seed=SEED + ep)
    return {"loops": loops, "serial": serial,
            "serial_metrics": serial_metrics, "ds": ds}


def test_width1_loop_is_byte_identical_to_serial(lane):
    loop, metrics = lane["loops"][1]
    assert metrics["loss"] == lane["serial_metrics"]["loss"]
    assert _bitmatch(loop.state, lane["serial"].state)


@pytest.mark.parametrize("width", [2, 4])
def test_wider_groups_bitmatch_width1(lane, width):
    loop1, m1 = lane["loops"][1]
    loopw, mw = lane["loops"][width]
    assert mw["loss"] == m1["loss"]
    assert _bitmatch(loopw.state, loop1.state)


@pytest.mark.parametrize("from_w,to_w", [(1, 2), (2, 1), (2, 4)])
def test_reshard_roundtrip_bitmatches(lane, from_w, to_w):
    src, _ = lane["loops"][from_w]
    dst, _ = lane["loops"][to_w]
    with tempfile.TemporaryDirectory() as d:
        store = ParamsStore(d)
        save_sharded(store, "t1", EPOCHS - 1, src.state, src.width)
        epoch, blob = store.latest_checkpoint("t1")
        assert epoch == EPOCHS - 1 and is_manifest(blob)
        restored = restore_sharded(store, blob, dst.state, dst.mesh,
                                   dst.plan)
    assert _bitmatch(restored, src.state)


def test_obs_shard_reads_a_freshly_journaled_lane_run(tmp_path, capsys):
    """The forensic reader against records the lane itself just wrote: a
    journaled plan / train / save / reshard-on-restore sequence, then
    ``obs shard`` over the directory (exit 0, the plan and the reshard
    among its rows); an empty directory is exit 1, not a silent pass."""
    from rafiki_tpu.obs.cli import cmd_shard
    from rafiki_tpu.obs.journal import journal

    init_fn, apply_fn, loss_fn = _loop_fns()
    ds, devs = _DS(), jax.devices()
    log_dir = tmp_path / "obs"
    journal.configure(log_dir, role="test")
    try:
        loops = {}
        for w in (2, 4):
            plan = ShardPlan(width=w, family="mlp")
            plan.note()
            loops[w] = ShardedTrainLoop(
                init_fn, apply_fn, loss_fn, devices=devs[:w], seed=SEED,
                plan=plan, program_key=("test_shard", "mlp"))
            loops[w].run_epoch(ds, BATCH, epoch_seed=SEED)
        store = ParamsStore(str(tmp_path / "params"))
        save_sharded(store, "a", 0, loops[2].state, 2)
        _epoch, blob = store.latest_checkpoint("a")
        restored = restore_sharded(store, blob, loops[4].state,
                                   loops[4].mesh, loops[4].plan)
        assert _bitmatch(restored, loops[2].state)
    finally:
        journal.close()
    assert cmd_shard(str(log_dir), as_json=True) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.strip()]
    assert {"plan", "reshard"} <= {r.get("name") for r in rows}
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cmd_shard(str(empty), as_json=True) == 1


def test_missing_chunk_fails_naming_the_chunk(lane):
    src, _ = lane["loops"][2]
    with tempfile.TemporaryDirectory() as d:
        store = ParamsStore(d)
        save_sharded(store, "t1", 0, src.state, 2)
        _epoch, blob = store.latest_checkpoint("t1")
        man = json.loads(blob.decode())
        man["shards"][1] = "t1_ckpt_0_s1of2_GONE"
        doctored = json.dumps(man).encode()
        with pytest.raises(IOError, match="t1_ckpt_0_s1of2_GONE"):
            restore_sharded(store, doctored, src.state, src.mesh,
                            src.plan)


def test_doctored_wrong_width_chunk_is_caught(lane):
    # A width-4 chunk spliced into a width-2 manifest: every sharded
    # leaf in it is a 1/4 slice where the manifest promises 1/2 — the
    # reader must refuse, naming the chunk.
    src2, _ = lane["loops"][2]
    src4, _ = lane["loops"][4]
    with tempfile.TemporaryDirectory() as d:
        store = ParamsStore(d)
        save_sharded(store, "a", 0, src2.state, 2)
        save_sharded(store, "b", 0, src4.state, 4)
        _epoch, blob = store.latest_checkpoint("a")
        man = json.loads(blob.decode())
        man["shards"][0] = "b_ckpt_0_s0of4"
        doctored = json.dumps(man).encode()
        with pytest.raises(IOError, match="b_ckpt_0_s0of4"):
            restore_sharded(store, doctored, src2.state, src2.mesh,
                            src2.plan)


def test_inconsistent_manifest_width_is_refused(lane):
    from rafiki_tpu.shard import load_manifest

    src, _ = lane["loops"][2]
    with tempfile.TemporaryDirectory() as d:
        store = ParamsStore(d)
        save_sharded(store, "t1", 0, src.state, 2)
        _epoch, blob = store.latest_checkpoint("t1")
        man = json.loads(blob.decode())
        man["width"] = 3  # claims 3, lists 2 chunks
        with pytest.raises(IOError, match="wrong-width"):
            load_manifest(json.dumps(man).encode())
        with pytest.raises(IOError, match="wrong format"):
            load_manifest(b'{"format": "not-a-manifest"}')


def test_serial_checkpoints_are_not_mistaken_for_manifests():
    assert not is_manifest(b"\x80\x05...pickled")
    assert not is_manifest(b'{"format": "other"}')
