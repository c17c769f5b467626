"""A dump goes from the fetched leaves to its file in one pass (ISSUE 34).

What must hold, each by a test here:

  * the file: ``<64 hex>\\n<pickle>``, the pickle loads to ``{"arch",
    "packed", "dataset_meta"}`` with ``"packed"`` one RTPK1 ``bytes``
    equal, byte for byte, to ``dump_pytree`` of the tree: the streamed
    road and the bytes road write the SAME file; blobs the parent wrote
    load unchanged; the benchmark's walk reads it;
  * the guarantees: sha256 over the whole blob, verified on load;
    ``fsync`` with the digest in place, then ``os.replace``, then the
    trial's row; a failed write leaves no ``.params``, no ``.tmp``, the
    trial ``ERRORED`` and the saver's thread alive;
  * the counters, and that no copy of the blob is made on the way
    (``tracemalloc``: the test that fails if one creeps back).
"""

import hashlib
import io
import json
import os
import pickle
import pickletools
import tracemalloc
import types

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rafiki_tpu import telemetry
from rafiki_tpu.chaos import ChaosError, FaultPlane, install, uninstall
from rafiki_tpu.model.base import _portable_meta, load_model_class
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.utils import serial
from rafiki_tpu.utils.serial import (
    MAGIC, StackedHostCopy, dump_pytree, load_pytree, parts_nbytes,
    pickled_dict_parts, pytree_parts)
from rafiki_tpu.worker.train import TrainWorker, save_parameters

from tests.test_scheduler import FF_SOURCE, TRAIN, VAL
from tests.test_trial_pack import _ScriptedAdvisor

BF16 = ml_dtypes.bfloat16
SMALL = {"arch": (5, (8, 8, 3)), "dataset_meta": {"classes": ["a", "b", "a"], "n": 3}}


def _rng(shape, dtype, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(dtype)


def _stacked_member():
    """Member 2 of a pack round's stacked host copy: ``a[i, ...]`` views,
    a 0-d one among them."""
    stacked = {"w": _rng((4, 6, 5), np.float32).astype(BF16),
               "b": _rng((4, 5), np.float32, 1).astype(BF16),
               "t": np.arange(4, dtype=np.int32)}
    copy = StackedHostCopy(stacked, cast_f32_to_bf16=False)
    return copy.member(2)


# name -> (tree, cast_f32_to_bf16)
TREES = {
    "bfloat16": lambda: ({"k": _rng((7, 3), np.float32).astype(BF16),
                          "d": {"b": np.ones((3,), BF16)}}, False),
    "float32_kept": lambda: ({"k": _rng((7, 3), np.float32)}, False),
    "float32_cast": lambda: ({"k": jnp.asarray(_rng((7, 3), np.float32)),
                              "i": jnp.arange(5, dtype=jnp.int32)}, True),
    "integers": lambda: ({"i8": np.arange(-3, 3, dtype=np.int8),
                          "u32": np.arange(4, dtype=np.uint32),
                          "i64": np.arange(3, dtype=np.int64)}, False),
    "zero_d": lambda: ({"step": np.int32(17), "scale": np.float32(2.5),
                        "s": np.asarray(1.5, BF16)}, False),
    "empty_leaves": lambda: ({"e": np.zeros((0, 4), np.float32),
                              "z": np.zeros((3, 0), BF16),
                              "w": np.ones((2,), np.float32)}, False),
    "empty_tree": lambda: ({}, True),
    "member_view": lambda: (_stacked_member(), False),
    "not_contiguous": lambda: ({"t": _rng((6, 4), np.float32).T,
                                "s": _rng((8, 8), np.float32, 2)[::2, 1::3]}, False),
    "device_tuple_state": lambda: (({"k": jnp.ones((3, 3), jnp.bfloat16)},
                                    jnp.zeros((), jnp.int32)), True),
}


def _parents_dump_pytree(tree, cast):
    """The RTPK1 bytes as the parent commit made them: ``tobytes`` of every
    leaf, joined. The layout's oracle, kept apart from ``pytree_parts``."""
    if cast:
        tree = serial._cast_tree_bf16(tree)
    spec, raw = [], []
    for k, v in serial._flat_items(tree):
        if not (isinstance(v, np.ndarray)
                and v.dtype == jax.dtypes.canonicalize_dtype(v.dtype)):
            v = jnp.asarray(v)
        a = np.ascontiguousarray(np.asarray(v))
        spec.append({"k": k, "shape": list(v.shape), "dtype": v.dtype.name})
        raw.append(a.tobytes())
    header = json.dumps(spec).encode()
    return b"".join([MAGIC, len(header).to_bytes(8, "little"), header] + raw)


def _parents_file(blob):
    """The stored file as the parent's ``ParamsStore.save`` wrote it."""
    return hashlib.sha256(blob).hexdigest().encode() + b"\n" + blob


def _walk(blob):
    """``benchmark/check.py::parse_params_blob``'s walk, copied: that file
    is the benchmark's and reads what this PR writes."""
    payload = pickle.loads(blob)
    raw = payload["packed"]
    assert raw[: len(MAGIC)] == MAGIC
    off = len(MAGIC)
    hlen = int.from_bytes(raw[off: off + 8], "little")
    off += 8
    spec = json.loads(raw[off: off + hlen].decode())
    off += hlen
    out = {}
    for ent in spec:
        dt = (np.dtype(BF16) if ent["dtype"] == "bfloat16"
              else np.dtype(ent["dtype"]))
        shape = tuple(ent["shape"])
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        out[ent["k"]] = np.frombuffer(raw, dtype=dt, count=n, offset=off
                                      ).reshape(shape).astype(np.float32)
        off += n * dt.itemsize
    assert off == len(raw)
    return out


# -- the layout: one definition, two roads ---------------------------------------


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_parts_join_to_the_parents_rtpk1_bytes(name):
    tree, cast = TREES[name]()
    want = _parents_dump_pytree(tree, cast)
    parts = pytree_parts(tree, cast_f32_to_bf16=cast)
    assert b"".join(parts) == want
    assert dump_pytree(tree, cast_f32_to_bf16=cast) == want
    assert parts_nbytes(parts) == len(want)
    # a leaf's part is its own memory, flat and read-only
    for p in parts[3:]:
        assert isinstance(p, memoryview) and p.readonly and p.ndim == 1 \
            and p.contiguous and p.itemsize == 1


def test_a_host_leaf_that_is_contiguous_is_not_copied():
    member = _stacked_member()
    parts = pytree_parts(member, cast_f32_to_bf16=False)
    leaves = [v for _k, v in serial._flat_items(member)]
    for leaf, part in zip(leaves, parts[3:]):
        assert np.shares_memory(np.frombuffer(part, np.uint8), leaf) \
            or leaf.size == 0


@pytest.mark.parametrize("name", sorted(TREES))
def test_a_streamed_file_is_the_file_the_bytes_road_writes(tmp_path, name):
    tree, cast = TREES[name]()
    packed = dump_pytree(tree, cast_f32_to_bf16=cast)
    store = ParamsStore(tmp_path)
    parts = pickled_dict_parts(SMALL, "packed",
                               pytree_parts(tree, cast_f32_to_bf16=cast))
    sid = store.save_parts(parts)
    bid = store.save(b"".join(parts))
    streamed = (tmp_path / f"{sid}.params").read_bytes()
    assert streamed == (tmp_path / f"{bid}.params").read_bytes()
    blob = store.load(sid)                  # the digest verified, as ever
    assert streamed == _parents_file(blob)  # sha256 of the whole, at the head
    # what pickle.loads makes of the opcodes written by hand: the parent's dict
    got = pickle.loads(blob)
    assert got == {**SMALL, "packed": packed}
    assert type(got["packed"]) is bytes and set(got) == {
        "arch", "packed", "dataset_meta"}
    assert got == pickle.loads(pickle.dumps({
        "arch": SMALL["arch"], "packed": packed,
        "dataset_meta": SMALL["dataset_meta"]}))
    pickletools.dis(blob, out=io.StringIO())     # a well-formed pickle
    # the benchmark's walk and the program's loader read it
    walked = _walk(blob)
    loaded = load_pytree(got["packed"])
    flat = dict(serial._flat_items(loaded)) if loaded else {}
    assert set(walked) == set(flat)
    for k, v in flat.items():
        np.testing.assert_array_equal(walked[k], np.asarray(v, np.float32))


def test_the_small_entries_keep_their_memo_and_a_long_key_its_length():
    shared = "x" * 300
    small = {"arch": (3, (4,)), "dataset_meta": {"a": [shared, shared],
                                                 "b": {"c": shared}}}
    value = [b"ab", memoryview(b"cd"), b""]
    blob = b"".join(pickled_dict_parts(small, "k" * 70000, value))
    assert pickle.loads(blob) == {**small, "k" * 70000: b"abcd"}
    assert pickle.loads(b"".join(pickled_dict_parts({}, "packed", []))) == {
        "packed": b""}


# -- a model's blob, both roads ------------------------------------------------------


@pytest.fixture()
def stored_dtype(request):
    from rafiki_tpu.config import Config, get_config, set_config

    prev = get_config()
    set_config(Config(data_dir=prev.data_dir,
                      serving_params_dtype=request.param))
    yield request.param
    set_config(prev)


@pytest.fixture(scope="module")
def trained():
    cls = load_model_class(FF_SOURCE, "TinyFF")
    m = cls(hidden_units=16, learning_rate=1e-2, batch_size=32, epochs=1)
    m.train(TRAIN)
    return cls, m


def _parents_blob(m, cast):
    """``JaxModel.dump_parameters`` as the parent commit had it."""
    return pickle.dumps({
        "arch": m._arch,
        "packed": dump_pytree(m._loop.params, cast_f32_to_bf16=cast),
        "dataset_meta": _portable_meta(m._dataset_meta)})


@pytest.mark.parametrize("stored_dtype", ["bfloat16", "float32"], indirect=True)
def test_a_models_streamed_file_loads_as_the_parents(tmp_path, trained,
                                                     stored_dtype):
    cls, m = trained
    store = ParamsStore(tmp_path)
    parent = _parents_blob(m, stored_dtype == "bfloat16")
    sid = save_parameters(store, m)
    blob = store.load(sid)
    assert blob == m.dump_parameters() == b"".join(m.dump_parameter_parts())
    assert pickle.loads(blob) == pickle.loads(parent)
    assert {a.dtype.name for a in _leaves(load_pytree(
        pickle.loads(blob)["packed"]))} == {stored_dtype}
    # a file the parent wrote still loads, to the same parameters
    (tmp_path / "old.params").write_bytes(_parents_file(parent))
    assert store.load("old") == parent
    # ... and one written by save(dump_parameters()), the API every other caller has
    bid = store.save(m.dump_parameters())
    x = _rng((16, 8, 8, 1), np.float32, 3)
    probs = []
    for pid in (sid, "old", bid):
        fresh = cls(**m.knobs)
        fresh.load_parameters(store.load(pid))
        probs.append(fresh.predict_proba(x))
    np.testing.assert_array_equal(probs[0], probs[1])
    np.testing.assert_array_equal(probs[0], probs[2])
    assert set(_walk(blob)) == set(_walk(parent))
    for k, v in _walk(parent).items():
        np.testing.assert_array_equal(_walk(blob)[k], v)


def _leaves(tree):
    return jax.tree.leaves(tree)


# -- the guarantees --------------------------------------------------------------------


def test_the_file_is_synced_whole_before_it_is_renamed(tmp_path, monkeypatch):
    store = ParamsStore(tmp_path)
    seen = []
    real_fsync, real_replace = os.fsync, os.replace

    def fsync(fd):
        # what is on its way to the disk is the finished file: digest and all
        tmp = tmp_path / "p1.tmp"
        data = tmp.read_bytes()
        digest, blob = data.split(b"\n", 1)
        seen.append(("fsync", hashlib.sha256(blob).hexdigest().encode() == digest,
                     (tmp_path / "p1.params").exists()))
        return real_fsync(fd)

    def replace(src, dst):
        seen.append(("replace", os.path.basename(src), os.path.basename(dst)))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(os, "replace", replace)
    store.save_parts([b"abc", memoryview(b"defg"), b"h" * 100000], "p1")
    assert seen == [("fsync", True, False), ("replace", "p1.tmp", "p1.params")]
    assert store.load("p1") == b"abcdefg" + b"h" * 100000
    assert sorted(p.name for p in tmp_path.iterdir()) == ["p1.params"]


def _serial_worker(tmp_path, n_trials, params, async_persist=True, src=FF_SOURCE,
                   name="TinyFF"):
    store = MetaStore(tmp_path / "meta.sqlite3")
    row = store.create_model("m", "IMAGE_CLASSIFICATION", None, src, name)
    job = store.create_train_job("app", "IMAGE_CLASSIFICATION", None, TRAIN, VAL,
                                 {"MODEL_TRIAL_COUNT": n_trials})
    sub = store.create_sub_train_job(job["id"], row["id"])
    adv = _ScriptedAdvisor(dict(hidden_units=16, batch_size=32, epochs=1))
    worker = TrainWorker(store, params, sub["id"], load_model_class(src, name),
                         adv, TRAIN, VAL, {"MODEL_TRIAL_COUNT": n_trials},
                         async_persist=async_persist)
    return store, worker, sub


def _rows(store, sub):
    return sorted(store.get_trials_of_sub_train_job(sub["id"]),
                  key=lambda t: t["started_at"])


def test_the_trials_row_comes_after_the_rename(tmp_path, monkeypatch):
    params = ParamsStore(tmp_path / "params")
    store, worker, sub = _serial_worker(tmp_path, 1, params, async_persist=False)
    order = []
    real_fsync, real_replace = os.fsync, os.replace
    real_mark = store.mark_trial_as_completed

    def fsync(fd):
        order.append("fsync")
        return real_fsync(fd)

    def replace(src, dst):
        if str(dst).endswith(".params"):
            order.append("replace")
        return real_replace(src, dst)

    def mark(tid, score, params_id):
        # the row names parameters that are there, whole
        assert params.exists(params_id) and params.load(params_id)
        order.append("mark")
        return real_mark(tid, score, params_id)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(os, "replace", replace)
    monkeypatch.setattr(store, "mark_trial_as_completed", mark)
    assert worker.run() == 1
    assert [t["status"] for t in _rows(store, sub)] == ["COMPLETED"]
    assert order == ["fsync", "replace", "mark"]


@pytest.mark.parametrize("nth", [0, 2, 5])
def test_a_write_that_raises_at_the_nth_part_leaves_nothing_behind(tmp_path, nth):
    store = ParamsStore(tmp_path)
    tree, cast = TREES["bfloat16"]()
    parts = pickled_dict_parts(SMALL, "packed", pytree_parts(tree, cast))

    def until():
        for i, p in enumerate(parts):
            if i == nth:
                raise OSError("disk full")
            yield p

    with pytest.raises(OSError, match="disk full"):
        store.save_parts(until(), "p1")
    assert list(tmp_path.iterdir()) == [] and not store.exists("p1")
    # a part that is no buffer at all
    with pytest.raises(TypeError):
        store.save_parts([b"ok", 7], "p2")
    assert list(tmp_path.iterdir()) == []
    # ... and an older file under the id stays as it was
    store.save(b"older", "p1")
    with pytest.raises(OSError):
        store.save_parts(until(), "p1")
    assert store.load("p1") == b"older"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["p1.params"]


def test_the_chaos_hook_fires_once_a_save_keyed_by_the_id_before_any_write(tmp_path):
    store = ParamsStore(tmp_path)
    plane = FaultPlane.from_spec("store.params_write:error:match=_ckpt_")
    install(plane)
    try:
        assert store.load(store.save_parts([b"a", b"b"], "final")) == b"ab"
        with pytest.raises(ChaosError):
            store.save_parts([b"a", b"b"], "t1_ckpt_3")
        with pytest.raises(ChaosError):
            store.save_checkpoint("t1", 4, b"ab")
    finally:
        uninstall()
    assert sorted(p.name for p in tmp_path.iterdir()) == ["final.params"]


class _FailsSecond(ParamsStore):
    """The second save's write raises part-way, inside the store's own pass."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.saves = 0

    def save_parts(self, parts, params_id=None):
        self.saves += 1
        if self.saves != 2:
            return super().save_parts(parts, params_id)

        def until():
            for i, p in enumerate(parts):
                if i == 3:
                    raise OSError("disk full")
                yield p

        return super().save_parts(until(), params_id)


@pytest.mark.parametrize("fault", ["nth_part", "chaos"])
def test_a_failed_write_errors_its_trial_and_the_saver_lives_on(tmp_path, fault):
    params = (_FailsSecond if fault == "nth_part" else ParamsStore)(
        tmp_path / "params")
    store, worker, sub = _serial_worker(tmp_path, 3, params)
    if fault == "chaos":
        install(FaultPlane.from_spec("store.params_write:error:after=1:times=1"))
    try:
        assert worker.run() == 3
    finally:
        uninstall()
    rows = _rows(store, sub)
    assert [t["status"] for t in rows] == ["COMPLETED", "ERRORED", "COMPLETED"]
    assert rows[1]["error"].startswith("params persist failed")
    assert not rows[1].get("params_id")
    # the third was written by the same thread, after the failure
    written = sorted(p.name for p in (tmp_path / "params").iterdir())
    assert written == sorted(f"{t['params_id']}.params" for t in (rows[0], rows[2]))
    for t in (rows[0], rows[2]):
        assert pickle.loads(params.load(t["params_id"]))["packed"][:6] == MAGIC


@pytest.mark.parametrize("damage", ["last_byte_cut", "half_cut", "digest_cut",
                                    "payload_flip", "digest_flip", "head_flip"])
def test_a_torn_or_flipped_file_fails_the_check(tmp_path, damage):
    store = ParamsStore(tmp_path)
    tree, cast = TREES["bfloat16"]()
    sid = store.save_parts(pickled_dict_parts(SMALL, "packed",
                                              pytree_parts(tree, cast)))
    path = tmp_path / f"{sid}.params"
    data = bytearray(path.read_bytes())
    assert store.load(sid)
    if damage == "last_byte_cut":
        data = data[:-1]
    elif damage == "half_cut":
        data = data[: len(data) // 2]
    elif damage == "digest_cut":
        data = data[:40]
    else:
        at = {"payload_flip": len(data) - 10, "digest_flip": 5,
              "head_flip": 66}[damage]
        data[at] ^= 0x01
    path.write_bytes(bytes(data))
    with pytest.raises((IOError, ValueError)):
        store.load(sid)


# -- the counters -------------------------------------------------------------------------


def _persist_counters():
    c = telemetry.snapshot()["counters"]
    return {k: c.get(f"persist.{k}", 0.0) for k in
            ("streamed", "buffered", "host_copy_bytes", "blob_bytes")}


def _delta(before):
    after = _persist_counters()
    return {k: after[k] - before[k] for k in after}


class _BytesOnly:
    """A model that is no ``JaxModel``: it has only the bytes."""

    def dump_parameters(self):
        return b"params"


def test_the_counters_say_which_road_and_what_it_copied(tmp_path, trained):
    cls, m = trained
    store = ParamsStore(tmp_path)
    before = _persist_counters()
    sid = save_parameters(store, m)
    payload = len(pickle.loads(store.load(sid))["packed"])
    assert _delta(before) == {"streamed": 1, "buffered": 0,
                              "host_copy_bytes": 0, "blob_bytes": payload}
    # the bytes API makes one copy, the join, and says so
    before = _persist_counters()
    blob = m.dump_parameters()
    assert _delta(before) == {"streamed": 0, "buffered": 0,
                              "host_copy_bytes": len(blob), "blob_bytes": payload}
    # a model that offers no parts
    before = _persist_counters()
    assert store.load(save_parameters(store, _BytesOnly())) == b"params"
    assert _delta(before) == {"streamed": 0, "buffered": 1,
                              "host_copy_bytes": 0, "blob_bytes": 0}

    # a template with a dump of its own: its bytes are the blob
    class Wrapped(cls):
        def dump_parameters(self):
            return b"v2:" + super().dump_parameters()

    w = Wrapped(**m.knobs)
    w._loop, w._arch, w._dataset_meta = m._loop, m._arch, m._dataset_meta
    assert w.dump_parameter_parts() is None
    before = _persist_counters()
    assert store.load(save_parameters(store, w)) == b"v2:" + blob
    d = _delta(before)
    assert (d["streamed"], d["buffered"], d["host_copy_bytes"]) == (0, 1, len(blob))


def test_a_worker_streams_every_dump_one_write_record_a_trial(tmp_path):
    params = ParamsStore(tmp_path / "params")
    store, worker, sub = _serial_worker(tmp_path, 2, params)
    telemetry.reset()
    assert worker.run() == 2
    rows = _rows(store, sub)
    payload = sum(len(pickle.loads(params.load(t["params_id"]))["packed"])
                  for t in rows)
    assert _persist_counters() == {"streamed": 2, "buffered": 0,
                                   "host_copy_bytes": 0, "blob_bytes": payload}
    by_name = {}
    for r in telemetry.span_records():
        if r["name"].startswith("persist."):
            assert r["thread"] == f"saver-{worker.worker_id}"
            by_name[r["name"]] = by_name.get(r["name"], 0) + 1
    assert by_name == {"persist.fetch": 2, "persist.write": 2, "persist.mark": 2}


# -- no copy of the blob on the way ------------------------------------------------------


def _staged_model(n_bytes):
    """A serial trial whose state was let go for its staged host copy
    (``JaxModel.release_train_state``), at a size worth measuring."""
    leaves = 8
    tree = {f"layer_{i}": {"w": np.full((n_bytes // 2 // leaves,), i, np.uint16
                                        ).view(BF16)} for i in range(leaves)}
    cls = load_model_class(FF_SOURCE, "TinyFF")
    m = cls(hidden_units=16, learning_rate=1e-2, batch_size=32, epochs=1)
    copy = StackedHostCopy(tree, cast_f32_to_bf16=False)
    copy.fetch()
    m._loop = types.SimpleNamespace(host_copy=copy)
    m._arch, m._dataset_meta = (5, (8, 8, 3)), {}
    return m


def _peak_over(f):
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        f()
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_persisting_64_mb_allocates_no_copy_of_the_blob(tmp_path):
    blob_bytes = 64 * 2**20
    m = _staged_model(blob_bytes)
    store = ParamsStore(tmp_path)
    ids = []
    streamed = _peak_over(lambda: ids.append(save_parameters(store, m)))
    # the instrument sees a copy where one is made: the bytes road's join
    buffered = _peak_over(lambda: ids.append(store.save(m.dump_parameters())))
    # ... and the parent's road (tobytes, join, pickle, digest + blob) read four
    leaves = [v for _k, v in serial._flat_items(m._loop.host_copy.member(None))]

    def parents():
        packed = b"".join([v.tobytes() for v in leaves])
        ids.append(_parents_file(pickle.dumps({"packed": packed})))

    parent = _peak_over(parents)
    assert streamed < 0.1 * blob_bytes, streamed / blob_bytes
    assert 0.9 * blob_bytes < buffered < 1.5 * blob_bytes, buffered / blob_bytes
    assert parent > 3.0 * blob_bytes, parent / blob_bytes
    assert store.load(ids[0]) == store.load(ids[1])
    assert len(pickle.loads(store.load(ids[0]))["packed"]) > blob_bytes
