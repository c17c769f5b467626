"""Set-up seen from inside (ISSUE 35).

  * ``telemetry.record_span``: a finished phase that someone else timed is
    a span record like any other, a child of the span open on its thread;
    phases under a millisecond fold into one record of the open span;
  * ``ops/train.py`` turns jax's compile stages into such records, named by
    function, once a process however often it is reloaded, and says what
    the persistent cache did;
  * a serial trial and a packed round through ``LocalScheduler``: the first
    of a program leaves ``compile.*``, ``data.*`` and (serial) ``train.init``
    records, the second leaves none of the first two and its epoch span
    says ``compile_s`` 0.0.
"""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from sweep_common import run_sweep

from rafiki_tpu import telemetry
from rafiki_tpu.telemetry.spans import Tracer

REPO = Path(__file__).resolve().parents[1]
STAGES = ("compile.trace", "compile.lower", "compile.backend")

# -- the write call -------------------------------------------------------------


def test_record_span_keeps_the_record_shape_of_a_span():
    tracer = Tracer()
    with tracer.span("trial.train", trial_id="t"):
        with tracer.span("train.epoch", leaf=True, steps=2) as sp:
            before_ts, before = time.time(), time.monotonic()
            tracer.record_span("compile.lower", 0.25, fun="jit(f)")
            after_ts, after = time.time(), time.monotonic()
    lower, epoch, train = tracer.records()
    assert set(lower) == set(train)          # same keys as a plain span's
    assert set(epoch) - set(lower) == {"leaf"}
    assert lower["name"] == "compile.lower" and lower["dur_s"] == 0.25
    assert lower["tags"] == {"fun": "jit(f)"}
    # start = now - duration on both clocks; the end is the call's moment
    assert before - 0.25 <= lower["mono"] <= after - 0.25
    assert before_ts - 0.25 <= lower["ts"] <= after_ts - 0.25
    # a child of the span open on its thread, never a leaf
    assert lower["parent"] == "train.epoch"
    assert lower["parent_id"] == sp.span_id == epoch["span_id"]
    assert lower["thread"] == threading.current_thread().name
    agg = tracer.summary()["compile.lower"]
    assert agg == {"count": 1, "total_s": 0.25, "min_s": 0.25, "max_s": 0.25}


def test_record_span_outside_any_span_has_no_parent():
    tracer = Tracer()
    tracer.record_span("compile.trace", 0.002, "compile.small", fun="f")
    tracer.record_span("compile.trace", 0.0002, "compile.small", fun="g")
    a, b = tracer.records()       # nothing open: nothing to fold into
    assert a["parent"] is None and a["parent_id"] is None
    assert b["dur_s"] == 0.0002 and b["tags"] == {"fun": "g"}


def test_phases_under_a_millisecond_fold_into_one_record_of_the_open_span():
    tracer = Tracer()
    with tracer.span("trial_pack.init", leaf=True) as sp:
        for _ in range(300):
            tracer.record_span("compile.trace", 0.0001, "compile.small", fun="f")
        tracer.record_span("compile.trace", 0.004, "compile.small", fun="init_all")
        tracer.record_span("compile.lower", 0.0005, fun="no small name")
    names = [r["name"] for r in tracer.records()]
    assert names == ["compile.trace", "compile.lower", "compile.small",
                     "trial_pack.init"]
    small = tracer.records()[2]
    assert small["tags"] == {"n": 300}
    assert small["dur_s"] == pytest.approx(0.03, abs=1e-6)
    assert small["parent_id"] == sp.span_id and "leaf" not in small
    init = tracer.records()[3]
    # written when its span closes, ending where the span ends
    assert small["mono"] + small["dur_s"] == pytest.approx(
        init["mono"] + init["dur_s"], abs=1e-4)
    assert tracer.summary()["compile.small"]["count"] == 1


@pytest.mark.parametrize("dur", ["not a number", None, float("nan"), -3.0])
def test_record_span_never_raises_into_its_caller(dur):
    tracer = Tracer()
    tracer.record_span("compile.backend", dur, fun="jit(f)")
    assert all(r["dur_s"] == 0.0 for r in tracer.records())
    telemetry.record_span("compile.backend", dur, "compile.small", fun="jit(f)")


def test_current_span_id_is_the_innermost_open_span():
    tracer = Tracer()
    assert tracer.current_span_id() is None
    with tracer.span("trial.total") as outer:
        with tracer.span("trial.train") as inner:
            assert tracer.current_span_id() == inner.span_id
        assert tracer.current_span_id() == outer.span_id
    assert tracer.current_span_id() is None


# -- the listeners, in a process of their own -----------------------------------

_RELOADED = """
import importlib, json
import jax, jax.numpy as jnp
import rafiki_tpu.ops.train as T
from rafiki_tpu import telemetry
importlib.reload(T)
import rafiki_tpu.ops.train
importlib.reload(T)

@jax.jit
def once(x):
    return (x * 3.0).sum()

telemetry.reset()
with telemetry.span("outer") as sp:
    once(jnp.ones((8, 8)))
print(json.dumps([r for r in telemetry.span_records()
                  if r.get("tags", {}).get("fun") in ("once", "jit(once)")]))
"""

_CACHED = """
import json, sys
import jax, jax.numpy as jnp
import rafiki_tpu.ops.train  # the listeners
from rafiki_tpu import telemetry
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def kept(x):
    return jnp.tanh(x @ x).sum()

out = []
for _ in range(2):
    telemetry.reset()
    with telemetry.span("pass"):
        kept(jnp.ones((16, 16))).block_until_ready()
    out.append([r for r in telemetry.span_records()
                if r.get("tags", {}).get("fun") == "jit(kept)"])
    jax.clear_caches()
print(json.dumps(out))
"""


def _run(code, *args):
    p = subprocess.run([sys.executable, "-c", code, *args], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_reloading_the_train_path_registers_the_listeners_once():
    records = _run(_RELOADED)
    # one record an event: a trace, a lowering, a backend compile
    assert sorted(r["name"] for r in records) == sorted(STAGES)
    assert {r["parent"] for r in records} == {"outer"}
    by_name = {r["name"]: r for r in records}
    assert by_name["compile.trace"]["tags"]["fun"] == "once"
    assert by_name["compile.backend"]["tags"]["fun"] == "jit(once)"


def test_a_persistent_cache_hit_is_tagged_with_its_retrieval(tmp_path):
    cold, warm = _run(_CACHED, str(tmp_path / "cache"))
    (miss,) = [r for r in cold if r["name"] == "compile.backend"]
    (hit,) = [r for r in warm if r["name"] == "compile.backend"]
    assert miss["tags"]["cache_hit"] is False and "retrieval_s" not in miss["tags"]
    assert hit["tags"]["cache_hit"] is True
    assert 0 < hit["tags"]["retrieval_s"] <= hit["dur_s"] + 1e-3
    # the lowering is paid again on a hit: no cache saves it
    assert [r["name"] for r in warm].count("compile.lower") == 1


# -- a serial trial and a packed round through the scheduler --------------------

# (sizes no other test file uses, so that this process's persistent-cache
# and jit state hold none of these programs whatever ran before)
TRAIN = "synthetic://images?classes=5&n=192&w=7&h=7&c=1&seed=35"
VAL = "synthetic://images?classes=5&n=64&w=7&h=7&c=1&seed=36"


def _sweep(tmp_path_factory, lane, pack, trials):
    return run_sweep(tmp_path_factory.mktemp(lane), trials, pack, fresh=True,
                     train=TRAIN, val=VAL)["records"]


@pytest.fixture(scope="module")
def serial(tmp_path_factory):
    """Two serial trials of one program."""
    return _sweep(tmp_path_factory, "serial", 1, 2)


@pytest.fixture(scope="module")
def packed(tmp_path_factory):
    """Two packed rounds of four."""
    return _sweep(tmp_path_factory, "packed", 4, 8)


def _inside(records, outer):
    """The records of ``outer``'s thread that lie inside it on the clock."""
    lo, hi = outer["mono"], outer["mono"] + outer["dur_s"]
    return [r for r in records if r is not outer and r["thread"] == outer["thread"]
            and lo <= r["mono"] and r["mono"] + r["dur_s"] <= hi + 1e-4]


def _named(records, name):
    return sorted((r for r in records if r["name"] == name),
                  key=lambda r: r["mono"])


@pytest.mark.parametrize("lane,total,epoch", [
    ("serial", "trial.total", "train.epoch"),
    ("packed", "trial_pack.total", "train.packed_epoch")])
def test_the_first_of_a_program_leaves_its_compile_stages_the_second_none(
        lane, total, epoch, request):
    records = request.getfixturevalue(lane)
    by_id = {r["span_id"]: r for r in records}
    first, second = _named(records, total)
    cold = _inside(records, first)
    for stage in STAGES:
        found = [r for r in cold if r["name"] == stage]
        assert found, f"no {stage} record in the first {total}"
        for r in found:
            assert r["tags"]["fun"] and "leaf" not in r
            # a child of the span that was open on its thread
            assert by_id[r["parent_id"]]["name"] == r["parent"]
            up = by_id[r["parent_id"]]
            assert up["mono"] - 1e-3 <= r["mono"]
            assert r["mono"] + r["dur_s"] <= up["mono"] + up["dur_s"] + 1e-3
    # the epoch program's own stages are children of the epoch span
    epochs = _named(records, epoch)
    # ``cold`` is a loop's first epoch, so every trial's and every round's:
    # ``compile_s`` is what tells the one that built its program from the
    # one that found it.
    assert [e["tags"]["cold"] for e in epochs] == [True, True]
    # The epoch program's own stages: children of the packed epoch span; in
    # the serial lane the profiler's cost capture builds it first, inside
    # ``trial.train`` and ahead of the epoch span, which finds it built.
    own = {r["name"]: r["parent"] for r in records
           if r["name"] in STAGES and "train_epoch" in r["tags"]["fun"]}
    assert set(own) == set(STAGES)
    assert set(own.values()) == {
        "train.packed_epoch" if lane == "packed" else "trial.train"}
    assert 0 <= epochs[0]["tags"]["compile_s"] <= epochs[0]["dur_s"] + 1e-3
    if lane == "packed":
        assert epochs[0]["tags"]["compile_s"] > 0.5 * epochs[0]["dur_s"]
    # the second trial (round) of the same program: nothing compiles, no
    # data set is loaded or uploaded, and its epoch span says so
    warm = _inside(records, second)
    assert not [r["name"] for r in warm
                if r["name"].startswith(("compile.", "data."))]
    assert epochs[1]["tags"]["compile_s"] == 0.0


@pytest.mark.parametrize("lane", ["serial", "packed"])
def test_a_data_set_is_loaded_and_uploaded_once_in_plain_spans(lane, request):
    records = request.getfixturevalue(lane)
    loads, uploads = _named(records, "data.load"), _named(records, "data.upload")
    assert len(loads) == 2 and len(uploads) == 2     # train and validation
    for r in loads + uploads:
        assert "leaf" not in r and r["tags"]["bytes"] > 0
    assert {r["tags"]["uri_scheme"] for r in loads} == {"synthetic"}
    assert loads[0]["tags"]["bytes"] == 192 * 7 * 7 * 4 + 192 * 4
    # children of whatever was open: leaf phases, or nothing at all (the
    # packed runner asks for the train set before its first round)
    parents = [r["parent"] for r in loads + uploads]
    assert parents == {
        "serial": ["trial.train", "trial.evaluate", "trial.train",
                   "trial.evaluate"],
        "packed": [None, "trial_pack.evaluate", "train.packed_epoch",
                   "trial_pack.evaluate"]}[lane]


def test_a_serial_trial_has_a_leaf_for_its_initialisation(serial):
    inits = _named(serial, "train.init")
    assert len(inits) == 2 and all(r["leaf"] for r in inits)
    assert {r["parent"] for r in inits} == {"trial.train"}
    # the init program's stages are the first init's children alone
    kids = [{r["name"] for r in serial if r["parent_id"] == i["span_id"]}
            for i in inits]
    assert kids[0] >= set(STAGES) and not kids[1]
    builds = _named(serial, "trial.build")
    assert len(builds) == 2 and all(r["leaf"] for r in builds)


def test_the_packed_lane_has_no_serial_initialisation_record(packed):
    assert not _named(packed, "train.init")
    assert len(_named(packed, "trial_pack.init")) == 2
