"""The hand-over between pack rounds, seen from inside (ISSUE 25).

  * the bridge: a leaf span enters and leaves the installed annotator, in
    order and inside its own clock; a plain span never does; telemetry and
    the scheduler stay off jax, ``ops/train.py`` installs the profiler's
    annotation;
  * a whole packed sweep under ``jax.profiler``: the program's phases are
    events of the ``/host:`` planes the benchmark's reducer reads, no leaf
    phase nests in another on its thread, every record carries the
    monotonic start and the thread's name, and the worker's childless spans
    cover the hand-over but for a stated remainder;
  * the packed epoch's own record is made after its metrics are on the
    host; the step's scope names are in the lowered programs.
"""

import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from sweep_common import run_sweep

from rafiki_tpu import telemetry
from rafiki_tpu.telemetry.spans import Tracer

REPO = Path(__file__).resolve().parents[1]

# -- the bridge ---------------------------------------------------------------


class _Recorder:
    """An annotator that writes down what it is asked to do."""

    def __init__(self):
        self.calls = []

    def __call__(self, name):
        rec = self

        class _Annotation:
            def __enter__(self):
                rec.calls.append(("enter", name))

            def __exit__(self, *exc):
                rec.calls.append(("exit", name))

        return _Annotation()


def test_leaf_span_enters_and_leaves_the_annotator_in_order():
    tracer, rec = Tracer(), _Recorder()
    tracer.install_annotator(rec)
    with tracer.span("trial_pack.train"):          # enclosing: not bridged
        with tracer.span("trial_pack.init", leaf=True, k=2):
            rec.calls.append(("body", "trial_pack.init"))
        with tracer.span("train.packed_epoch", leaf=True):
            pass
    assert rec.calls == [
        ("enter", "trial_pack.init"), ("body", "trial_pack.init"),
        ("exit", "trial_pack.init"),
        ("enter", "train.packed_epoch"), ("exit", "train.packed_epoch")]
    by_name = {r["name"]: r for r in tracer.records()}
    assert by_name["trial_pack.init"]["leaf"] is True
    assert "leaf" not in by_name["trial_pack.train"]
    assert by_name["trial_pack.init"]["tags"] == {"k": 2}


@pytest.mark.parametrize("installed", [False, True])
def test_plain_span_never_reaches_the_annotator(installed):
    tracer, rec = Tracer(), _Recorder()
    if installed:
        tracer.install_annotator(rec)
    with tracer.span("trial.total", trial_id="t"):
        with tracer.span("program.build"):
            pass
    assert rec.calls == []
    assert [r["name"] for r in tracer.records()] == ["program.build",
                                                    "trial.total"]


def test_leaf_span_without_an_annotator_is_a_plain_record():
    tracer = Tracer()
    with tracer.span("trial.log", leaf=True):
        pass
    (rec,) = tracer.records()
    assert rec["leaf"] is True and rec["dur_s"] >= 0


def test_an_annotator_that_raises_costs_the_event_not_the_span():
    def broken(name):
        raise RuntimeError("no profiler")

    tracer = Tracer()
    tracer.install_annotator(broken)
    with tracer.span("trial.claim", leaf=True):
        pass
    assert [r["name"] for r in tracer.records()] == ["trial.claim"]


def test_span_records_carry_the_monotonic_start_and_the_thread():
    import time

    tracer = Tracer()
    before = time.monotonic()

    def work():
        with tracer.span("persist.write", leaf=True):
            pass

    t = threading.Thread(target=work, name="saver-w0")
    t.start()
    t.join()
    with tracer.span("trial.log", leaf=True):
        pass
    saver, worker = tracer.records()
    assert saver["thread"] == "saver-w0"
    assert worker["thread"] == threading.current_thread().name
    assert before <= saver["mono"] <= worker["mono"] <= time.monotonic()


@pytest.mark.parametrize("module", ["rafiki_tpu.telemetry",
                                    "rafiki_tpu.scheduler"])
def test_importing_it_leaves_jax_out(module):
    code = (f"import sys, {module}; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-500:]


def test_the_train_path_installs_the_profilers_annotation():
    import jax

    import rafiki_tpu.ops.train  # noqa: F401

    assert telemetry.get_tracer()._annotator is jax.profiler.TraceAnnotation


# -- a packed sweep under the profiler ----------------------------------------

PACK, ROUNDS = 4, 3

#: The hand-over phases of the worker's thread (ISSUE 25's table, and the
#: health plane's pre-epoch copy of the state, which the CPU rehearsal
#: found uncovered; ISSUE 26's dispatch of a round's one copy of the
#: stacked parameters, before the evaluation), and the saver's.
WORKER_PHASES = {"trial.log", "trial.advisor_feedback", "trial.persist_wait",
                 "trial.advisor_propose", "trial_pack.bucket", "trial.claim",
                 "trial_pack.build", "trial_pack.init",
                 "train.health_snapshot", "train.packed_epoch",
                 "persist.dispatch", "trial_pack.evaluate"}
SAVER_PHASES = {"persist.fetch", "persist.write", "persist.mark"}


@pytest.fixture(scope="module")
def sweep(tmp_path_factory):
    """Three packed rounds of four through ``LocalScheduler`` (the
    benchmark's entry), traced as the benchmark traces: the python tracer
    off, the host tracer at its default."""
    return run_sweep(tmp_path_factory.mktemp("bridge"), PACK * ROUNDS, PACK,
                     traced=True)


@pytest.fixture(scope="module")
def serial_sweep(tmp_path_factory):
    """Three serial trials through the same entry (ISSUE 35: the serial
    lane's leaves ``train.init`` and ``trial.build`` belong to the walk)."""
    return run_sweep(tmp_path_factory.mktemp("bridge-serial"), 3, 1)


def _bench_module(name):
    import importlib.util

    path = REPO / "benchmark" / name
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_phases_are_events_of_the_host_planes_the_reducer_reads(sweep):
    planes = _bench_module("trace_reduce.py").load_xplane(sweep["trace_dir"])
    names = {e[0] for p in planes if p["name"].startswith("/host:")
             for ln in p["lines"] for e in ln["events"]}
    assert {"trial.persist_wait", "trial.advisor_feedback", "trial_pack.init",
            "persist.write"} <= names
    assert WORKER_PHASES | SAVER_PHASES <= names
    # enclosing spans are not bridged: they would name every gap
    assert not {"trial_pack.total", "trial_pack.train", "trial.persist"} & names


def test_every_phase_of_the_table_was_recorded_on_its_thread(sweep):
    by_thread = {}
    for r in sweep["records"]:
        if r.get("leaf"):
            by_thread.setdefault(r["thread"], set()).add(r["name"])
    savers = [t for t in by_thread if t.startswith("saver-")]
    workers = [t for t in by_thread if not t.startswith("saver-")]
    assert len(savers) == 1 and len(workers) == 1
    assert by_thread[savers[0]] == SAVER_PHASES
    assert by_thread[workers[0]] == WORKER_PHASES


@pytest.mark.parametrize("lane", ["sweep", "serial_sweep"])
def test_no_leaf_phase_nests_in_another_on_its_thread(lane, request):
    records = request.getfixturevalue(lane)["records"]
    if lane == "serial_sweep":
        leaves = {r["name"] for r in records if r.get("leaf")}
        assert {"train.init", "trial.build", "train.epoch",
                "trial.evaluate"} <= leaves
        # finished phases (a compile stage, a data set's load) are never
        # leaves, wherever they lie
        assert not [r["name"] for r in records if r.get("leaf")
                    and r["name"].startswith(("compile.", "data."))]
    by_id = {r["span_id"]: r for r in records}
    for r in records:
        if not r.get("leaf"):
            continue
        up = by_id.get(r["parent_id"])
        while up is not None:
            assert not up.get("leaf"), (r["name"], "inside", up["name"])
            up = by_id.get(up["parent_id"])
    # and on the clock: one thread's leaf phases do not overlap
    for thread in {r["thread"] for r in records}:
        leaves = sorted((r for r in records
                         if r["thread"] == thread and r.get("leaf")),
                        key=lambda r: r["mono"])
        for a, b in zip(leaves, leaves[1:]):
            assert a["mono"] + a["dur_s"] <= b["mono"] + 1e-4, (a, b)


def test_every_record_has_the_monotonic_start_and_the_thread(sweep):
    assert sweep["records"]
    for r in sweep["records"]:
        assert isinstance(r["mono"], float) and r["thread"]


def test_childless_spans_cover_the_hand_over_but_for_a_tenth(sweep):
    sys.path.insert(0, str(REPO / "benchmark" / "layer_metrics"))
    try:
        handover = _bench_module("layer_metrics/_handover.py")
    finally:
        sys.path.pop(0)
    m = {"spans": sweep["records"]}
    hs = handover.handovers(m)
    assert len(hs) == ROUNDS - 1
    total = sum(b - a for _t, a, b in hs)
    uncovered = sum(handover.uncovered_seconds(m, t, a, b) for t, a, b in hs)
    # The stated remainder: a tenth of the hand-over (the statements
    # between two phases; measured here at about a hundredth).
    assert 0 < total and uncovered <= 0.10 * total, (uncovered, total)


# -- the packed epoch's own record --------------------------------------------


def _tiny_pack():
    import jax

    from rafiki_tpu.ops.train import PackedTrainLoop, cross_entropy_loss

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 3)) * 0.1}

    def apply_fn(params, batch):
        return batch["x"] @ params["w"]

    def loss_fn(params, batch, rng, hyper):
        loss, acc = cross_entropy_loss(apply_fn(params, batch), batch["y"])
        return loss, {"acc": acc}

    class _Data:
        size = 32
        x = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
        y = (np.arange(32) % 3).astype(np.int32)
        mask = None

    loop = PackedTrainLoop(init_fn, apply_fn, loss_fn, seeds=[0, 1],
                           hypers=[{"lr": 1e-2}, {"lr": 1e-3}])
    return loop, _Data()


@pytest.mark.parametrize("path", ["device_resident", "by_steps"])
def test_packed_epoch_is_recorded_after_its_metrics_are_on_the_host(
        path, monkeypatch):
    import jax

    import rafiki_tpu.ops.train as ops_train
    from rafiki_tpu.obs.perf import profiler

    if path == "by_steps":
        monkeypatch.setattr(ops_train, "device_dataset_cap_bytes", lambda: 0)
    loop, data = _tiny_pack()
    order, noted = [], []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (order.append("device_get"), real_get(x))[1])
    real_note = profiler.note_epoch
    monkeypatch.setattr(
        profiler, "note_epoch",
        lambda key, dt, **kw: (order.append("note_epoch"),
                               noted.append((dt, kw)),
                               real_note(key, dt, **kw))[2])
    telemetry.reset()
    rows = loop.run_epoch(data, 8, [0, 1])
    assert len(rows) == 2 and set(rows[0]) >= {"loss", "acc"}
    assert order.count("note_epoch") == 1
    assert order.index("note_epoch") > max(
        i for i, what in enumerate(order) if what == "device_get")
    (span,) = [r for r in telemetry.span_records()
               if r["name"] == "train.packed_epoch"]
    assert span["leaf"] is True
    # (``compile_s``, ISSUE 35: the compile stages inside this cold epoch)
    assert 0 < span["tags"].pop("compile_s") <= span["dur_s"] + 1e-3
    assert span["tags"] == {"cold": True, "k": 2, "steps": 4}
    # the observers are handed the epoch the span measured, not its enqueue
    (dt, kw), = noted
    assert dt >= span["dur_s"] and kw["cold"] is True and kw["k"] == 2
    loop.run_epoch(data, 8, [1, 2])
    assert [r["tags"]["cold"] for r in telemetry.span_records()
            if r["name"] == "train.packed_epoch"] == [True, False]
    # the two histograms this span retires are gone
    hist = telemetry.snapshot()["histograms"]
    assert not {"train.packed_epoch_s", "train.packed_cold_epoch_s"} & set(hist)
    assert telemetry.snapshot()["spans"]["train.packed_epoch"]["count"] == 2


# -- scope names --------------------------------------------------------------


def test_the_steps_scope_names_are_in_the_lowered_programs():
    from rafiki_tpu.ops import train as ops_train

    loop, data = _tiny_pack()
    import jax.numpy as jnp

    X, Y = jnp.asarray(data.x), jnp.asarray(data.y)
    idx = np.zeros((4, 2, 8), np.int32)
    poison = np.ones((4, 2), np.float32)
    train_text = loop.program.train_epoch.lower(
        loop.state, X, Y, idx, poison).as_text(debug_info=True)
    eval_text = loop.program.eval_epoch.lower(
        loop.state[0], X, Y, np.zeros((4, 8), np.int32)).as_text(debug_info=True)
    for name in (ops_train.SCOPE_GATHER, ops_train.SCOPE_LOSS,
                 ops_train.SCOPE_OPTIMIZER, ops_train.SCOPE_HEALTH):
        assert name in train_text, name
    for name in (ops_train.SCOPE_GATHER, ops_train.SCOPE_EVAL_COUNT):
        assert name in eval_text, name
    assert set(ops_train.STEP_SCOPES) == {
        "rafiki.batch_gather", "rafiki.loss", "rafiki.optimizer",
        "rafiki.health", "rafiki.eval_count"}
