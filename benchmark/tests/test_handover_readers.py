"""The readers of the hand-over's spans (PR 25): each on a hand-written
``measured`` of three rounds with known answers, ``None`` where its spans
are absent (the parent's program writes none of them), and a CPU rehearsal
of ``run.py --trace 1`` with a window that holds two rounds."""

import json

import pytest
from conftest import BENCH

import run

WORKER, SAVER = "train-worker-0", "saver-w0"

NEW = ["handover_s.sweep", "handover_unattributed_share.sweep",
       "bookkeeping_share.sweep", "feedback_share.sweep",
       "persist_wait_share.sweep", "persist_fetch_ms.sweep",
       "persist_write_ms.sweep", "epoch_program_ms.sweep"]


def measured():
    """Three rounds of two trials on a worker's thread, a saver beside it.

    A round, from its start ``t``: advisor_propose 0.5 s, bucket 0.1, claim
    0.1, [trial_pack.total: build 0.05, [trial_pack.train: init 0.25,
    packed_epoch 10.0 (100 steps)], evaluate 1.0], then a trial: log 0.1,
    advisor_feedback 0.2, persist_wait 0.5; twice. Between the second
    persist_wait and the next round's draft the thread is in no span for
    0.2 s. A round is 13.6 s and the next starts 0.2 s after it, so a
    hand-over (end of evaluate to the next packed_epoch) is 1.6 + 0.2 + 0.7
    + 0.05 + 0.25 = 2.8 s, of which 0.2 s in no childless span."""
    spans = []
    ids = iter(range(10_000))

    def add(name, thread, start, dur, parent=None, **tags):
        sid = f"s{next(ids)}"
        rec = {"type": "span", "name": name, "ts": 1e9 + start, "mono": 50.0 + start,
               "thread": thread, "dur_s": dur, "span_id": sid,
               "parent_id": parent, "parent": None}
        if tags:
            rec["tags"] = tags
        spans.append(rec)
        return sid

    for r in range(3):
        t = r * 13.8
        add("trial.advisor_propose", WORKER, t, 0.5)
        add("trial_pack.bucket", WORKER, t + 0.5, 0.1)
        add("trial.claim", WORKER, t + 0.6, 0.1)
        total = add("trial_pack.total", WORKER, t + 0.7, 11.3)
        add("trial_pack.build", WORKER, t + 0.7, 0.05, total)
        train = add("trial_pack.train", WORKER, t + 0.75, 10.25, total)
        add("trial_pack.init", WORKER, t + 0.75, 0.25, train)
        add("train.packed_epoch", WORKER, t + 1.0, 10.0, train,
            cold=True, k=2, steps=100)
        add("trial_pack.evaluate", WORKER, t + 11.0, 1.0, total)
        for i in range(2):
            u = t + 12.0 + 0.8 * i
            add("trial.log", WORKER, u, 0.1)
            add("trial.advisor_feedback", WORKER, u + 0.1, 0.2)
            add("trial.persist_wait", WORKER, u + 0.3, 0.5)
            # the saver, overlapping the worker's next steps
            p = add("trial.persist", SAVER, u + 0.35, 0.7)
            add("persist.fetch", SAVER, u + 0.35, 0.3, p)
            add("persist.write", SAVER, u + 0.65, 0.1, p)
            add("persist.write", SAVER, u + 0.75, 0.2, p)
            add("persist.mark", SAVER, u + 0.95, 0.1, p)
    return {"spans": spans, "window_s": 50.0, "steps_per_trial": 100, "k": 2}


ANSWERS = {
    "handover_s.sweep": 2.8,
    "handover_unattributed_share.sweep": 100.0 * 0.2 / 2.8,
    # 3 rounds x (2 x 0.1 log + 0.1 claim + 0.1 bucket + 0.25 init) / 50 s
    "bookkeeping_share.sweep": 100.0 * 3 * 0.65 / 50.0,
    "feedback_share.sweep": 100.0 * 6 * 0.2 / 50.0,
    "persist_wait_share.sweep": 100.0 * 6 * 0.5 / 50.0,
    "persist_fetch_ms.sweep": 300.0,
    "persist_write_ms.sweep": 400.0,
    "epoch_program_ms.sweep": 100.0,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_the_known_answer(metric):
    assert run.load_reader(metric)(measured()) == pytest.approx(ANSWERS[metric])


@pytest.mark.parametrize("metric", NEW)
def test_reader_returns_none_without_its_spans(metric):
    # what the parent's program writes: the old spans, without the
    # monotonic start and the thread
    old = {"trial.advisor_propose", "trial_pack.total", "trial_pack.build",
           "trial_pack.train", "trial_pack.evaluate", "trial.persist"}
    m = measured()
    m["spans"] = [{k: v for k, v in s.items() if k not in ("mono", "thread")}
                  for s in m["spans"] if s["name"] in old]
    assert run.load_reader(metric)(m) is None
    assert run.load_reader(metric)(dict(m, spans=[])) is None


def test_a_lone_round_has_no_hand_over():
    m = measured()
    m["spans"] = [s for s in m["spans"] if s["mono"] < 50.0 + 13.8]
    assert run.load_reader("handover_s.sweep")(m) is None
    assert run.load_reader("handover_unattributed_share.sweep")(m) is None
    assert run.load_reader("epoch_program_ms.sweep")(m) == pytest.approx(100.0)


def test_a_hand_over_is_read_on_the_workers_own_thread():
    # a second worker's epoch that starts inside the first one's hand-over
    # does not end it
    m = measured()
    m["spans"].append({"type": "span", "name": "train.packed_epoch",
                       "ts": 0.0, "mono": 50.0 + 12.5, "thread": "train-worker-1",
                       "dur_s": 1.0, "span_id": "other", "parent_id": None,
                       "tags": {"steps": 0}})
    assert run.load_reader("handover_s.sweep")(m) == pytest.approx(2.8)


def test_the_manifest_names_the_new_metrics_last_and_their_files_exist():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW):] == NEW
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["handover_s.sweep"] == "trial loop"
    assert layers["feedback_share.sweep"] == "advisor"
    assert layers["persist_fetch_ms.sweep"] == "persist"
    assert layers["epoch_program_ms.sweep"] == "device programs"
    for m in manifest["per_layer"][-len(NEW):]:
        assert m["moves"] == "trials_per_hour" and m["source"] == "program_span"
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


def rehearse_two_rounds(seed=11):
    """``test_run_cpu.rehearse`` with ``--trace 1`` and a window that holds
    two rounds: the budget is checked between rounds and a tiny round takes
    4 to 7 s on this CPU, so a 10 s window closes after its second or
    third."""
    import io

    from conftest import tiny
    from rafiki_tpu.ops.train import clear_program_cache
    from test_run_cpu import TINY_LIMITS, tiny_traffic

    clear_program_cache()
    out = io.StringIO()
    rc = run.main(["--workload", "vgg16_sweep_packed", "--seed", str(seed),
                   "--seconds", "10", "--trace", "1"], platform="cpu",
                  overrides=dict(cfg=tiny, out=out, traffic=tiny_traffic,
                                 limits=TINY_LIMITS))
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])


def test_traced_rehearsal_prints_every_per_layer_metric_but_the_device_planes():
    rc, line = rehearse_two_rounds()
    # (no device plane on the CPU, so the traced run's line says so under
    # ``problems`` and is not ``correct``: test_run_cpu.py has the same)
    assert rc == 0 and line["failed"] == 0, line
    assert line["attempted"] >= 32, "the window held fewer than two rounds"
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in manifest["per_layer"]} - {
        "traced_idle_share.sweep",   # needs a device plane
        "sweep_mfu.sweep"}           # needs the chip's peak
    assert set(line["metrics"]) == expected
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["compiles_in_window.sweep"] == 0
    assert 0 < got["handover_s.sweep"] < line["window_s"]
    assert 0 <= got["handover_unattributed_share.sweep"] < 10.0
    # the epoch program alone is a part of what train_step_ms.sweep holds
    assert 0 < got["epoch_program_ms.sweep"] <= got["train_step_ms.sweep"]
    for name in ("bookkeeping_share.sweep", "feedback_share.sweep",
                 "persist_wait_share.sweep"):
        assert 0 <= got[name] < 100.0
    assert got["persist_fetch_ms.sweep"] > 0 and got["persist_write_ms.sweep"] > 0
