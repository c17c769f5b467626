"""The readers of set-up's spans (PR 35): the split on hand-written records
with known answers, ``None`` where the program writes none of the new names
(the parent's), and a CPU rehearsal of ``run.py --trace 1`` at a tiny size in
one image cell and one language-model cell: every reader a number, each
second of ``setup_s`` counted once."""

import pytest

import _setup
import run

SETUP = ["setup_trace_s.sweep", "setup_compile_s.sweep", "setup_init_s.sweep",
         "setup_data_s.sweep", "setup_unattributed_share.sweep",
         "setup_outside_program_share.sweep"]
WORKER, SAVER = "train-worker-0", "saver-w0"
NEW_NAMES = ("compile.", "data.", "train.init")


def records():
    """A set-up of 20 s that ends at mono 120 (ts 1e9 + 20): nothing for
    4 s, then one serial trial on the worker's thread:

      trial.total 4..18 [ trial.build 4..4.5 (leaf);
        trial.train 4.5..16 [ data.load 4.5..5.5 (plain);
          train.init 5.5..8 (leaf) [ compile.trace init_all 5.6..6.6 with
            compile.trace matmul 5.8..6.0 nested in it; compile.lower 6.6..7.0;
            compile.backend 7.0..7.8 ];
          train.epoch 8.5..16 (leaf) [ compile.trace 8.5..10.5;
            compile.backend 10.5..13.5; compile.small 15.9..16 ] ];
        trial.evaluate 16..17.5 (leaf) ]
    and on the saver's thread trial.persist 17..19.5 [ persist.write 17..19 ]:
    0.5 s of it beside the worker's evaluate, 0.5 s beside trial.total's own
    last half second, 1.5 s after the worker has gone. The window's first
    record starts at 20."""
    out, ids = [], iter(range(10_000))

    def add(name, thread, start, end, parent=None, leaf=False, **tags):
        sid = f"s{next(ids)}"
        rec = {"type": "span", "name": name, "ts": 1e9 + start,
               "mono": 100.0 + start, "thread": thread, "dur_s": end - start,
               "span_id": sid, "parent_id": parent, "parent": None}
        if leaf:
            rec["leaf"] = True
        if tags:
            rec["tags"] = tags
        out.append(rec)
        return sid

    total = add("trial.total", WORKER, 4, 18)
    add("trial.build", WORKER, 4, 4.5, total, leaf=True)
    train = add("trial.train", WORKER, 4.5, 16, total)
    add("data.load", WORKER, 4.5, 5.5, train, uri_scheme="synthetic", bytes=8)
    init = add("train.init", WORKER, 5.5, 8, train, leaf=True)
    add("compile.trace", WORKER, 5.8, 6.0, init, fun="matmul")
    add("compile.trace", WORKER, 5.6, 6.6, init, fun="init_all")
    add("compile.lower", WORKER, 6.6, 7.0, init, fun="jit(init_all)")
    add("compile.backend", WORKER, 7.0, 7.8, init, fun="jit(init_all)",
        cache_hit=True, retrieval_s=0.7)
    epoch = add("train.epoch", WORKER, 8.5, 16, train, leaf=True, cold=True,
                steps=1, compile_s=5.1)
    add("compile.trace", WORKER, 8.5, 10.5, epoch, fun="train_step")
    add("compile.backend", WORKER, 10.5, 13.5, epoch, fun="jit(train_step)",
        cache_hit=False)
    add("compile.small", WORKER, 15.9, 16, epoch, n=40)
    add("trial.evaluate", WORKER, 16, 17.5, total, leaf=True)
    persist = add("trial.persist", SAVER, 17, 19.5)
    add("persist.write", SAVER, 17, 19, persist, leaf=True)
    # the window, and a round after it
    w = add("trial.total", WORKER, 20, 30)
    add("train.init", WORKER, 20.5, 21.0, w, leaf=True)
    add("compile.backend", SAVER, 25, 25.2, None, fun="jit(cast)", cache_hit=True,
        retrieval_s=0.1)
    add("trial_pack.init", WORKER, 40, 41, leaf=True)
    return out


def measured(recs):
    window = [r for r in recs if 1e9 + 20 <= r["ts"] < 1e9 + 35]
    return {"spans": window, "window_s": 10.0, "setup_s": 20.0}


ANSWERS = {
    # 5.6..6.6 (the nested trace counted once) + 6.6..7.0 + 8.5..10.5 + the
    # folded 0.1
    "setup_trace_s.sweep": 1.0 + 0.4 + 2.0 + 0.1,
    "setup_compile_s.sweep": 0.8 + 3.0,
    # train.init 2.5 s, less the 2.2 s of compile stages inside it
    "setup_init_s.sweep": 2.5 - 2.2,
    "setup_data_s.sweep": 1.0,
    # trial.train's own 8..8.5 and trial.total's own 17.5..18; the saver's
    # trial.persist 19..19.5 once the worker has gone
    "setup_unattributed_share.sweep": 100.0 * 1.5 / 20.0,
    # 0..4 before the first span, 19.5..20 after the last
    "setup_outside_program_share.sweep": 100.0 * 4.5 / 20.0,
    "init_share.lm": 100.0 * 0.5 / 10.0,
}


@pytest.fixture
def ring(monkeypatch):
    recs = records()
    monkeypatch.setattr(_setup, "process_records", lambda: recs)
    return recs


@pytest.mark.parametrize("metric", SETUP + ["init_share.lm"])
def test_reader_gives_the_known_answer(metric, ring):
    assert run.load_reader(metric)(measured(ring)) == pytest.approx(ANSWERS[metric])


def test_every_second_of_set_up_is_counted_once(ring):
    s = _setup.split(measured(ring))
    kinds = ("trace", "backend", "init", "data", "named", "unattributed", "outside")
    assert sum(s[k] for k in kinds) == pytest.approx(20.0)
    assert sum(s["by_name"].values()) + s["outside"] == pytest.approx(20.0)
    # the other named leaves, each net of what lies inside it; the saver's
    # write counts only where the worker's thread has nothing open
    assert s["by_name"]["train.epoch"] == pytest.approx(7.5 - 5.1)
    assert s["by_name"]["trial.evaluate"] == pytest.approx(1.5)
    assert s["by_name"]["trial.build"] == pytest.approx(0.5)
    assert s["by_name"]["persist.write"] == pytest.approx(1.0)
    assert s["by_name"]["(trial.persist)"] == pytest.approx(0.5)
    assert s["records"] == 16


@pytest.mark.parametrize("metric", SETUP + ["init_share.lm"])
def test_reader_returns_none_without_the_new_names(metric, monkeypatch):
    # what the parent's program writes: the spans it had, and none of the
    # compile stages, data-set spans or train.init
    old = [r for r in records() if not r["name"].startswith(NEW_NAMES)]
    monkeypatch.setattr(_setup, "process_records", lambda: old)
    assert run.load_reader(metric)(measured(old)) is None
    assert run.load_reader(metric)({"spans": [], "window_s": 1.0,
                                    "setup_s": 20.0}) is None


def test_the_window_is_located_by_its_own_records(ring, capsys):
    # a ring that lost its oldest records still yields a split of what is
    # left, and the log says what a compile in the window was
    m = measured(ring)
    assert _setup.split(m) is _setup.split(m)        # computed once a run
    err = capsys.readouterr().err
    assert "compile stages in the window: 1" in err
    assert "compile.backend jit(cast) 0.200 s cache_hit True" in err
    assert "compile.backend jit(train_step) 3.000 s cache_hit False" in err


# -- the rehearsal ----------------------------------------------------------------


def _check_line(line, lm):
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SETUP) <= set(got)
    assert ("init_share.lm" in got) == lm
    assert got["setup_trace_s.sweep"] > 0 and got["setup_compile_s.sweep"] > 0
    assert got["setup_init_s.sweep"] > 0 and got["setup_data_s.sweep"] > 0
    assert 0 <= got["setup_unattributed_share.sweep"] < 10.0
    assert 0 < got["setup_outside_program_share.sweep"] < 100.0
    return got


@pytest.fixture
def fresh_ring():
    """A run is a process of its own; a rehearsal shares this one's ring
    with whatever ran before."""
    from rafiki_tpu import telemetry

    telemetry.reset()


def test_rehearsal_of_the_image_cell_prints_the_setup_metrics(fresh_ring, capfd):
    from test_handover_readers import rehearse_two_rounds

    rc, line = rehearse_two_rounds(seed=2**31 + 35)
    assert rc == 0 and line["failed"] == 0, line
    _check_line(line, lm=False)
    err = capfd.readouterr().err
    assert "[setup] set-up by span name" in err
    assert "compile stages in the window: 0" in err


def test_rehearsal_of_a_language_model_cell_sums_to_setup_s(
        fresh_ring, monkeypatch, capfd):
    from test_lm_cells_cpu import LM, rehearse

    kept = {}
    real = _setup._split

    def keep(m):
        kept["split"], kept["setup_s"] = real(m), m["setup_s"]
        return kept["split"]

    monkeypatch.setattr(_setup, "_split", keep)
    rc, line = rehearse(LM, seed=2**31 + 35, trace=1)
    assert rc == 0 and line["failed"] == 0, line
    got = _check_line(line, lm=True)
    assert 0 < got["init_share.lm"] < 100.0
    s, setup_s = kept["split"], kept["setup_s"]
    # the six categories + the other named leaves + outside = setup_s
    parts = (got["setup_trace_s.sweep"] + got["setup_compile_s.sweep"]
             + got["setup_init_s.sweep"] + got["setup_data_s.sweep"]
             + s["named"]
             + (got["setup_unattributed_share.sweep"]
                + got["setup_outside_program_share.sweep"]) * setup_s / 100.0)
    assert parts == pytest.approx(setup_s, rel=0.02)
    assert s["records"] < _setup.RING / 2
    assert "compile.trace train_step" in capfd.readouterr().err
