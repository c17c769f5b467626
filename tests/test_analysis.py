"""The analyzer proves itself against the bugs it encodes: every
checker fires on its bad fixture (including the PRE-FIX forms of the
two real round-5 bugs, reconstructed from the live files) and stays
silent on the good one."""

import os
import textwrap

import pytest

from rafiki_tpu.analysis import analyze_paths, load_builtin_checkers
from rafiki_tpu.analysis.core import REGISTRY, module_name_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

load_builtin_checkers()


def _ids(result, path=None):
    return sorted({f.checker_id for f in result.unsuppressed
                   if path is None or f.path == str(path)})


def _analyze_snippet(tmp_path, source, name="snippet.py", select=None):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], select=select)


def test_all_builtin_checkers_registered():
    assert {"RF001", "RF002", "RF003", "RF004", "RF005", "RF006",
            "RF007", "RF008", "RF009", "RF010", "RF011",
            "RF012", "RF013", "RF014", "RF015", "RF016",
            "RF017", "RF018", "RF019"} <= set(REGISTRY)


# ---------------------------------------------------------------------------
# RF001 entrypoint-platform-pin
# ---------------------------------------------------------------------------


def test_rf001_fires_on_unpinned_jax_entrypoint(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import jax

        def run_worker_process(meta_path):
            return jax.devices()

        def main():
            run_worker_process("x")

        if __name__ == "__main__":
            main()
        """)
    # run_*_process AND main AND the __main__ block (whose only call,
    # main(), does not pin) are all unpinned
    assert [f.checker_id for f in r.unsuppressed].count("RF001") == 3


def test_rf001_quiet_when_pinned_before_touch(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import jax
        from rafiki_tpu.utils.backend import honor_env_platform

        def main():
            honor_env_platform()
            return jax.devices()

        if __name__ == "__main__":
            main()
        """)
    assert "RF001" not in _ids(r)


def test_rf001_fires_when_jax_touched_before_pin(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import jax
        from rafiki_tpu.utils.backend import honor_env_platform

        def main():
            devices = jax.devices()
            honor_env_platform()
            return devices
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF001"]
    assert len(found) == 1 and "before the platform pin" in found[0].message


def test_rf001_pin_through_local_helper_chain(tmp_path):
    # a root script's shape: main -> _init_backend -> honor_env_platform
    r = _analyze_snippet(tmp_path, """
        import jax

        def _init_backend():
            from rafiki_tpu.utils.backend import honor_env_platform
            honor_env_platform()

        def main():
            _init_backend()
            return jax.devices()

        if __name__ == "__main__":
            main()
        """)
    assert "RF001" not in _ids(r)


def test_rf001_ignores_jaxfree_entrypoints(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import json

        def main():
            print(json.dumps({}))

        if __name__ == "__main__":
            main()
        """)
    assert "RF001" not in _ids(r)


def test_rf001_real_prefix_inference_worker(tmp_path):
    """The round-5 bug verbatim: worker/inference.py WITHOUT the
    honor_env_platform() call, analyzed against the real tree (the jax
    taint arrives transitively through rafiki_tpu.model.base)."""
    live = open(os.path.join(REPO, "rafiki_tpu/worker/inference.py")).read()
    assert "honor_env_platform" in live  # the fix is present today
    prefix = "\n".join(l for l in live.splitlines()
                       if "honor_env_platform" not in l)
    bad = tmp_path / "inference_prefix.py"
    bad.write_text(prefix)
    r = analyze_paths([str(bad), os.path.join(REPO, "rafiki_tpu")],
                      select=["RF001"])
    mine = [f for f in r.unsuppressed if f.path == str(bad)]
    assert [f.checker_id for f in mine] == ["RF001"]
    assert "run_inference_worker_process" in mine[0].message


def test_rf001_current_inference_worker_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu")], select=["RF001"])
    assert [f for f in r.unsuppressed
            if f.path.endswith("worker/inference.py")] == []


# ---------------------------------------------------------------------------
# RF002 platform-literal-gate
# ---------------------------------------------------------------------------


def test_rf002_fires_on_tpu_literal_compare(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def gate(platform):
            if platform == "tpu":
                return 1
            if "tpu" != platform:
                return 2
        """)
    assert [f.checker_id for f in r.unsuppressed] == ["RF002", "RF002"]


def test_rf002_quiet_on_cpu_gate_and_membership(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def gate(platform, device_kind):
            on_accel = platform != "cpu"
            return on_accel or "TPU" in device_kind or platform in ("tpu",)
        """)
    assert "RF002" not in _ids(r)


#: The MFU gate of the retired one-chip bench script as it stood when
#: the round-5 bug was fixed (kept here as history: the script is gone,
#: the failure class is not).
HISTORICAL_MFU_GATE = '''
def microbench(sc, loop, dev_b, step_s):
    mfu = mfu_model = None
    if sc["platform"] != "cpu":
        from rafiki_tpu.utils.backend import peak_bf16_flops

        peak = peak_bf16_flops(jax.devices()[0].device_kind)
        # whole-program flops from XLA's own cost model
        compiled = loop._train_step.lower(loop.state, dev_b).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))
        if flops > 0:
            mfu = flops / step_s / peak
    return mfu, mfu_model
'''


def test_rf002_real_prefix_bench_mfu_gate(tmp_path):
    """The round-5 bug verbatim: the bench's MFU gate reverted to the
    == "tpu" form that nulled MFU when the backend registered the chip
    under another platform name."""
    assert 'sc["platform"] != "cpu"' in HISTORICAL_MFU_GATE  # the fixed form
    fixed = tmp_path / "bench_fixed.py"
    fixed.write_text(HISTORICAL_MFU_GATE)
    assert analyze_paths([str(fixed)], select=["RF002"]).unsuppressed == []
    prefix = HISTORICAL_MFU_GATE.replace('sc["platform"] != "cpu"', 'sc["platform"] == "tpu"')
    bad = tmp_path / "bench_prefix.py"
    bad.write_text(prefix)
    r = analyze_paths([str(bad)], select=["RF002"])
    assert [f.checker_id for f in r.unsuppressed] == ["RF002"]


def test_rf002_current_bench_is_clean():
    r = analyze_paths([os.path.join(REPO, "chip_smoke.py")], select=["RF002"])
    assert r.unsuppressed == []


# ---------------------------------------------------------------------------
# RF003 defaultdict-read-leak
# ---------------------------------------------------------------------------

RF003_BAD = """
    from collections import defaultdict

    class Bus:
        def __init__(self):
            self._workers = defaultdict(set)

        def get_workers(self, job_id):
            return sorted(self._workers[job_id])

        def heartbeat(self, job_id, worker_id):
            if worker_id in self._workers[job_id]:
                pass
    """

RF003_GOOD = """
    from collections import defaultdict

    class Bus:
        def __init__(self):
            self._workers = defaultdict(set)
            self._plain = {}

        def add_worker(self, job_id, worker_id):
            self._workers[job_id].add(worker_id)

        def get_workers(self, job_id):
            return sorted(self._workers.get(job_id, ()))

        def read_plain(self, job_id):
            return self._plain[job_id]
    """


def test_rf003_fires_on_read_side_subscript(tmp_path):
    r = _analyze_snippet(tmp_path, RF003_BAD)
    assert [f.checker_id for f in r.unsuppressed] == ["RF003", "RF003"]


def test_rf003_quiet_on_insert_idiom_and_get(tmp_path):
    r = _analyze_snippet(tmp_path, RF003_GOOD)
    assert "RF003" not in _ids(r)


def test_rf003_current_bus_queues_is_clean():
    """The live bus keeps the read-side fix: heartbeat/get_workers use
    ``.get(job_id, ...)`` instead of defaultdict subscripts, so probing
    rotating job ids cannot leak empty registry entries."""
    live = os.path.join(REPO, "rafiki_tpu", "bus", "queues.py")
    r = analyze_paths([live], select=["RF003"])
    assert r.unsuppressed == []


# ---------------------------------------------------------------------------
# RF004 unguarded-shared-mutation
# ---------------------------------------------------------------------------

RF004_BAD = """
    import threading

    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._counters = {}
            self._events = []

        def inc(self, name):
            self._counters[name] = self._counters.get(name, 0) + 1

        def log(self, ev):
            self._events.append(ev)
    """

RF004_GOOD = """
    import threading

    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._counters = {}
            self._events = []

        def inc(self, name):
            with self._lock:
                self._counters[name] = self._counters.get(name, 0) + 1

        def log(self, ev):
            with self._lock:
                self._events.append(ev)

    class NoLockNoRules:
        def __init__(self):
            self._events = []

        def log(self, ev):
            self._events.append(ev)
    """


def test_rf004_fires_on_unlocked_mutation(tmp_path):
    r = _analyze_snippet(tmp_path, RF004_BAD)
    assert [f.checker_id for f in r.unsuppressed] == ["RF004", "RF004"]


def test_rf004_quiet_under_lock_and_in_lockless_classes(tmp_path):
    r = _analyze_snippet(tmp_path, RF004_GOOD)
    assert "RF004" not in _ids(r)


def test_rf004_condition_counts_as_lock(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import threading

        class Slots:
            def __init__(self):
                self._cv = threading.Condition()
                self._preds = {}

            def put(self, k, v):
                with self._cv:
                    self._preds.setdefault(k, []).append(v)
                    self._cv.notify_all()
        """)
    assert "RF004" not in _ids(r)


# ---------------------------------------------------------------------------
# RF005 jit-hazard
# ---------------------------------------------------------------------------

RF005_BAD = """
    import jax
    import numpy as np

    def train_step(state, batch):
        if state > 0:
            state = state - 1
        loss = float(batch.mean())
        host = np.asarray(batch)
        return state, loss, host

    train_step = jax.jit(train_step)

    def rebuild_per_iteration(xs):
        outs = []
        for x in xs:
            outs.append(jax.jit(lambda v: v + 1)(x))
        return outs
    """

RF005_GOOD = """
    import jax
    import jax.numpy as jnp

    def train_step(state, batch):
        state = jnp.where(state > 0, state - 1, state)
        if "valid" in batch:
            pass
        return state

    train_step = jax.jit(train_step)

    _step = jax.jit(lambda v: v + 1)

    def apply_all(xs):
        return [float(_step(x)) for x in xs]
    """


def test_rf005_fires_on_branch_sync_and_jit_in_loop(tmp_path):
    r = _analyze_snippet(tmp_path, RF005_BAD)
    msgs = [f.message for f in r.unsuppressed if f.checker_id == "RF005"]
    assert any("python `if`" in m for m in msgs)
    assert any("host sync `float" in m for m in msgs)
    assert any("host sync `np.asarray" in m for m in msgs)
    assert any("inside a loop" in m for m in msgs)


def test_rf005_quiet_on_device_side_idioms(tmp_path):
    r = _analyze_snippet(tmp_path, RF005_GOOD)
    assert "RF005" not in _ids(r)


def test_rf005_ops_train_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu/ops"),
                       os.path.join(REPO, "rafiki_tpu/parallel")],
                      select=["RF005"])
    assert r.unsuppressed == []


# ---------------------------------------------------------------------------
# RF006 swallowed-interrupt
# ---------------------------------------------------------------------------


def test_rf006_fires_on_swallowed_base_exception(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def supervise():
            try:
                work()
            except BaseException:
                log("oops")
        """, select=["RF006"])
    assert len(r.unsuppressed) == 1
    assert r.unsuppressed[0].severity == "error"


def test_rf006_fires_on_bare_except_and_interrupt_tuple(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def a():
            try:
                work()
            except:
                pass

        def b():
            try:
                work()
            except (ValueError, KeyboardInterrupt):
                pass
        """, select=["RF006"])
    assert len(r.unsuppressed) == 2


def test_rf006_quiet_on_catch_log_reraise_and_exits(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import os
        import sys

        def supervise():
            try:
                work()
            except BaseException:
                mark_errored()
                raise

        def run():
            while True:
                try:
                    step()
                except BaseException:
                    return

        def watchdog():
            try:
                work()
            except BaseException:
                os._exit(17)
        """, select=["RF006"])
    assert r.unsuppressed == []


def test_rf006_conditional_reraise_is_clean(tmp_path):
    # The services-manager fix shape: record, then re-raise interrupts.
    r = _analyze_snippet(tmp_path, """
        def run():
            try:
                work()
            except BaseException as e:
                record(e)
                if not isinstance(e, Exception):
                    raise
        """, select=["RF006"])
    assert r.unsuppressed == []


def test_rf006_warns_on_silent_swallow_in_loop_function(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def run():
            while True:
                try:
                    step()
                except Exception:
                    continue

        def saver_loop():
            while alive():
                try:
                    persist()
                except Exception:
                    pass
        """, select=["RF006"])
    assert len(r.unsuppressed) == 2
    assert all(f.severity == "warning" for f in r.unsuppressed)


def test_rf006_quiet_on_handled_swallow_and_non_loop_functions(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def run():
            while True:
                try:
                    step()
                except Exception as e:
                    count(e)  # absorbed but accounted for

        def helper():  # not a long-running-loop name
            while True:
                try:
                    step()
                except Exception:
                    pass

        def run_once():
            try:  # not inside a while loop
                step()
            except Exception:
                pass
        """, select=["RF006"])
    assert r.unsuppressed == []


def test_rf006_live_tree_is_clean():
    """The violations RF006 found in this repo are fixed or carry a
    justified suppression — and stay that way."""
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")],
                      select=["RF006"])
    assert r.unsuppressed == []


# ---------------------------------------------------------------------------
# suppressions / cli / misc
# ---------------------------------------------------------------------------


def test_suppression_with_justification_suppresses(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def gate(platform):
            # lint: disable=RF002 — exercised by the suppression test
            return platform == "tpu"
        """)
    assert r.unsuppressed == []
    assert len(r.findings) == 1 and r.findings[0].suppressed
    assert "suppression test" in r.findings[0].justification


def test_suppression_without_justification_does_not_suppress(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def gate(platform):
            return platform == "tpu"  # lint: disable=RF002
        """)
    assert len(r.unsuppressed) == 1
    assert "no justification" in r.unsuppressed[0].message


# ---------------------------------------------------------------------------
# RF008 metric-name-drift
# ---------------------------------------------------------------------------


def test_rf008_fires_on_dynamic_metric_names(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu import telemetry

        def f(site, mode, n):
            telemetry.inc(f"chaos.injected.{site}.{mode}")
            name = "worker." + str(n)
            telemetry.observe(name, 1.0)
            telemetry.set_gauge("bus." + "depth", 2)
        """)
    assert [f.checker_id for f in r.unsuppressed] == ["RF008"] * 3


def test_rf008_quiet_on_static_names(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu import telemetry

        COLD_METRIC = "train.cold_epoch_s"

        class Names:
            EPOCH = "train.epoch_s"

        def f(cold):
            telemetry.inc("train.epochs")
            telemetry.observe(COLD_METRIC if cold else Names.EPOCH, 1.0)
            with telemetry.span("worker.epoch"):
                pass
        """)
    assert "RF008" not in _ids(r)


def test_rf008_tracks_from_import_aliases(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu.telemetry import inc as bump

        def f(reason):
            bump(f"gateway.shed.{reason}")
        """)
    assert [f.checker_id for f in r.unsuppressed] == ["RF008"]


def test_rf008_justified_suppression_honored(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu import telemetry

        def f(reason):
            # lint: disable=RF008 — bounded shed-reason enum
            telemetry.inc(f"gateway.shed.{reason}")
        """)
    assert "RF008" not in _ids(r)


def test_rf008_exempts_the_registry_itself(tmp_path):
    obs = tmp_path / "rafiki_tpu" / "obs"
    obs.mkdir(parents=True)
    (tmp_path / "rafiki_tpu" / "__init__.py").write_text("")
    (obs / "__init__.py").write_text("")  # module_name_for walks these
    f = obs / "inner.py"
    f.write_text("from rafiki_tpu import telemetry\n\n"
                 "def flush(name):\n"
                 "    telemetry.inc(f\"obs.flush.{name}\")\n")
    r = analyze_paths([str(f)], select=["RF008"])
    assert "RF008" not in _ids(r)


def test_rf008_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF008"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF008"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF009 wall-clock-duration
# ---------------------------------------------------------------------------


def test_rf009_fires_on_wall_clock_delta(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import time

        def measure(work):
            t0 = time.time()
            work()
            return time.time() - t0
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF009"]
    assert len(found) == 1 and "monotonic" in found[0].message


def test_rf009_quiet_on_legal_wall_clock_shapes(tmp_path):
    # deadline - time.time() (remaining budget against an absolute
    # cutoff), bare timestamps, and monotonic deltas are all fine.
    r = _analyze_snippet(tmp_path, """
        import time

        def remaining(deadline):
            return deadline - time.time()

        def stamp(rec):
            rec["ts"] = time.time()
            return rec

        def measure(work):
            t0 = time.monotonic()
            work()
            return time.monotonic() - t0
        """)
    assert "RF009" not in _ids(r)


def test_rf009_justified_suppression_honored(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import time

        def lease_cutoff(max_age_s):
            # lint: disable=RF009 — cutoff vs cross-process wall-clock beats
            return time.time() - max_age_s
        """)
    assert "RF009" not in _ids(r)


def test_rf009_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF009"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF009"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF010 nondeterministic-sim
# ---------------------------------------------------------------------------


def _twin_snippet(tmp_path, source, select=None):
    """Write the snippet INSIDE a rafiki_tpu/obs/twin/ package tree so
    module_name_for resolves it into RF010's scope."""
    twin = tmp_path / "rafiki_tpu" / "obs" / "twin"
    twin.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", tmp_path / "rafiki_tpu" / "obs",
              twin):
        (d / "__init__.py").write_text("")
    f = twin / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], select=select)


RF010_BAD = """
    import random
    import time

    def simulate_badly(n):
        rng = random.Random()            # OS entropy
        jitter = random.random()         # global stream
        t0 = time.monotonic()            # ambient clock
        return rng, jitter, t0
    """


def test_rf010_fires_on_each_entropy_source(tmp_path):
    r = _twin_snippet(tmp_path, RF010_BAD)
    found = [f for f in r.unsuppressed if f.checker_id == "RF010"]
    assert len(found) == 3
    messages = " ".join(f.message for f in found)
    assert "OS entropy" in messages
    assert "GLOBAL random stream" in messages
    assert "ambient clock" in messages


def test_rf010_scoped_to_twin_package_only(tmp_path):
    # The identical source OUTSIDE rafiki_tpu/obs/twin/ is legal:
    # entropy is only a defect where determinism is the contract.
    r = _analyze_snippet(tmp_path, RF010_BAD)
    assert "RF010" not in _ids(r)


def test_rf010_quiet_on_seeded_streams(tmp_path):
    r = _twin_snippet(tmp_path, """
        import random

        def simulate(seed, samples):
            rng = random.Random(f"{seed}:service")
            return samples[rng.randrange(len(samples))] + rng.random()
        """)
    assert "RF010" not in _ids(r)


def _train_twin_snippet(tmp_path, source, select=None):
    """Same as _twin_snippet but one level deeper — the train twin
    subpackage inherits the determinism contract verbatim."""
    train = tmp_path / "rafiki_tpu" / "obs" / "twin" / "train"
    train.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", tmp_path / "rafiki_tpu" / "obs",
              train.parent, train):
        (d / "__init__.py").write_text("")
    f = train / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], select=select)


def test_rf010_covers_train_subpackage(tmp_path):
    r = _train_twin_snippet(tmp_path, RF010_BAD)
    found = [f for f in r.unsuppressed if f.checker_id == "RF010"]
    assert len(found) == 3
    messages = " ".join(f.message for f in found)
    assert "OS entropy" in messages
    assert "GLOBAL random stream" in messages
    assert "ambient clock" in messages


def test_rf010_justified_suppression_honored(tmp_path):
    r = _twin_snippet(tmp_path, """
        import time

        def artifact(doc):
            # lint: disable=RF010 — metadata stamp, not simulation state
            doc["created_ts"] = time.time()
            return doc
        """)
    assert "RF010" not in _ids(r)


def test_rf010_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF010"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF010"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


def test_suppression_only_covers_named_ids(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def gate(platform):
            # lint: disable=RF005 — wrong id on purpose
            return platform == "tpu"
        """)
    assert [f.checker_id for f in r.unsuppressed] == ["RF002"]


def test_select_runs_only_requested_checkers(tmp_path):
    f = tmp_path / "both.py"
    f.write_text('import jax\n\ndef main():\n    return jax.devices()\n'
                 '\nx = "x" == "tpu"\n')
    r = analyze_paths([str(f)], select=["RF002"])
    assert _ids(r) == ["RF002"]


def test_module_name_for_package_files():
    assert module_name_for(
        os.path.join(REPO, "rafiki_tpu/bus/queues.py")) == "rafiki_tpu.bus.queues"
    assert module_name_for(os.path.join(REPO, "chip_smoke.py")) == "chip_smoke"


def test_cli_json_and_exit_codes(tmp_path, capsys):
    import json as _json

    from rafiki_tpu.analysis.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text('def gate(p):\n    return p == "tpu"\n')
    assert main([str(bad), "--format", "json"]) == 1
    payload = _json.loads(capsys.readouterr().out)
    assert payload["unsuppressed"] == 1
    assert payload["findings"][0]["checker"] == "RF002"

    good = tmp_path / "good.py"
    good.write_text('def gate(p):\n    return p != "cpu"\n')
    assert main([str(good), "--format", "json"]) == 0

    assert main([str(good), "--select", "NOPE01"]) == 2


# ---------------------------------------------------------------------------
# RF011 unjournaled-decision
# ---------------------------------------------------------------------------


def _advisor_snippet(tmp_path, source, select=None):
    """Write the snippet INSIDE a rafiki_tpu/advisor/ package tree so
    module_name_for resolves it into RF011's scope."""
    adv = tmp_path / "rafiki_tpu" / "advisor"
    adv.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", adv):
        (d / "__init__.py").write_text("")
    f = adv / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], select=select)


RF011_BAD = """
    class SneakyAdvisor:
        def _propose(self):
            return {"lr": 0.1}

        def _feedback(self, score, knobs):
            self._X.append(knobs)
    """


def test_rf011_fires_on_unjournaled_hooks(tmp_path):
    r = _advisor_snippet(tmp_path, RF011_BAD)
    found = [f for f in r.unsuppressed if f.checker_id == "RF011"]
    assert len(found) == 2
    assert all(f.severity == "error" for f in found)
    assert "obs sweep" in found[0].message


def test_rf011_scoped_to_advisor_package_only(tmp_path):
    # The identical source OUTSIDE rafiki_tpu/advisor/ is legal: the
    # audit contract binds engines, not arbitrary code with _propose.
    r = _analyze_snippet(tmp_path, RF011_BAD)
    assert "RF011" not in _ids(r)


def test_rf011_quiet_when_hooks_journal(tmp_path):
    r = _advisor_snippet(tmp_path, """
        from rafiki_tpu.obs.search import audit

        class GoodAdvisor:
            def _propose(self):
                knobs = {"lr": 0.1}
                audit.record_propose(self, knobs, {"phase": "fixed"})
                return knobs

            def _propose_batch(self, n):
                out = [self._propose() for _ in range(n)]
                audit.record_propose_batch(self, n, out, strategy="seq")
                return out

            def _feedback(self, score, knobs):
                audit.record_feedback(self, score, knobs)
        """)
    assert "RF011" not in _ids(r)


def test_rf011_quiet_on_member_import_and_raw_journal(tmp_path):
    # Both alias shapes count: a member imported from audit, and the
    # journal handle itself.
    r = _advisor_snippet(tmp_path, """
        from rafiki_tpu.obs.journal import journal
        from rafiki_tpu.obs.search.audit import record_feedback

        class DirectAdvisor:
            def _propose(self):
                knobs = {"lr": 0.1}
                journal.record("advisor", "propose", knobs=knobs)
                return knobs

            def _feedback(self, score, knobs):
                record_feedback(self, score, knobs)
        """)
    assert "RF011" not in _ids(r)


def test_rf011_exempts_abstract_raise_only_hooks(tmp_path):
    # BaseAdvisor._propose's shape: a docstring plus a bare raise
    # decides nothing, so there is nothing to journal.
    r = _advisor_snippet(tmp_path, """
        class AbstractAdvisor:
            def _propose(self):
                \"\"\"Engines override.\"\"\"
                raise NotImplementedError
        """)
    assert "RF011" not in _ids(r)


def test_rf011_justified_suppression_honored(tmp_path):
    r = _advisor_snippet(tmp_path, """
        class ShimAdvisor:
            # lint: disable=RF011 — test shim, inner engine journals
            def _feedback(self, score, knobs):
                self.inner.feedback(score, knobs)
        """)
    assert "RF011" not in _ids(r)


def test_rf011_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu")], select=["RF011"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF011"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF012 undamped-actuator
# ---------------------------------------------------------------------------


RF012_BAD = """
    def burst(lane, handle_cls):
        lane.scale_to(8)
        handle = handle_cls.ElasticHandle()
        handle.request(2)
    """


def test_rf012_fires_on_direct_actuator_calls(tmp_path):
    r = _analyze_snippet(tmp_path, RF012_BAD)
    found = [f for f in r.unsuppressed if f.checker_id == "RF012"]
    assert len(found) == 2
    assert all(f.severity == "error" for f in found)
    assert "AutoscaleController" in found[0].message


def test_rf012_exempts_autoscale_package(tmp_path):
    # The identical source INSIDE rafiki_tpu/autoscale/ is the surface
    # itself — the controller must be able to call its own actuators.
    pkg = tmp_path / "rafiki_tpu" / "autoscale"
    pkg.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", pkg):
        (d / "__init__.py").write_text("")
    f = pkg / "snippet.py"
    f.write_text(textwrap.dedent(RF012_BAD))
    r = analyze_paths([str(f)])
    assert "RF012" not in _ids(r)


def test_rf012_fires_on_lane_internals(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def sneak(lane):
            lane._spawn_one()
            lane._drain_one()
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF012"]
    assert len(found) == 2


def test_rf012_quiet_on_unrelated_request_calls(tmp_path):
    # .request on HTTP sessions / arbitrary objects is NOT the
    # actuator surface: only a name bound to ElasticHandle(...) is.
    r = _analyze_snippet(tmp_path, """
        import requests

        def fetch(session):
            session.request("GET", "/x")
            return requests.Session().request("GET", "/y")
        """)
    assert "RF012" not in _ids(r)


def test_rf012_tracks_elastic_handle_binding(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu.scheduler.mesh import ElasticHandle

        def grow():
            h = ElasticHandle()
            h.request(1)
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF012"]
    assert len(found) == 1
    assert "ElasticHandle" in found[0].message


def test_rf012_justified_suppression_honored(tmp_path):
    r = _analyze_snippet(tmp_path, """
        def teardown(lane):
            # lint: disable=RF012 — teardown after controller stop
            lane.scale_to(0)
        """)
    assert "RF012" not in _ids(r)


def test_rf012_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF012"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF012"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF013 undurable-decision
# ---------------------------------------------------------------------------


def _scheduler_snippet(tmp_path, source, select=None):
    """Write the snippet INSIDE a rafiki_tpu/scheduler/ package tree so
    module_name_for resolves it into RF013's scope."""
    sched = tmp_path / "rafiki_tpu" / "scheduler"
    sched.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", sched):
        (d / "__init__.py").write_text("")
    f = sched / "snippet.py"
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], select=select)


RF013_BAD = """
    def claim_and_assign(store, runner, knobs):
        trial = store.create_trial(knobs)
        runner.tasks.put(("pack", [trial]))
        runner.tasks.put(("resume", trial["id"]))
    """


def test_rf013_fires_on_undurable_mutations(tmp_path):
    r = _scheduler_snippet(tmp_path, RF013_BAD)
    found = [f for f in r.unsuppressed if f.checker_id == "RF013"]
    assert len(found) == 3
    assert all(f.severity == "error" for f in found)
    assert "unresumable" in found[0].message


def test_rf013_scoped_to_scheduler_package_only(tmp_path):
    # The identical source OUTSIDE rafiki_tpu/scheduler/ is legal: the
    # WAL contract binds the sweep control plane, not arbitrary code.
    r = _analyze_snippet(tmp_path, RF013_BAD)
    assert "RF013" not in _ids(r)


def test_rf013_quiet_when_intent_precedes(tmp_path):
    r = _scheduler_snippet(tmp_path, """
        def claim(store, wal, runner, knobs):
            txn = wal.intent("budget_claim", knobs_hash="h")
            trial = store.create_trial(knobs)
            wal.commit(txn, "budget_claim", trial_id=trial["id"])
            runner.tasks.put(("pack", [trial]))
        """)
    assert "RF013" not in _ids(r)


def test_rf013_guarded_wal_idiom_counts(tmp_path):
    # The degraded no-WAL mode: the intent call is conditionally
    # skipped at runtime but lexically present — recovery handles the
    # missing log loudly; the static contract is satisfied.
    r = _scheduler_snippet(tmp_path, """
        def backfill(store, wal, knobs):
            txn = None if wal is None else wal.intent("backfill")
            return store.create_trial(knobs)
        """)
    assert "RF013" not in _ids(r)


def test_rf013_mutation_before_intent_still_fires(tmp_path):
    # Ordering matters: an intent AFTER the mutation logs nothing the
    # reconciler can use for a crash in between.
    r = _scheduler_snippet(tmp_path, """
        def backwards(store, wal, knobs):
            trial = store.create_trial(knobs)
            wal.intent("budget_claim")
            return trial
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF013"]
    assert len(found) == 1


def test_rf013_nested_closure_is_own_scope(tmp_path):
    # The enclosing function's intent does NOT cover a closure that
    # mutates later, on its own schedule: the closure needs its own.
    r = _scheduler_snippet(tmp_path, """
        def outer(store, wal, knobs):
            wal.intent("budget_claim")

            def backfill():
                return store.create_trial(knobs)
            return backfill
        """)
    found = [f for f in r.unsuppressed if f.checker_id == "RF013"]
    assert len(found) == 1


def test_rf013_ignores_non_assignment_puts(tmp_path):
    r = _scheduler_snippet(tmp_path, """
        def drain(runner, q):
            runner.tasks.put(("stop", None))
            q.put("anything")
        """)
    assert "RF013" not in _ids(r)


def test_rf013_justified_suppression_honored(tmp_path):
    r = _scheduler_snippet(tmp_path, """
        def fake_claim(store, knobs):
            # lint: disable=RF013 — test double; prod path WALs in mesh
            return store.create_trial(knobs)
        """)
    assert "RF013" not in _ids(r)


def test_rf013_current_scheduler_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu")], select=["RF013"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF013"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF014/RF016 — regression fixtures for the live violations this
# analysis surfaced when first enabled (fixed in the bench script,
# scripts/smoke_trial_pack.py, scripts/perf_smoke.py, and closed by the
# `obs decisions` reader). Each fixture freezes the *fixed* shape as
# quiet and the pre-fix shape as firing, so the fixes can't regress.
# ---------------------------------------------------------------------------


def _tree(tmp_path, files):
    import textwrap as _tw
    tmp_path.mkdir(parents=True, exist_ok=True)
    paths = []
    for name, src in files.items():
        f = tmp_path / name
        f.write_text(_tw.dedent(src))
        paths.append(str(f))
    return paths


def test_rf016_bench_trials_regression(tmp_path):
    # the pre-fix bench script: two reads of RAFIKI_BENCH_TRIALS with mode-
    # specific defaults "3"/"30" → divergent
    r = analyze_paths(_tree(tmp_path, {"bench_old.py": """
        import os
        def scale(mode):
            if mode == "cpu":
                return int(os.environ.get("RAFIKI_BENCH_TRIALS", "3"))
            return int(os.environ.get("RAFIKI_BENCH_TRIALS", "30"))
        """}), select=["RF016"])
    assert any("RAFIKI_BENCH_TRIALS" in f.message for f in r.unsuppressed)
    # the fix: one env read, mode-specific fallback in code
    r = analyze_paths(_tree(tmp_path / "fixed", {"bench_new.py": """
        import os
        def scale(mode):
            env_trials = os.environ.get("RAFIKI_BENCH_TRIALS")
            if mode == "cpu":
                return int(env_trials) if env_trials else 3
            return int(env_trials) if env_trials else 30
        """}), select=["RF016"])
    assert r.unsuppressed == []


def test_rf016_trial_pack_setdefault_regression(tmp_path):
    # pre-fix smoke scripts defaulted RAFIKI_TRIAL_PACK to "4" while
    # the worker defaults to "1" → divergent
    worker = """
        import os
        PACK = int(os.environ.get("RAFIKI_TRIAL_PACK", "1"))
        """
    r = analyze_paths(_tree(tmp_path, {"worker.py": worker,
                                       "smoke_old.py": """
        import os
        pack = max(2, int(os.environ.get("RAFIKI_TRIAL_PACK", "4")))
        """}), select=["RF016"])
    assert any("RAFIKI_TRIAL_PACK" in f.message for f in r.unsuppressed)
    # the fix: setdefault (a write, not a defaulted read) + required read
    r = analyze_paths(_tree(tmp_path / "fixed", {"worker.py": worker,
                                                 "smoke_new.py": """
        import os
        os.environ.setdefault("RAFIKI_TRIAL_PACK", "4")
        pack = max(2, int(os.environ["RAFIKI_TRIAL_PACK"]))
        """}), select=["RF016"])
    assert r.unsuppressed == []


def test_rf014_decisions_reader_closes_control_plane_records(tmp_path):
    # the four control-plane records were write-only until the
    # `obs decisions` CLI reader; its elif-chain shape must keep
    # counting as a reader for every branch
    writers = """
        def emit(journal):
            journal.record("serving", "route", reason="warm")
            journal.record("gateway", "shed", reason="capacity")
            journal.record("gateway", "breaker_transition", state="open")
            journal.record("twin", "placement", plan="p0")
        """
    r = analyze_paths(_tree(tmp_path, {"writers.py": writers}),
                      select=["RF014"])
    assert len(r.unsuppressed) == 4  # write-only: all four flagged
    r = analyze_paths(_tree(tmp_path / "fixed", {"writers.py": writers,
                                                 "decisions.py": """
        def decisions(recs):
            for r in recs:
                kind, name = r.get("kind"), r.get("name")
                if kind == "serving" and name == "route":
                    yield "route", r.get("reason")
                elif kind == "gateway" and name == "shed":
                    yield "shed", r.get("reason")
                elif kind == "gateway" and name == "breaker_transition":
                    yield "breaker", r.get("state")
                elif kind == "twin" and name == "placement":
                    yield "twin", r.get("plan")
        """}), select=["RF014"])
    assert r.unsuppressed == []


# ---------------------------------------------------------------------------
# RF017 unbounded-per-tenant-state
# ---------------------------------------------------------------------------


RF017_BAD = """
    from rafiki_tpu.tenancy import TenantFabric

    class Ledger:
        def __init__(self):
            self.stats = {}
            self.queues = {}

        def note(self, tenant_id, v):
            self.stats[tenant_id] = v
            self.queues.setdefault(tenant_id, []).append(v)
    """


def test_rf017_fires_on_tenant_keyed_writes(tmp_path):
    r = _analyze_snippet(tmp_path, RF017_BAD, select=["RF017"])
    found = [f for f in r.unsuppressed if f.checker_id == "RF017"]
    assert len(found) == 2  # the Store subscript AND the setdefault
    assert all("BoundedTenantMap" in f.message for f in found)


def test_rf017_scoped_to_tenancy_touching_modules(tmp_path):
    # The identical leak WITHOUT a rafiki_tpu.tenancy import is out of
    # scope: unbounded-keyed-state is only a wire-driven leak where
    # tenant ids actually flow.
    r = _analyze_snippet(tmp_path, RF017_BAD.replace(
        "from rafiki_tpu.tenancy import TenantFabric", "import os"),
        select=["RF017"])
    assert "RF017" not in _ids(r)


def test_rf017_quiet_with_eviction_or_cap(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu.tenancy import TenantFabric

        class Pruned:
            def __init__(self):
                self.stats = {}

            def note(self, tenant_id, v):
                self.stats[tenant_id] = v
                while len(self.stats) > 64:
                    self.stats.pop(next(iter(self.stats)))
        """, select=["RF017"])
    assert "RF017" not in _ids(r)


def test_rf017_quiet_on_non_tenant_keys(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu.tenancy import TenantFabric

        class ByReason:
            def __init__(self):
                self.shed = {}

            def note(self, reason):
                self.shed[reason] = self.shed.get(reason, 0) + 1
        """, select=["RF017"])
    assert "RF017" not in _ids(r)


def test_rf017_justified_suppression_honored(tmp_path):
    r = _analyze_snippet(tmp_path, """
        from rafiki_tpu.tenancy import TenantFabric

        class ConfigMap:
            def __init__(self, raw):
                self.tiers = {}
                for tenant, tier in raw.items():
                    # lint: disable=RF017 — construction-time config, not wire-keyed growth
                    self.tiers[tenant] = tier
        """, select=["RF017"])
    assert "RF017" not in _ids(r)


def test_rf017_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF017"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF017"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF018 unaudited-speculation
# ---------------------------------------------------------------------------


RF018_BAD_MUTATION = """
    class LeakyAdvisor:
        def adopt_rows(self, rows):
            for x, y in rows:
                self._X.append(x)
                self._y.append(y)

        def drop_worst(self):
            del self._y[0]
    """


def test_rf018_fires_on_training_data_mutation_outside_surfaces(tmp_path):
    r = _advisor_snippet(tmp_path, RF018_BAD_MUTATION, select=["RF018"])
    found = [f for f in r.unsuppressed if f.checker_id == "RF018"]
    # append(x), append(y), del — three mutation sites
    assert len(found) == 3
    assert all(f.severity == "error" for f in found)
    assert "byte-identity" in found[0].message


def test_rf018_fires_on_unaudited_kill_site(tmp_path):
    r = _advisor_snippet(tmp_path, """
        class SilentKiller:
            def kill_verdict(self, h, epoch):
                st = self.trials[h]
                st.killed = True
                return st.fit
        """, select=["RF018"])
    found = [f for f in r.unsuppressed if f.checker_id == "RF018"]
    assert len(found) == 1
    assert "record_kill" in found[0].message


def test_rf018_scoped_to_advisor_package_only(tmp_path):
    # The identical source OUTSIDE rafiki_tpu/advisor/ is legal: the
    # contract binds the advisor package, not arbitrary code.
    r = _analyze_snippet(tmp_path, RF018_BAD_MUTATION, select=["RF018"])
    assert "RF018" not in _ids(r)


def test_rf018_quiet_on_sanctioned_surfaces_and_audited_kills(tmp_path):
    r = _advisor_snippet(tmp_path, """
        from rafiki_tpu.obs.search import audit

        class GoodAdvisor:
            def _feedback(self, score, knobs):
                self._X.append(knobs)
                self._y.append(score)
                audit.record_feedback(self, score, knobs)

            def _speculate(self, score, knobs):
                self._X.append(knobs)
                self._y.append(score)

            def _correct(self, score, knobs, predicted):
                self._y[0] = score
                audit.record_correct(self, knobs, predicted, score)

            def kill_verdict(self, h, epoch, best):
                st = self.trials[h]
                st.killed = True
                audit.record_kill(st.knobs, st.fit, epoch, best,
                                  config={}, trial_id=None)
                return st.fit
        """, select=["RF018"])
    assert "RF018" not in _ids(r)


def test_rf018_pure_kill_predicate_is_not_a_decision_site(tmp_path):
    # KillConfig.should_kill's shape: comparisons only, no state
    # mutated — a predicate, not a decision; the caller journals.
    r = _advisor_snippet(tmp_path, """
        class KillConfig:
            def should_kill(self, fit, epoch, best):
                return fit.hi < best - self.margin
        """, select=["RF018"])
    assert "RF018" not in _ids(r)


def test_rf018_justified_suppression_honored(tmp_path):
    r = _advisor_snippet(tmp_path, """
        class RebuildShim:
            def rebuild(self, rows):
                for x, y in rows:
                    # lint: disable=RF018 — rows come FROM advisor/feedback records, already journaled
                    self._X.append(x)
        """, select=["RF018"])
    assert "RF018" not in _ids(r)


def test_rf018_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu")], select=["RF018"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF018"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]


# ---------------------------------------------------------------------------
# RF019 full-gather-hazard
# ---------------------------------------------------------------------------


RF019_BAD_GATHER = """
    import jax
    import numpy as np
    from rafiki_tpu.shard import ShardedTrainLoop, train_sharded

    def snapshot(model, uri, devices):
        loop, history = train_sharded(model, uri, devices)
        host = jax.device_get(loop.state)
        return np.asarray(host), history

    def peek(init_fn, apply_fn, loss_fn, devices):
        loop = ShardedTrainLoop(init_fn, apply_fn, loss_fn,
                                devices=devices)
        st = loop.state
        return np.asarray(st)
    """


def test_rf019_fires_on_full_gather_of_group_state(tmp_path):
    r = _analyze_snippet(tmp_path, RF019_BAD_GATHER, select=["RF019"])
    found = [f for f in r.unsuppressed if f.checker_id == "RF019"]
    # device_get(loop.state), np.asarray(host)... host is not tracked
    # (one-hop chains only) — device_get + np.asarray(st) = 2 sites
    assert len(found) == 2
    assert all(f.severity == "error" for f in found)
    assert "gather_state" in found[0].message


def test_rf019_quiet_on_sanctioned_paths(tmp_path):
    # save_sharded of loop.state and gather_state are THE manifest
    # path; device_get of anything untainted is ordinary jax.
    r = _analyze_snippet(tmp_path, """
        import jax
        from rafiki_tpu.shard import (gather_state, save_sharded,
                                      train_sharded)

        def checkpoint(store, tid, model, uri, devices):
            loop, _hist = train_sharded(model, uri, devices)
            save_sharded(store, tid, 0, loop.state, loop.width)
            return gather_state(loop.state)

        def other(x):
            return jax.device_get(x)
        """, select=["RF019"])
    assert "RF019" not in _ids(r)


def test_rf019_exempts_the_checkpoint_module_itself(tmp_path):
    shard = tmp_path / "rafiki_tpu" / "shard"
    shard.mkdir(parents=True)
    for d in (tmp_path / "rafiki_tpu", shard):
        (d / "__init__.py").write_text("")
    f = shard / "checkpoint.py"
    f.write_text(textwrap.dedent(RF019_BAD_GATHER))
    r = analyze_paths([str(f)], select=["RF019"])
    assert "RF019" not in _ids(r)


def test_rf019_justified_suppression_honored(tmp_path):
    r = _analyze_snippet(tmp_path, """
        import numpy as np
        from rafiki_tpu.shard import train_sharded

        def debug_norms(model, uri, devices):
            loop, _h = train_sharded(model, uri, devices)
            # lint: disable=RF019 — scalar leaf norms only, bounded copy
            return np.asarray(loop.state)
        """, select=["RF019"])
    assert "RF019" not in _ids(r)


def test_rf019_current_tree_is_clean():
    r = analyze_paths([os.path.join(REPO, "rafiki_tpu"),
                       os.path.join(REPO, "scripts")], select=["RF019"])
    mine = [f for f in r.unsuppressed if f.checker_id == "RF019"]
    assert mine == [], [f"{f.path}:{f.line}" for f in mine]
