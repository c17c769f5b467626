"""utils/backend.py: a compile cache that can be placed, device peaks
keyed by device kind, and a process scheduler that stays off jax.

The persistent cache's directory is part of its key, so it must be the
same in every process and every run: ``JAX_COMPILATION_CACHE_DIR`` when
that is set (and then nothing else is set in code), else one fixed path
inside the checkout — never ``~``, ``Config.data_dir``, a mkdtemp, a pid
or a time.
"""

import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

import jax

from rafiki_tpu.utils import backend

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def cache_config():
    """Put jax's cache settings back after a test that placed the cache
    (they are process-global, and this worker runs other tests)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_cache_dir_from_the_standard_variable_is_left_to_jax(
        tmp_path, monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert backend.enable_compilation_cache() == str(tmp_path / "placed")
    assert jax.config.jax_compilation_cache_dir == before, \
        "with the variable set, no directory is set in code"
    assert (tmp_path / "placed").is_dir()


def test_default_cache_dir_is_one_fixed_path_inside_the_checkout(
        tmp_path, monkeypatch, cache_config):
    from rafiki_tpu.config import Config, get_config, set_config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = get_config()
    got = []
    try:
        for name in ("a", "b"):
            set_config(Config(data_dir=tmp_path / name))
            got.append(backend.enable_compilation_cache())
    finally:
        set_config(prev)
    assert got[0] == got[1] == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got[0]
    path = Path(got[0])
    assert path.parent == REPO  # in the checkout, not ~ or a data dir
    assert str(os.getpid()) not in path.name
    assert not any(ch.isdigit() for ch in path.name)  # no pid, no time
    assert not path.is_relative_to(tempfile.gettempdir())
    assert not path.is_relative_to(tmp_path)


def test_default_cache_dir_is_git_ignored():
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("kind,ok", [
    ("TPU v5 lite", True), ("cpu", False), ("TPU v4", False), ("", False)])
def test_peak_flops_is_keyed_by_device_kind_and_unknown_is_an_error(kind, ok):
    if ok:
        assert backend.peak_bf16_flops(kind) == 197e12
    else:
        with pytest.raises(ValueError, match="no peak"):
            backend.peak_bf16_flops(kind)


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("cpu", None), ("TPU v9", None)])
def test_profiler_claims_mfu_only_for_a_listed_device_kind(
        kind, peak, monkeypatch):
    """The live profiler: a kind the table does not list gets no MFU —
    never the v5e peak."""
    from rafiki_tpu.obs.perf import profiler

    profiler.reset()
    monkeypatch.setattr(profiler, "_device_kind", lambda: kind)
    try:
        key = ("test_backend", kind)
        with profiler._lock:
            profiler._get(key, "serial", 1).cost = {"flops": 1e12}
        profiler.note_epoch(key, 0.5)
        summary = profiler.snapshot()["programs"][profiler.key_hash(key)]
        assert summary["achieved_flops_s"] == pytest.approx(2e12)
        if peak is None:
            assert "mfu" not in summary
        else:
            assert summary["mfu"] == pytest.approx(2e12 / peak)
    finally:
        profiler.reset()


@pytest.mark.parametrize("value,want", [
    ("cpu", "cpu"), ("CPU", "cpu"), (None, "tpu"), ("", "tpu"),
    ("tpu", "tpu"), ("tpu,cpu", "tpu")])
def test_scheduler_reads_its_platform_from_the_environment(
        value, want, monkeypatch):
    from rafiki_tpu.scheduler.process import platform_from_env

    if value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", value)
    assert platform_from_env() == want


def test_worker_env_names_its_platform_or_refuses():
    from rafiki_tpu.scheduler import worker_device_env

    assert worker_device_env("tpu", 0)["JAX_PLATFORMS"] == "tpu"
    assert worker_device_env("cpu", 0)["JAX_PLATFORMS"] == "cpu"
    with pytest.raises(ValueError, match="no worker environment"):
        worker_device_env("gpu", 0)


_SCHEDULER_PROBE = """
import sys
from jax._src import xla_bridge
from rafiki_tpu.scheduler import ProcessScheduler
from rafiki_tpu.store import MetaStore, ParamsStore
from tests.test_scheduler import FF_SOURCE, TRAIN, VAL

tmp = sys.argv[1]
store = MetaStore(tmp + "/meta.sqlite3")
params = ParamsStore(tmp + "/params")
model = store.create_model("tinyff", "IMAGE_CLASSIFICATION", None,
                           FF_SOURCE, "TinyFF")
job = store.create_train_job("probe", "IMAGE_CLASSIFICATION", None,
                             TRAIN, VAL, {"MODEL_TRIAL_COUNT": 1})
store.create_sub_train_job(job["id"], model["id"])
result = ProcessScheduler(store, params).run_train_job(
    job["id"], n_workers=1, advisor_kind="random")  # platform from the env
print("STATUS", result.status, [t["status"] for t in result.trials])
print("BACKENDS_INITIALIZED", xla_bridge.backends_are_initialized())
"""


def test_process_scheduler_initialises_no_jax_backend(tmp_path):
    """One process per chip: the scheduler process takes its platform
    from the environment and must not have initialised a jax backend
    when ``run_train_job`` returns — on a TPU host that would take the
    chip its worker needs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SCHEDULER_PROBE, str(tmp_path)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "STATUS COMPLETED ['COMPLETED']" in r.stdout, r.stdout
    assert "BACKENDS_INITIALIZED False" in r.stdout, r.stdout
