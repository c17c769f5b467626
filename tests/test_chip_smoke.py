"""chip_smoke.py's contract, pinned on the CPU.

The driver reads ONE thing from the script: the last line of its
standard output, a JSON object with exactly the keys ``ok`` and
``device`` (``device`` exactly ``platform``, ``kind``, ``count``), and the
exit code. A run that finds no accelerator exits non-zero and prints no
result. These tests hold the script to that without a chip: the failing
paths through the real command line, the passing path at a tiny size
through ``main``'s internal arguments (the command line has no such
option).
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"

sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402  (imports nothing of jax or the package)


def _run(cmd, env_extra=None, env_drop=(), cwd=None, timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_extra or {})
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=cwd, timeout=timeout)


@pytest.mark.parametrize("ok", [True, False])
def test_final_line_has_exactly_the_contract_keys(ok):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "id": 0, "seconds": 12.5}  # extras must not leak through
    line = chip_smoke.final_line(ok, device)
    assert set(line) == {"ok", "device"}
    assert set(line["device"]) == {"platform", "kind", "count"}
    assert line["ok"] is ok
    assert line == json.loads(json.dumps(line))
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}


def test_cpu_request_exits_nonzero_and_prints_no_result():
    r = _run([sys.executable, str(SCRIPT)], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == chip_smoke.EXIT_NO_ACCELERATOR != 0
    assert r.stdout == "", "a run with no accelerator prints no result"
    assert "no result" in r.stderr


def test_no_chip_exits_nonzero_and_prints_no_result():
    """No CPU request, and no chip either: the worker subprocess cannot
    take one (that is reported, never papered over with a CPU run) and
    this process's own jax finds only the CPU."""
    r = _run([sys.executable, str(SCRIPT)],
             {"RAFIKI_WORKER_MAX_RESTARTS": "0"}, env_drop=("JAX_PLATFORMS",))
    assert r.returncode == chip_smoke.EXIT_NO_ACCELERATOR
    assert r.stdout == ""
    assert '"ok": true' not in r.stdout + r.stderr
    assert "check process.job_completed: FAILED" in r.stderr
    assert "process.parent_stayed_off_jax: ok" in r.stderr


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail, not report."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
             env_drop=("JAX_PLATFORMS", "PYTHONPATH"))
    assert r.returncode != 0
    assert r.stdout == ""


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """The whole one-chip path at a tiny size on the CPU, as a
    subprocess, with the flight recorder installed (RAFIKI_LOG_DIR set,
    inherited by the worker subprocess it spawns)."""
    log_dir = tmp_path_factory.mktemp("chip-smoke-journal")
    code = ("import sys, chip_smoke; "
            "sys.exit(chip_smoke.main([], size=chip_smoke.TINY, "
            "platform='cpu'))")
    r = _run([sys.executable, "-c", code],
             {"JAX_PLATFORMS": "cpu", "RAFIKI_LOG_DIR": str(log_dir)},
             cwd=REPO, timeout=600)
    return r, log_dir


def test_tiny_run_passes_every_phase(tiny_run):
    r, _ = tiny_run
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "FAILED" not in r.stdout
    for phase in ("process", "sweep", "serve", "packed"):
        assert f"[chip_smoke] phase {phase}: start" in r.stdout


def test_tiny_run_last_stdout_line_is_the_contract_object(tiny_run):
    r, _ = tiny_run
    assert r.stdout.endswith("\n")
    lines = r.stdout.splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["ok"] is True
    assert (last["device"]["platform"], last["device"]["kind"]) == ("cpu", "cpu")
    assert isinstance(last["device"]["count"], int) and last["device"]["count"] >= 1


def test_tiny_run_stdout_holds_only_report_lines(tiny_run):
    """A child worker was spawned and the flight recorder installed in
    both processes — and still nothing but this script's own report
    lines reached stdout, before or after the final line."""
    r, log_dir = tiny_run
    lines = r.stdout.splitlines()
    assert all(l.startswith("[chip_smoke] ") for l in lines[:-1]), \
        [l for l in lines[:-1] if not l.startswith("[chip_smoke] ")][:5]
    assert sum(l.startswith("{") for l in lines) == 1
    assert "check process.all_trials_completed: ok" in r.stdout
    journals = sorted(p.name for p in log_dir.glob("journal-*.jsonl"))
    assert any(n.startswith("journal-chip-smoke-") for n in journals), journals
    assert any(n.startswith("journal-train-worker-") for n in journals), journals
