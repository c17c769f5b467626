"""Backend helpers: platform pinning, the compile cache, device peaks.

An explicit CPU request (``JAX_PLATFORMS=cpu``) is applied before the
first backend use: every entry point that must run on CPU (tests,
multichip dryrun, subprocess workers asked for cpu) funnels through
``honor_env_platform``/``force_cpu_backend`` instead of hand-rolling
the ``jax.config`` update. Nothing here initialises a backend — that
is left to the process that owns the chip.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def ensure_host_device_count(n_devices: int) -> None:
    """Ensure XLA_FLAGS requests >= n_devices virtual CPU devices.

    Replaces an inherited smaller count (e.g. a scheduler-injected
    ``=1``) rather than deferring to it. Must run before jax's CPU
    backend initializes to take effect.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        flags = flags[: m.start(1)] + str(n_devices) + flags[m.end(1):]
    else:
        return
    os.environ["XLA_FLAGS"] = flags


def host_device_count_flag(n_devices: int) -> str:
    """The XLA_FLAGS fragment requesting n virtual CPU devices (the
    single source of truth for the flag's spelling)."""
    return f"{_COUNT_FLAG}={n_devices}"


def force_cpu_backend(n_devices: int | None = None) -> None:
    """Pin jax to the CPU backend, optionally with >= n virtual devices."""
    if n_devices is not None:
        ensure_host_device_count(n_devices)
    import jax

    jax.config.update("jax_platforms", "cpu")


def honor_env_platform() -> bool:
    """Apply a ``JAX_PLATFORMS=cpu`` request before the first backend
    use. Entry points that respect the env (quickstart, serve, workers,
    bench) call this once before touching jax devices. Returns True
    when a CPU request was applied."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        force_cpu_backend()
        return True
    return False


#: Where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is unset: one fixed, git-ignored directory inside the checkout. The
#: directory is part of the cache key, so it must never move between
#: runs (no ``~``, data dir, mkdtemp, pid or time in it).
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives for this process —
    resolved without touching jax."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_COMPILE_CACHE_DIR))


def enable_compilation_cache() -> str:
    """Turn on XLA's persistent (on-disk) compilation cache.

    The in-process program cache (ops.train.get_program) amortizes
    compiles across trials of ONE worker process; this cache amortizes
    them across processes and restarts — the second process-per-chip
    worker to hit a given (program, topology) loads the serialized
    executable from disk instead of recompiling. Every entry point
    that compiles (subprocess workers, bench, admin boot, chip_smoke,
    the quickstart) calls this one function.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already uses that
    directory and no other is set in code; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Returns the directory in use.
    """
    import jax

    cache_dir = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(os.environ.get("RAFIKI_XLA_CACHE_MIN_S", "1.0")))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


#: Peak dense bf16 FLOP/s per chip, keyed by ``device.device_kind`` —
#: the one MFU denominator (obs/perf/profiler.py and the benchmark's
#: readers divide by it). Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``. A device that
    is not in the table is an error, never a default."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)}") from None
