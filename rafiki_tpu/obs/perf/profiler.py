"""Continuous profiler: per-program cost model x observed step times.

Two data feeds, one store:

* **Cost capture** — at a program's first epoch the train loop hands
  its jitted callable (plus example args) to :func:`capture_cost`,
  which AOT-lowers and compiles it and reads XLA's cost analysis:
  flops, bytes accessed, and (where the backend reports it) a peak
  device-memory estimate. One extra compile per program key per
  process — bounded, and switchable via ``RAFIKI_PERF_COST_CAPTURE=0``.
  Captured costs are journaled (``perf/cost``) so they survive the
  process and can be joined cross-process by the CLI.

* **Step sampling** — every epoch the train loop calls
  :func:`note_epoch` with the measured wall split. Warm samples feed a
  per-program :class:`~rafiki_tpu.obs.perf.anomaly.EwmaMad` detector;
  an anomalous epoch journals ``perf/anomaly``, bumps the
  ``perf.anomalies`` counter, and charges the excess wall over the
  expected mean to the goodput ledger's ``badput_s`` bucket — time the
  hardware spent but the baseline says it shouldn't have.

The joined view (model flops / observed step seconds = achieved
FLOP/s, over peak = MFU) is exposed three ways: the ``perf`` telemetry
collector (so ``GET /metrics`` and prom exposition pick it up for
free), the ``perf/cost``+``perf/step`` journal records, and the
``python -m rafiki_tpu.obs profile`` CLI that renders the roofline
join. Program identities are long key reprs; metrics key on a short
sha1 prefix (``key_hash``) and the full repr travels in the journal.

Import-light by design: jax is only touched inside guarded helpers,
so the obs CLI can read journals on boxes with no accelerator stack.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict, deque
from typing import Any, Dict, Optional

from rafiki_tpu import telemetry
from rafiki_tpu.obs.journal import journal
from rafiki_tpu.obs.ledger import ledger
from rafiki_tpu.obs.perf.anomaly import EwmaMad

ENV_COST_CAPTURE = "RAFIKI_PERF_COST_CAPTURE"

#: Bounded stores: distinct programs per process / warm samples per program.
MAX_PROGRAMS = 64
STEP_RING = 256


def _key_str(key: Any) -> str:
    return key if isinstance(key, str) else repr(key)


def key_hash(key: Any) -> str:
    return hashlib.sha1(_key_str(key).encode()).hexdigest()[:10]


def cost_capture_enabled() -> bool:
    return os.environ.get(ENV_COST_CAPTURE, "1") not in ("0", "false", "off")


class _ProgramStats:
    """One program's cost model + observed-step reservoir."""

    def __init__(self, key: Any, kind: str, k: int):
        self.key = _key_str(key)
        self.hash = key_hash(key)
        self.kind = kind
        self.k = int(k)
        self.cost: Optional[Dict[str, Any]] = None
        self.warm = deque(maxlen=STEP_RING)
        self.warm_count = 0
        self.warm_sum = 0.0
        self.cold_count = 0
        self.cold_sum = 0.0
        self.feed_sum = 0.0
        self.detector = EwmaMad()
        self.cold_detector = EwmaMad(warmup=2)

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"kind": self.kind, "k": self.k,
                               "epochs": self.warm_count,
                               "cold_epochs": self.cold_count}
        if self.warm_count:
            out["step_mean_s"] = self.warm_sum / self.warm_count
            ordered = sorted(self.warm)
            out["step_p50_s"] = ordered[len(ordered) // 2]
            out["step_min_s"] = ordered[0]
        if self.cold_count:
            out["compile_mean_s"] = self.cold_sum / self.cold_count
        if self.feed_sum:
            out["feed_s"] = self.feed_sum
        if self.cost:
            out.update({k: v for k, v in self.cost.items() if v is not None})
            flops = self.cost.get("flops")
            p50 = out.get("step_p50_s")
            if flops and p50:
                out["achieved_flops_s"] = flops / p50
                peak = _peak_flops()
                if peak is not None:
                    out["mfu"] = flops / p50 / peak
        return out


_lock = threading.Lock()
_programs: "OrderedDict[str, _ProgramStats]" = OrderedDict()
_hbm_peak = 0.0
_mem_broken = False
#: One-element list holding this process's peak (or None for a device
#: kind with no peak on record), looked up once; empty until the first
#: MFU question.
_peak_cache: list = []


def _get(key: Any, kind: str, k: int) -> _ProgramStats:
    ks = _key_str(key)
    stats = _programs.get(ks)
    if stats is None:
        stats = _ProgramStats(key, kind, k)
        _programs[ks] = stats
        while len(_programs) > MAX_PROGRAMS:
            _programs.popitem(last=False)
    return stats


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of this process's device kind, from the one
    table in utils.backend. A kind the table does not list (the CPU
    included) gets no MFU — and one ``perf/no_peak`` journal record
    saying why — never another device's peak."""
    if not _peak_cache:
        from rafiki_tpu.utils.backend import PEAK_BF16_FLOPS

        kind = _device_kind()
        _peak_cache.append(PEAK_BF16_FLOPS.get(kind))
        if _peak_cache[0] is None:
            # lint: disable=RF014 — breadcrumb for a human reading why a profile has no MFU column
            journal.record("perf", "no_peak", device_kind=kind,
                           known=sorted(PEAK_BF16_FLOPS))
    return _peak_cache[0]


def _sample_device_mem() -> None:
    """Track the process-lifetime peak of device bytes_in_use. CPU
    backends report no memory_stats — one failed probe disables it."""
    global _hbm_peak, _mem_broken
    if _mem_broken:
        return
    try:
        import jax

        total = 0.0
        seen = False
        for dev in jax.local_devices():
            ms = dev.memory_stats()
            if ms and "bytes_in_use" in ms:
                total += float(ms["bytes_in_use"])
                seen = True
        if not seen:
            _mem_broken = True
            return
        if total > _hbm_peak:
            _hbm_peak = total
            telemetry.set_gauge("perf.hbm_peak_bytes", total)
    except Exception:
        _mem_broken = True


def capture_cost(key: Any, jitted: Any, *args: Any,
                 kind: str = "serial", k: int = 1,
                 compiled: Any = None) -> Optional[Dict[str, Any]]:
    """AOT-compile ``jitted(*args)`` and record its XLA cost analysis
    under ``key`` (``compiled``: the executable, where the caller has
    built it ahead of time itself). Idempotent per key; never raises (a
    backend that can't lower/compile the AOT path just leaves the cost
    model empty). Returns the captured cost dict, or None."""
    if not cost_capture_enabled():
        return None
    with _lock:
        stats = _get(key, kind, k)
        if stats.cost is not None:
            return stats.cost
        stats.cost = {}  # claim under the lock; compile outside it
    cost: Dict[str, Any] = {}
    try:
        import time as _time

        t0 = _time.monotonic()
        if compiled is None:
            compiled = jitted.lower(*args).compile()
        cost["cost_capture_s"] = _time.monotonic() - t0
        ca = compiled.cost_analysis() or {}
        cost["flops"] = float(ca.get("flops", 0.0)) or None
        cost["bytes_accessed"] = float(ca.get("bytes accessed", 0.0)) or None
        try:
            ma = compiled.memory_analysis()
            peak = (getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0))
            cost["peak_hbm_bytes"] = float(peak) or None
        except Exception:
            cost["peak_hbm_bytes"] = None
    except Exception:
        cost = {}
    with _lock:
        stats = _get(key, kind, k)
        stats.cost = cost or None
    if cost.get("flops"):
        telemetry.inc("perf.cost_captures")
        journal.record("perf", "cost", key=_key_str(key),
                       key_hash=key_hash(key), program_kind=kind, k=int(k),
                       flops=cost.get("flops"),
                       bytes_accessed=cost.get("bytes_accessed"),
                       peak_hbm_bytes=cost.get("peak_hbm_bytes"),
                       cost_capture_s=cost.get("cost_capture_s"),
                       device_kind=_device_kind())
    return cost or None


def note_epoch(key: Any, dt: float, feed_s: float = 0.0, cold: bool = False,
               kind: str = "serial", k: int = 1,
               packing_key: Optional[str] = None,
               group_width: Optional[int] = None) -> Optional[Dict[str, float]]:
    """Record one epoch's wall split for ``key``; runs the anomaly
    detector on the compute portion and returns its report (already
    journaled / countered / ledgered) when it fires. ``packing_key``
    (the repr of the model's packing key, when the caller is a packed
    loop) is stamped onto the ``perf/step`` record so the train twin's
    step-time calibration buckets per (packing_key, k) without joining
    through LRU key strings (docs/twin.md). ``group_width`` (set by the
    sharded loop) likewise rides the record so calibration can keep
    group-sharded samples out of the single-chip step-time pools — a
    width-w epoch's wall includes per-step all-gathers and is not a
    single-chip observation."""
    compute_s = max(dt - feed_s, 0.0)
    with _lock:
        stats = _get(key, kind, k)
        if cold:
            stats.cold_count += 1
            stats.cold_sum += compute_s
            report = stats.cold_detector.observe(compute_s)
        else:
            stats.warm_count += 1
            stats.warm_sum += compute_s
            stats.warm.append(compute_s)
            report = stats.detector.observe(compute_s)
        stats.feed_sum += feed_s
        h = stats.hash
    _sample_device_mem()
    journal.record("perf", "step", key_hash=h, dt=dt, feed_s=feed_s,
                   cold=bool(cold), program_kind=kind, k=int(k),
                   packing_key=packing_key,
                   group_width=int(group_width) if group_width else None)
    if report is not None:
        telemetry.inc("perf.anomalies")
        # The wall this epoch spent over its expected mean bought no
        # extra training — book it as badput so degraded goodput and
        # the anomaly stream agree (docs/perf.md).
        ledger.add("badput_s", max(report["value"] - report["mean"], 0.0))
        journal.record("perf", "anomaly", key_hash=h, key=_key_str(key),
                       program_kind=kind,
                       phase="compile" if cold else "step", **report)
    return report


def snapshot() -> Dict[str, Any]:
    """The ``perf`` telemetry collector: per-program joined summaries
    keyed by key_hash, plus process-wide aggregates."""
    with _lock:
        programs = {s.hash: s.summary() for s in _programs.values()}
        out: Dict[str, Any] = {"n_programs": len(programs),
                               "programs": programs}
        if _hbm_peak:
            out["hbm_peak_bytes"] = _hbm_peak
    return out


def reset() -> None:
    """Drop all profiler state (tests)."""
    global _hbm_peak, _mem_broken
    with _lock:
        _programs.clear()
        _hbm_peak = 0.0
        _mem_broken = False
        _peak_cache.clear()


telemetry.register_collector("perf", snapshot)
