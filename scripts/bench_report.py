#!/usr/bin/env python
"""Bench regression gate: trend the BENCH_r*.json history, verdict it.

Each bench round leaves an artifact — either the driver wrapper
``{"n": ..., "cmd": ..., "rc": ..., "tail": [...], "parsed": {...}}``
or a raw ``bench.py`` result line. This report joins them into one
trajectory per headline metric and renders a verdict:

  regressed     latest measurable value is worse than the best prior
                measurable value by more than ``--tolerance``
  improved      better than the best prior value by more than tolerance
  flat          within tolerance of the best prior value
  single-point  only one round ever measured this metric (no trend)
  no-data       no round measured it at all

"Measurable" is deliberately strict: a round whose payload carries an
``error`` (no chip found, watchdog fired) or a null/zero value is
**no data**, not a zero — a backend-unavailable artifact must not read
as a 100% throughput regression against an earlier round's real number.

Schema tolerance runs both directions: schema>=2 artifacts carry a
``headline`` block (bench.py stamps it); older rounds are backfilled
from ``value`` + ``detail`` with the same key fallbacks bench.py uses.

Output: one JSON document on stdout (schema_versioned, machine-first —
scripts/perf_smoke.py subprocesses this as a CI gate); the exit code is
the verdict: 0 clean, 1 any metric regressed, 2 unreadable history.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPORT_SCHEMA_VERSION = 1
DEFAULT_TOLERANCE = 0.10

#: Headline metrics and which direction is good. Keys match the
#: bench.py ``headline`` block.
METRICS = {
    "trials_per_hour": "higher",
    "train_img_per_s": "higher",
    "canonical_trial_s": "lower",
    "compile_s": "lower",
}

#: Serving-round metrics (``--serving``): bench_serving.py v2 artifact
#: keys with their polarities, so SERVING_r*.json rounds gate the
#: trajectory exactly like training rounds do.
SERVING_METRICS = {
    "qps": "higher",
    "p50_ms": "lower",
    "p99_ms": "lower",
    "shed_rate": "lower",
    "ensemble_fanout_cost_ms": "lower",
}

#: Twin-validation rounds (``--twin``): TWIN_r*.json artifacts from
#: ``python -m rafiki_tpu.obs twin validate --out`` (docs/twin.md).
#: Both errors are relative |predicted-measured|/measured — lower is a
#: better-calibrated twin; a creeping error trend means the simulator
#: has drifted from the serving code it predicts.
TWIN_METRICS = {
    "p50_err": "lower",
    "p99_err": "lower",
}

#: Train-twin-validation rounds (``--train-twin``): TRAINTWIN_r*.json
#: artifacts from ``python -m rafiki_tpu.obs twin train validate --out``
#: (docs/twin.md). Relative |predicted-measured|/measured on the sweep's
#: trials/hour and wall clock — a creeping error trend means the sweep
#: simulator has drifted from the scheduler it predicts.
TRAIN_TWIN_METRICS = {
    "tph_err": "lower",
    "wall_err": "lower",
}

#: Sweep-anatomy rounds (``--sweep``): SWEEP_r*.json artifacts from
#: ``python -m rafiki_tpu.obs sweep --out`` (docs/search_anatomy.md).
#: Reconciliation-failed rounds stamp ``error`` and read as no-data —
#: a sweep whose audit trail leaked is not a zero-regret sweep.
SWEEP_METRICS = {
    "effective_trials_per_hour": "higher",
    "best_score": "higher",
    "regret": "lower",
    "advisor_lift": "higher",
}

#: Elasticity rounds (``--scale``): SCALE_r*.json artifacts from
#: scripts/autoscale_smoke.py (docs/autoscale.md). Recovery-time-to-SLO
#: is the loop's headline — how long a load spike burns before the
#: scale-up lands and the breach clears; actuations is the flap bill
#: the damping machinery keeps bounded.
SCALE_METRICS = {
    "recovery_s": "lower",
    "actuations": "lower",
}

#: Params-store rounds (``--store``): STORE_r*.json artifacts from
#: scripts/measure_store_throughput.py. ``second_write_frac`` is the
#: CAS dedup acceptance number — the byte fraction a near-identical
#: second checkpoint actually writes (ISSUE 14 gate: < 0.20).
STORE_METRICS = {
    "write_txn_per_s": "higher",
    "dedup_ratio": "higher",
    "second_write_frac": "lower",
    "cas_dump_s": "lower",
}

#: Crash-recovery rounds (``--resume``): RESUME_r*.json artifacts from
#: scripts/resume_smoke.py (docs/recovery.md). Recovery wall-clock is
#: the headline — how long a SIGKILLed sweep takes to be adopted and
#: driven to completion by a fresh process; duplicate_claims is the
#: WAL-reconcile acceptance number and must stay at zero.
RESUME_METRICS = {
    "recovery_wall_s": "lower",
    "trials_salvaged": "higher",
    "trials_restarted": "lower",
    "duplicate_claims": "lower",
}

#: Sharded-lane rounds (``--shard``): SHARD_r*.json artifacts from
#: scripts/shard_smoke.py (docs/sharding.md). restore_s is the
#: reshard-on-restore wall — how long resuming a group trial at a new
#: width takes; group_trials_per_hour is the lane's throughput
#: headline. Error rounds (a group that never completed) stamp
#: ``error`` and yield no data — a dead lane is not a fast one.
SHARD_METRICS = {
    "restore_s": "lower",
    "group_trials_per_hour": "higher",
}

#: Multi-tenant serving rounds (``--tenants``): TENANT_r*.json
#: artifacts from ``bench_serving.py --tenants`` (docs/multitenancy.md).
#: The gold tenant's tail and shed rate are the isolation headline —
#: the protected tenant must not regress when the batch aggressor's
#: skewed load grows — while batch_qps guards the other direction:
#: proportional share means the aggressor still progresses, so a
#: "fix" that simply starves batch also fails the gate.
TENANT_METRICS = {
    "gold_p99_ms": "lower",
    "gold_shed_rate": "lower",
    "batch_qps": "higher",
    "qps": "higher",
}

#: Metrics where 0 is a legitimate measurement, not "did not run" —
#: a clean serving round genuinely sheds nothing, a 1-worker round
#: has zero fan-out cost, a perfectly calibrated twin has zero
#: prediction error, and a sweep that found the optimum early has
#: zero regret. (Throughput-style metrics keep the strict v > 0
#: rule: their zeros mean a dead backend.)
ZERO_OK = {"shed_rate", "ensemble_fanout_cost_ms", "p50_err", "p99_err",
           "tph_err", "wall_err",
           "regret", "advisor_lift", "dedup_ratio",
           "trials_salvaged", "trials_restarted", "duplicate_claims",
           "gold_shed_rate"}

#: Metrics that are legitimately signed: a GP that *hurt* the sweep
#: has negative lift, and that is a measurement the trend must carry,
#: not a dead-backend null.
NEG_OK = {"advisor_lift"}


def _payload_from_tail(tail: Any) -> Optional[Dict[str, Any]]:
    """Backfill path: no ``parsed`` block, so scan the captured stdout
    tail from the end for the single bench result line. Tail chunks are
    arbitrary splits, so join first and walk whole lines."""
    if not tail:
        return None
    text = "".join(str(t) for t in tail)
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and ("value" in obj or "metric" in obj):
            return obj
    return None


def load_round(path: str) -> Dict[str, Any]:
    """One artifact file -> {round, path, rc, payload}. Never raises on
    a malformed file: it becomes a payload-less round (= no data)."""
    name = os.path.basename(path)
    out: Dict[str, Any] = {"path": name, "round": name, "rc": None,
                           "payload": None, "source": None}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    if not isinstance(doc, dict):
        out["error"] = "artifact is not a JSON object"
        return out
    if ("metric" in doc or "headline" in doc or "qps" in doc
            or "schema_version" in doc or "twin_schema_version" in doc
            or "train_twin_schema_version" in doc
            or "sweep_schema_version" in doc
            or "scale_schema_version" in doc
            or "store_schema_version" in doc
            or "resume_schema_version" in doc
            or "shard_schema_version" in doc):
        # A raw bench.py / bench_serving.py result saved directly, no
        # driver wrapper.
        out["payload"], out["source"] = doc, "raw"
        return out
    out["round"] = doc.get("n", name)
    out["rc"] = doc.get("rc")
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        out["payload"], out["source"] = parsed, "parsed"
    else:
        out["payload"] = _payload_from_tail(doc.get("tail"))
        out["source"] = "tail" if out["payload"] else None
    return out


def headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The metric block to trend. An ``error``-bearing payload yields
    nothing: its zeros mean "did not run", not "ran this slow"."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    h = payload.get("headline")
    if isinstance(h, dict):
        return h
    d = payload.get("detail") or {}
    return {  # pre-schema_version backfill — mirrors bench.py._emit
        "trials_per_hour": payload.get("value"),
        "canonical_trial_s": d.get("canonical_trial_s",
                                   d.get("canonical_compute_s")),
        "compile_s": d.get("compile_s", d.get("cold_trial_s")),
        "train_img_per_s": d.get("train_img_per_s"),
    }


def serving_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The serving metric block: bench_serving.py v2 artifacts carry
    the headline keys at top level."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in SERVING_METRICS
            if payload.get(k) is not None}


def twin_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The twin-error block: validate artifacts carry p50_err/p99_err
    at top level. Error rounds (journals missing, too few requests)
    yield nothing — a round that never validated is no-data, not a
    perfect score."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in TWIN_METRICS
            if payload.get(k) is not None}


def train_twin_headline_of(payload: Optional[Dict[str, Any]]
                           ) -> Dict[str, Any]:
    """The train-twin-error block: ``twin train validate`` artifacts
    carry tph_err/wall_err at top level. Error rounds (journals
    missing, too few trials captured) yield nothing — a round that
    never validated is no-data, not a perfect score."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in TRAIN_TWIN_METRICS
            if payload.get(k) is not None}


def sweep_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The sweep-anatomy block: ``obs sweep --out`` artifacts carry the
    headline keys at top level. A reconciliation-failed artifact stamps
    ``error`` and yields nothing — no-data, not a perfect sweep."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in SWEEP_METRICS
            if payload.get(k) is not None}


def scale_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The elasticity block: autoscale_smoke artifacts carry the
    headline keys at top level. A round whose scenario failed stamps
    ``error`` and yields nothing — a loop that never closed is
    no-data, not an instant recovery."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in SCALE_METRICS
            if payload.get(k) is not None}


def store_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The params-store block: measure_store_throughput artifacts
    carry the headline keys at top level."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in STORE_METRICS
            if payload.get(k) is not None}


def resume_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The crash-recovery block: resume_smoke artifacts carry the
    headline keys at top level. A round whose resume never completed
    stamps ``error`` and yields nothing — a job still down is no-data,
    not an instant recovery."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in RESUME_METRICS
            if payload.get(k) is not None}


def shard_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The sharded-lane block: shard_smoke artifacts carry restore_s
    and group_trials_per_hour at top level. Error rounds yield nothing
    — a group that never resumed is no-data, not an instant restore."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in SHARD_METRICS
            if payload.get(k) is not None}


def tenant_headline_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The multi-tenant block: ``bench_serving.py --tenants`` artifacts
    carry the flat gold_*/batch_* headline keys at top level. Error
    rounds yield nothing — a run that never isolated anyone is no-data,
    not a zero-shed round."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    return {k: payload.get(k) for k in TENANT_METRICS
            if payload.get(k) is not None}


def health_of(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The ``detail.health`` numerics block (docs/health.md), when the
    artifact carries one. Trended as ADVISORY context — a round with
    divergences explains a throughput dip, it is not itself a
    regression verdict (the badput is already in the goodput split)."""
    if not isinstance(payload, dict) or payload.get("error"):
        return {}
    h = (payload.get("detail") or {}).get("health")
    return h if isinstance(h, dict) else {}


def _measurable(v: Any, zero_ok: bool = False,
                neg_ok: bool = False) -> bool:
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return False
    return v > 0 or (zero_ok and v == 0) or (neg_ok and v < 0)


def trend(rounds: List[Dict[str, Any]], tolerance: float,
          metrics: Optional[Dict[str, str]] = None,
          headline_fn=headline_of) -> Dict[str, Dict[str, Any]]:
    """Per-metric trajectory + verdict. Latest measurable point vs the
    best prior measurable point, with a relative tolerance band."""
    out: Dict[str, Dict[str, Any]] = {}
    for metric, direction in (metrics or METRICS).items():
        zero_ok = metric in ZERO_OK
        neg_ok = metric in NEG_OK
        points = []
        for r in rounds:
            v = headline_fn(r["payload"]).get(metric)
            points.append({
                "round": r["round"],
                "value": v if _measurable(v, zero_ok, neg_ok) else None})
        measured = [p for p in points if p["value"] is not None]
        entry: Dict[str, Any] = {"direction": direction,
                                 "trajectory": points,
                                 "n_measured": len(measured)}
        if not measured:
            entry["verdict"] = "no-data"
        elif len(measured) == 1:
            entry["verdict"] = "single-point"
            entry["latest"] = measured[-1]["value"]
        else:
            latest = measured[-1]["value"]
            prior = [p["value"] for p in measured[:-1]]
            best = max(prior) if direction == "higher" else min(prior)
            # Signed fraction, positive = worse, in units of the best
            # prior value — one tolerance knob works for both signs.
            # ZERO_OK metrics can have best == 0 (a clean round shed
            # nothing) and NEG_OK ones a negative best (a GP that hurt):
            # fall back to an absolute delta so going from 0 to anything
            # still registers instead of dividing by 0 (or flipping sign).
            denom = best if best > 0 else 1.0
            delta = ((best - latest) if direction == "higher"
                     else (latest - best)) / denom
            entry.update({"latest": latest, "best_prior": best,
                          "delta_frac": round(delta, 4)})
            if delta > tolerance:
                entry["verdict"] = "regressed"
            elif delta < -tolerance:
                entry["verdict"] = "improved"
            else:
                entry["verdict"] = "flat"
        out[metric] = entry
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="scripts/bench_report.py",
        description="trend BENCH_r*.json artifacts, exit 1 on regression")
    p.add_argument("artifacts", nargs="*",
                   help="artifact files in round order "
                        "(default: BENCH_r*.json next to bench.py)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative regression band (default 0.10)")
    p.add_argument("--serving", action="store_true",
                   help="trend bench_serving.py rounds (SERVING_r*.json "
                        "default glob, qps/p50/p99/shed/fanout polarities)")
    p.add_argument("--twin", action="store_true",
                   help="trend twin-validation rounds (TWIN_r*.json "
                        "default glob, p50_err/p99_err lower-better)")
    p.add_argument("--train-twin", action="store_true",
                   help="trend train-twin-validation rounds "
                        "(TRAINTWIN_r*.json default glob, "
                        "tph_err/wall_err lower-better)")
    p.add_argument("--sweep", action="store_true",
                   help="trend sweep-anatomy rounds (SWEEP_r*.json "
                        "default glob, trials-per-hour/best-score higher, "
                        "regret lower, advisor_lift signed)")
    p.add_argument("--scale", action="store_true",
                   help="trend elasticity rounds (SCALE_r*.json default "
                        "glob, recovery_s/actuations lower-better)")
    p.add_argument("--store", action="store_true",
                   help="trend params-store rounds (STORE_r*.json default "
                        "glob, txn/s + dedup higher, write frac lower)")
    p.add_argument("--resume", action="store_true",
                   help="trend crash-recovery rounds (RESUME_r*.json "
                        "default glob, recovery_wall_s/restarts/duplicate "
                        "claims lower, salvaged trials higher)")
    p.add_argument("--shard", action="store_true",
                   help="trend sharded-lane rounds (SHARD_r*.json "
                        "default glob, reshard restore_s lower, group "
                        "trials-per-hour higher)")
    p.add_argument("--tenants", action="store_true",
                   help="trend multi-tenant serving rounds "
                        "(TENANT_r*.json default glob, gold tail/shed "
                        "lower-better, batch qps higher-better)")
    args = p.parse_args(argv)

    if sum((args.serving, args.twin, args.train_twin, args.sweep,
            args.scale, args.store, args.resume, args.tenants,
            args.shard)) > 1:
        print(json.dumps(
            {"error": "--serving, --twin, --train-twin, --sweep, --scale, "
                      "--store, --resume, --tenants and --shard are "
                      "exclusive"}))
        return 2
    if args.shard:
        metric_set, headline_fn = SHARD_METRICS, shard_headline_of
        pattern = "SHARD_r*.json"
    elif args.tenants:
        metric_set, headline_fn = TENANT_METRICS, tenant_headline_of
        pattern = "TENANT_r*.json"
    elif args.resume:
        metric_set, headline_fn = RESUME_METRICS, resume_headline_of
        pattern = "RESUME_r*.json"
    elif args.scale:
        metric_set, headline_fn = SCALE_METRICS, scale_headline_of
        pattern = "SCALE_r*.json"
    elif args.store:
        metric_set, headline_fn = STORE_METRICS, store_headline_of
        pattern = "STORE_r*.json"
    elif args.sweep:
        metric_set, headline_fn = SWEEP_METRICS, sweep_headline_of
        pattern = "SWEEP_r*.json"
    elif args.train_twin:
        metric_set, headline_fn = TRAIN_TWIN_METRICS, train_twin_headline_of
        pattern = "TRAINTWIN_r*.json"
    elif args.twin:
        metric_set, headline_fn = TWIN_METRICS, twin_headline_of
        pattern = "TWIN_r*.json"
    elif args.serving:
        metric_set, headline_fn = SERVING_METRICS, serving_headline_of
        pattern = "SERVING_r*.json"
    else:
        metric_set, headline_fn = METRICS, headline_of
        pattern = "BENCH_r*.json"

    paths = args.artifacts
    if not paths:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(root, pattern)))
    if not paths:
        print(json.dumps({"error": "no bench artifacts found"}))
        return 2

    rounds = [load_round(pth) for pth in paths]
    metrics = trend(rounds, args.tolerance,
                    metrics=metric_set, headline_fn=headline_fn)
    regressed = sorted(m for m, e in metrics.items()
                       if e["verdict"] == "regressed")
    health_points = [dict(round=r["round"], **health_of(r["payload"]))
                     for r in rounds if health_of(r["payload"])]
    report = {
        "schema_version": REPORT_SCHEMA_VERSION,
        "tolerance": args.tolerance,
        "n_rounds": len(rounds),
        "mode": ("tenants" if args.tenants
                 else "resume" if args.resume
                 else "scale" if args.scale
                 else "store" if args.store
                 else "sweep" if args.sweep
                 else "train-twin" if args.train_twin
                 else "twin" if args.twin
                 else "serving" if args.serving else "training"),
        "rounds": [{"round": r["round"], "rc": r["rc"],
                    "source": r["source"],
                    "has_data": bool(headline_fn(r["payload"]))}
                   for r in rounds],
        "metrics": metrics,
        "health": {
            "trajectory": health_points,
            "latest_divergences": (health_points[-1].get("divergences")
                                   if health_points else None),
        },
        "regressed": regressed,
        "verdict": "regressed" if regressed else "ok",
    }
    print(json.dumps(report))
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
