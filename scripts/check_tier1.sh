#!/usr/bin/env bash
# The tier-1 verify gate, verbatim from ROADMAP.md — builders and CI
# must all run the IDENTICAL command so "tests pass"
# means the same thing everywhere. Edit ROADMAP.md and this file
# together or not at all.
#
# Prints DOTS_PASSED=<n> (count of passing-test dots) after the pytest
# summary; exits with pytest's own return code.
set -o pipefail
cd "$(dirname "$0")/.."
# Lint gate first: a static-analysis regression fails the same gate as
# tests (docs/static_analysis.md). Cheap (~1s, no jax touch), so it
# runs before the 870s pytest budget is spent.
scripts/check_lint.sh > /tmp/_lint.json || { echo "TIER1 LINT FAILED (see /tmp/_lint.json)"; exit 1; }
# Serving smoke: a deterministic in-process closed-loop run against the
# gateway + predictor stack (docs/serving.md). Sub-second; fails the
# gate on any 5xx or zero completed requests.
env JAX_PLATFORMS=cpu python scripts/bench_serving.py --smoke > /tmp/_bench_serving.json \
  || { echo "TIER1 SERVING SMOKE FAILED (see /tmp/_bench_serving.json)"; exit 1; }
# Trial-packing smoke: one RAFIKI_TRIAL_PACK=4 worker round over the
# fixed-shape FF template (docs/trial_packing.md) — asserts per-trial
# store rows, logs, feedback and the trial_pack.* telemetry. ~3s.
env JAX_PLATFORMS=cpu RAFIKI_TRIAL_PACK=4 python scripts/smoke_trial_pack.py > /tmp/_smoke_trial_pack.json \
  || { echo "TIER1 TRIAL PACK SMOKE FAILED (see /tmp/_smoke_trial_pack.json)"; exit 1; }
# Chaos smoke: three deterministic fault-injection recovery scenarios
# (docs/chaos.md) — kill-mid-trial resume, straggler quorum, drain
# under load. ~10s; fails the gate on any violated recovery invariant.
env JAX_PLATFORMS=cpu python scripts/chaos_smoke.py > /tmp/_chaos_smoke.json \
  || { echo "TIER1 CHAOS SMOKE FAILED (see /tmp/_chaos_smoke.json)"; exit 1; }
# Observability smoke: one gateway query traced end to end — the
# `obs trace` CLI must stitch >= 3 processes from the journals, and
# /metrics?format=prom must line-parse (docs/observability.md). ~6s.
env JAX_PLATFORMS=cpu python scripts/obs_smoke.py > /tmp/_obs_smoke.json \
  || { echo "TIER1 OBS SMOKE FAILED (see /tmp/_obs_smoke.json)"; exit 1; }
# Perf-sentinel smoke: bench_report must gate both ways (an errored
# round is no-data, a 3x drop regresses), an uninjected packed round
# must profile clean (obs profile joins the packed program's cost
# against a stated peak, zero anomalies/breaches),
# and an injected 0.25s epoch delay must land anomaly -> SLO breach
# -> flight record (docs/perf.md). ~7s.
env JAX_PLATFORMS=cpu python scripts/perf_smoke.py > /tmp/_perf_smoke.json \
  || { echo "TIER1 PERF SMOKE FAILED (see /tmp/_perf_smoke.json)"; exit 1; }
# Mesh-sweep smoke: a 2-virtual-chip elastic sweep with one injected
# chip loss (docs/mesh_sweep.md) — re-packs onto the survivor, every
# trial scores, resumed params bit-match serial. ~10s; a vacuous pass
# (no fault fired) also fails the gate.
env JAX_PLATFORMS=cpu python scripts/mesh_smoke.py > /tmp/_mesh_smoke.json \
  || { echo "TIER1 MESH SMOKE FAILED (see /tmp/_mesh_smoke.json)"; exit 1; }
# Numerics-health smoke: a quiet 2-trial round must trip nothing,
# then an injected train.nan must land a contained ERRORED trial, a
# health/divergence verdict, a replay capsule — and the real
# `obs replay` CLI must reproduce the divergent step bit-exactly in a
# fresh process (docs/health.md). ~13s.
env JAX_PLATFORMS=cpu python scripts/health_smoke.py > /tmp/_health_smoke.json \
  || { echo "TIER1 HEALTH SMOKE FAILED (see /tmp/_health_smoke.json)"; exit 1; }
# Request-anatomy smoke: a clean mp run must reconstruct a pinned
# >=4-hop waterfall across >=3 pids with hop sums reconciling, and an
# injected inference.forward delay must be attributed to the forward
# hop by `obs tails` AND breach its latency-budget SLO
# (docs/serving_anatomy.md).
env JAX_PLATFORMS=cpu python scripts/serving_obs_smoke.py > /tmp/_serving_obs_smoke.json \
  || { echo "TIER1 SERVING OBS SMOKE FAILED (see /tmp/_serving_obs_smoke.json)"; exit 1; }
# Digital-twin smoke: calibrate from a fresh captured run, validate
# predicted-vs-measured latency BOTH ways (correct calibration passes,
# a halved forward time fails), sweep deterministically from one seed,
# and gate the TWIN_r* error trend both ways (docs/twin.md). ~15s.
env JAX_PLATFORMS=cpu python scripts/twin_smoke.py > /tmp/_twin_smoke.json \
  || { echo "TIER1 TWIN SMOKE FAILED (see /tmp/_twin_smoke.json)"; exit 1; }
# Search-anatomy smoke: a seeded 12-trial GP sweep must reconstruct
# end to end from its journals alone (`obs sweep` — every proposal
# audited, regret non-increasing, lift CI present), a doctored journal
# missing one advisor/propose must fail reconciliation loudly, and
# bench_report --sweep must gate the SWEEP_r* trend both ways
# (docs/search_anatomy.md). ~10s.
env JAX_PLATFORMS=cpu python scripts/sweep_smoke.py > /tmp/_sweep_smoke.json \
  || { echo "TIER1 SWEEP SMOKE FAILED (see /tmp/_sweep_smoke.json)"; exit 1; }
# Elasticity smoke: the load-spike-scale-up chaos scenario must close
# the loop (breach -> scale-up -> recovery, time recorded for the
# SCALE_r* trend), a doctored undamped controller must be CAUGHT
# flapping by `obs autoscale --check`, and bench_report --scale/--store
# must gate both ways (docs/autoscale.md). ~5s.
env JAX_PLATFORMS=cpu python scripts/autoscale_smoke.py > /tmp/_autoscale_smoke.json \
  || { echo "TIER1 AUTOSCALE SMOKE FAILED (see /tmp/_autoscale_smoke.json)"; exit 1; }
# Crash-recovery smoke: a SIGKILLed sweep supervisor must be adopted
# by a fresh process (WAL reconciled with zero duplicate claims, job
# driven to COMPLETED, timeline reconstructible via `obs resume`), a
# doctored WAL must refuse resume loudly, and bench_report --resume
# must gate the RESUME_r* trend both ways (docs/recovery.md). ~15s.
env JAX_PLATFORMS=cpu python scripts/resume_smoke.py > /tmp/_resume_smoke.json \
  || { echo "TIER1 RESUME SMOKE FAILED (see /tmp/_resume_smoke.json)"; exit 1; }
# Train-twin smoke: capture a real seeded mini mesh sweep, calibrate
# the train bundle BOTH ways (real capture passes, an empty dir fails
# naming perf/step + mesh/pack_formed), validate predicted-vs-measured
# trials/hour BOTH ways (correct calibration passes, a doctored epoch
# scale fails), sweep a chips x pack grid byte-identically from one
# seed, and gate the TRAINTWIN_r* error trend both ways
# (docs/twin.md). ~30s.
env JAX_PLATFORMS=cpu python scripts/train_twin_smoke.py > /tmp/_train_twin_smoke.json \
  || { echo "TIER1 TRAIN TWIN SMOKE FAILED (see /tmp/_train_twin_smoke.json)"; exit 1; }
# Tenancy smoke: one worker must serve two distinct models through a
# journaled LRU residency swap under an HBM budget, the
# noisy-neighbor-shed scenario must PASS weighted (victim p99 inside
# its gold budget, aggressor sheds tenant_quota), and the doctored
# RAFIKI_TENANT_UNWEIGHTED=1 polarity must FAIL the victim-p99 gate
# specifically (docs/multitenancy.md). ~20s.
env JAX_PLATFORMS=cpu python scripts/tenancy_smoke.py > /tmp/_tenancy_smoke.json \
  || { echo "TIER1 TENANCY SMOKE FAILED (see /tmp/_tenancy_smoke.json)"; exit 1; }
# Sharded-lane smoke: the chip-loss-mid-sharded-trial scenario must
# PASS with the preempt fault actually fired (width-2 group loses a
# member, resumes at width 1 via reshard-on-restore, final params
# bit-match an unfaulted serial run), AND the doctored wrong-width
# chunk polarity must be REFUSED naming the chunk — a restore that
# silently accepts mismatched slices is the failure the lane exists
# to prevent (docs/sharding.md). ~35s.
env JAX_PLATFORMS=cpu python scripts/shard_smoke.py > /tmp/_shard_smoke.json \
  || { echo "TIER1 SHARD SMOKE FAILED (see /tmp/_shard_smoke.json)"; exit 1; }
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
