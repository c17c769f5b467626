"""docs/knobs.md generator — the env-knob registry rendered as docs.

The table is *derived*, not hand-maintained: ``python -m
rafiki_tpu.analysis --contracts --docs`` regenerates it from the same
extraction the manifest uses, so knob name / default / parse-type
drift between code and docs is structurally impossible — the only
hand-written content is the one-line description per knob in
:data:`KNOB_DOCS`. A knob read in code but missing from that dict
renders as *undocumented* (and scripts/check_lint.sh fails on the
marker), which is the "undocumented knob" cross-check: adding an env
read forces adding its one-liner here in the same change.
"""

from __future__ import annotations

from typing import List

from rafiki_tpu.analysis.contracts.envknobs import EnvContracts

UNDOCUMENTED = "**undocumented** (add a one-liner to " \
    "rafiki_tpu/analysis/contracts/knobdocs.py)"

#: Hand-written one-liners; everything else in the table is extracted.
KNOB_DOCS = {
    "RAFIKI_AUTOSCALE": "autoscale controller spec; empty disables the "
        "elasticity loop (docs/autoscale.md)",
    "RAFIKI_AUTOSCALE_DAMPING": "flap damping; off exists ONLY so "
        "tests can demonstrate the flapping it prevents",
    "RAFIKI_AUTOSCALE_DOWN_COOLDOWN_S": "cooldown after a scale-down "
        "actuation",
    "RAFIKI_AUTOSCALE_DOWN_THRESHOLD": "hysteresis band lower edge "
        "(pressure below it scales down)",
    "RAFIKI_AUTOSCALE_FLAP_BACKOFF": "direction-flip guard growth per "
        "excess flip",
    "RAFIKI_AUTOSCALE_FLAP_FLIPS": "direction flips inside the window "
        "before backoff engages",
    "RAFIKI_AUTOSCALE_FLAP_GUARD_CAP_S": "cap of the direction-flip "
        "guard",
    "RAFIKI_AUTOSCALE_FLAP_GUARD_S": "base of the direction-flip guard",
    "RAFIKI_AUTOSCALE_FLAP_WINDOW_S": "window for counting direction "
        "flips",
    "RAFIKI_AUTOSCALE_MAX": "lane size upper bound",
    "RAFIKI_AUTOSCALE_MIN": "lane size lower bound",
    "RAFIKI_AUTOSCALE_PREWARM": "pre-warm compiled packs at job "
        "admission (docs/autoscale.md)",
    "RAFIKI_AUTOSCALE_SEED": "controller seed; decisions are "
        "deterministic given clock+seed+sensors",
    "RAFIKI_AUTOSCALE_STEP": "replicas per actuation",
    "RAFIKI_AUTOSCALE_TARGET_EPH": "sweep-lane target effective-trials"
        "/hour; 0 (the default) holds the sweep lane",
    "RAFIKI_AUTOSCALE_TICK_S": "controller reconcile interval",
    "RAFIKI_AUTOSCALE_UP_COOLDOWN_S": "cooldown after a scale-up "
        "actuation",
    "RAFIKI_AUTOSCALE_UP_THRESHOLD": "hysteresis band upper edge "
        "(pressure above it scales up)",
    "RAFIKI_BACKEND_INIT_TIMEOUT_S": "worker gives up on jax backend "
        "init after this many seconds",
    "RAFIKI_BUS_REAP_FACTOR": "multiplier on queue TTL before an "
        "abandoned entry is reaped",
    "RAFIKI_CAS_CHUNK_KB": "content-addressed params store chunk size",
    "RAFIKI_CHAOS": "fault-injection spec for the chaos plane; unset "
        "means every hook is inert (docs/chaos.md)",
    "RAFIKI_CHECKPOINT_EVERY": "checkpoint cadence in epochs; 0 "
        "disables mid-trial checkpoints",
    "RAFIKI_COLLECTIVE_INIT_BACKOFF_S": "sleep between multi-process "
        "collective init retries",
    "RAFIKI_COLLECTIVE_INIT_RETRIES": "multi-process collective init "
        "attempts before the worker dies",
    "RAFIKI_COORDINATOR_ADDRESS": "jax distributed coordinator "
        "host:port (leader sets it for followers)",
    "RAFIKI_CURVE_KILL": "learning-curve early-kill switch "
        "(docs/early_kill.md); off by default — today's loops run "
        "bit-exactly",
    "RAFIKI_CURVE_KILL_MARGIN": "kill rule slack: a trial dies only "
        "when its credible band's upper edge sits below best-so-far "
        "minus this margin",
    "RAFIKI_CURVE_KILL_MIN_OBS": "curve points required before the "
        "extrapolator may condemn a trial",
    "RAFIKI_CURVE_KILL_WARMUP": "epochs every trial is immune from "
        "the early-kill rule",
    "RAFIKI_CURVE_SPECULATE": "speculative scoring switch: feed the "
        "advisor predicted scores for in-flight stragglers so "
        "propose_batch never blocks (docs/early_kill.md)",
    "RAFIKI_DEVICE_DATASET_MAX_MB": "cap on device-resident dataset "
        "size before falling back to host streaming",
    "RAFIKI_EVENTS_DIR": "control-plane event bus directory "
        "(docs/recovery.md)",
    "RAFIKI_EXEMPLAR_N": "serving exemplar reservoir size per window",
    "RAFIKI_EXEMPLAR_WINDOW_S": "serving exemplar sampling window",
    "RAFIKI_FOLLOWER_EXIT_GRACE_S": "follower wait for the leader's "
        "exit signal before exiting itself",
    "RAFIKI_HEALTH": "0/off disables numerics-divergence detection and "
        "capsules (docs/health.md)",
    "RAFIKI_HEALTH_CAPSULE": "0/off skips divergence snapshots and "
        "capsule writes",
    "RAFIKI_HEALTH_HYSTERESIS": "consecutive exploding epochs before "
        "the detector trips",
    "RAFIKI_HEALTH_K": "explosion multiplier over the running grad-norm "
        "median",
    "RAFIKI_HEALTH_WARMUP": "clean epochs before the explosion detector "
        "arms",
    "RAFIKI_JOURNAL_MAX": "per-process in-memory journal ring size",
    "RAFIKI_LEADER_SERVICE_ID": "leader's serving registration id, "
        "exported to followers for stacked serving",
    "RAFIKI_LEADER_WORKER_ID": "leader's worker id, exported to "
        "followers of a multi-process mesh",
    "RAFIKI_LOG_DIR": "journal directory; unset disables durable "
        "journaling (docs/observability.md)",
    "RAFIKI_MESH_CHIPS_PER_HOST": "override detected chips per host "
        "when planning mesh packing",
    "RAFIKI_MESH_FORM_GRACE_S": "mesh formation deadline before the "
        "supervisor declares the pack failed",
    "RAFIKI_MESH_INIT_BACKOFF_S": "sleep between mesh init retries",
    "RAFIKI_MESH_INIT_RETRIES": "mesh init attempts before giving up "
        "on a pack",
    "RAFIKI_NUM_PROCESSES": "process count of a multi-process mesh "
        "(spawner sets it; workers require it)",
    "RAFIKI_PARAMS_CAS": "enable the content-addressed params store "
        "backend",
    "RAFIKI_PERF_COST_CAPTURE": "capture per-program XLA cost models "
        "for the MFU join; on by default",
    "RAFIKI_PERF_K": "timing-anomaly threshold in MADs from the EWMA "
        "baseline",
    "RAFIKI_PERF_WARMUP": "timing samples before the anomaly detector "
        "arms",
    "RAFIKI_PROCESS_ID": "this process's rank within the mesh "
        "(spawner-assigned, required in workers)",
    "RAFIKI_PROFILE_DIR": "write jax profiler traces for each trial "
        "here; unset disables profiling",
    "RAFIKI_RESUME_POLL_S": "resume-reaper poll cadence "
        "(docs/recovery.md)",
    "RAFIKI_RESUME_STALE_S": "supervisor heartbeat age before a job is "
        "adoptable by resume (docs/recovery.md)",
    "RAFIKI_SHARD_HBM_CEILING": "per-chip HBM fraction the shard "
        "planner fits a group member under (docs/sharding.md)",
    "RAFIKI_SHARD_MAX_WIDTH": "cap on the solved group width even "
        "when the HBM estimate wants more chips",
    "RAFIKI_SHARD_WIDTH": "pin the group width (tests); 0 "
        "solves it from the HBM estimate",
    "RAFIKI_SLO": "SLO spec overrides as JSON; empty keeps the "
        "defaults (docs/slo.md)",
    "RAFIKI_SLO_TICK_S": "SLO burn-rate evaluation cadence",
    "RAFIKI_STACKED_SERVING": "serve from training hosts (stacked) "
        "instead of a dedicated pool; on by default",
    "RAFIKI_SUPERVISOR_HEARTBEAT_S": "supervisor liveness heartbeat "
        "cadence in the MetaStore",
    "RAFIKI_TENANT_BATCH_WEIGHT": "batch-tier admission weight "
        "(docs/multitenancy.md)",
    "RAFIKI_TENANT_DEFAULT_TIER": "QoS tier for tenants absent from "
        "RAFIKI_TENANT_TIERS (docs/multitenancy.md)",
    "RAFIKI_TENANT_GOLD_WEIGHT": "gold-tier admission weight "
        "(docs/multitenancy.md)",
    "RAFIKI_TENANT_HBM_BUDGET_MB": "co-host HBM residency budget per "
        "worker; 0 disables the cap (docs/multitenancy.md)",
    "RAFIKI_TENANT_MAX_TENANTS": "bound on tracked per-tenant "
        "admission/accounting state before LRU eviction",
    "RAFIKI_TENANT_QUOTA_FRAC": "per-tenant cap as a fraction of "
        "gateway inflight/queue capacity",
    "RAFIKI_TENANT_STD_WEIGHT": "std-tier admission weight "
        "(docs/multitenancy.md)",
    "RAFIKI_TENANT_TIERS": "tenant→tier map, e.g. "
        "\"alice=gold,bob=batch\" (docs/multitenancy.md)",
    "RAFIKI_TENANT_UNWEIGHTED": "polarity knob: disable weighted "
        "admission and quotas (the tenancy tests' doctored run)",
    "RAFIKI_TPU_DATA_DIR": "root for all durable state (stores, "
        "journals, caches)",
    "RAFIKI_TRACE_ID": "trace id stamped on every journal record of "
        "this process (spawner-propagated)",
    "RAFIKI_TRIAL_PACK": "trial-packing width k; 1 = off "
        "(docs/trial_packing.md)",
    "RAFIKI_TWIN_PLACEMENT": "consult the training twin for placement "
        "advisories at pack formation (docs/twin.md)",
    "RAFIKI_WAL_DIR": "sweep write-ahead-log directory; empty keeps "
        "the WAL beside the MetaStore (docs/recovery.md)",
    "RAFIKI_WORKER_ADOPT_SERVICE_ID": "serving registration the "
        "restarted worker should adopt instead of re-registering",
    "RAFIKI_WORKER_ADVISOR_ID": "advisor identity for this worker's "
        "trial proposals",
    "RAFIKI_WORKER_ADVISOR_SECRET": "shared secret for advisor calls",
    "RAFIKI_WORKER_ADVISOR_URL": "advisor service endpoint the worker "
        "proposes/reports against",
    "RAFIKI_WORKER_DB": "MetaStore path handed to a spawned worker",
    "RAFIKI_WORKER_ID": "worker identity; empty derives one from "
        "pid/host",
    "RAFIKI_WORKER_MAX_RESTARTS": "per-worker restart budget before "
        "the scheduler gives up on it",
    "RAFIKI_WORKER_PARAMS_DIR": "ParamsStore path handed to a spawned "
        "worker",
    "RAFIKI_WORKER_RESTART_BACKOFF_S": "sleep before restarting a "
        "crashed worker",
    "RAFIKI_WORKER_SERVICE_ID": "serving registration id assigned to "
        "the spawned worker",
    "RAFIKI_WORKER_SUB_JOB_ID": "sub-train-job the spawned worker "
        "executes",
    "RAFIKI_XLA_CACHE_MIN_S": "minimum compile time before a program "
        "is worth caching",
}

_HEADER = """\
# Environment knobs

<!-- GENERATED FILE — do not edit the table by hand.
     Regenerate with:  python -m rafiki_tpu.analysis --contracts --docs
     Descriptions live in rafiki_tpu/analysis/contracts/knobdocs.py;
     names, defaults, parse types, and read sites are extracted from
     the code (docs/static_analysis.md, "Contracts"). -->

Every `RAFIKI_*` environment variable the code reads, extracted by the
contracts pass. `<required>` means the read raises when the variable
is unset (spawner-provided); `<dynamic>` means the fallback is
computed at runtime; `<none>` means the reader handles absence itself.

| knob | type | default(s) | read at | what it does |
|---|---|---|---|---|
"""


def generate_knobs_md(env: EnvContracts) -> str:
    rows: List[str] = []
    for knob, reads in sorted(env.by_knob().items()):
        parse = "/".join(sorted({r.parse for r in reads}))
        defaults = ", ".join(
            sorted({str(r.manifest_default()) for r in reads}))
        sites = "<br>".join(
            f"`{s}`" for s in sorted({f"{r.path}:{r.line}"
                                      for r in reads}))
        desc = KNOB_DOCS.get(knob, UNDOCUMENTED)
        rows.append(f"| `{knob}` | {parse} | `{defaults}` | {sites} "
                    f"| {desc} |")
    return _HEADER + "\n".join(rows) + "\n"
