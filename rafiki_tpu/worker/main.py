"""Train-worker process entrypoint: ``python -m rafiki_tpu.worker.main``.

Reference parity: rafiki/worker/ entrypoints (unverified — SURVEY.md
§1 L5): the reference launches workers inside containers "driven by
env vars (service id, job id)". Same contract here — the
ProcessScheduler spawns this module with:

  RAFIKI_WORKER_DB            meta-store sqlite path
  RAFIKI_WORKER_PARAMS_DIR    params-store directory
  RAFIKI_WORKER_SUB_JOB_ID    sub-train-job to pull trials for
  RAFIKI_WORKER_ID            human-readable worker id
  RAFIKI_WORKER_SERVICE_ID    service row to heartbeat (optional)
  RAFIKI_WORKER_ADVISOR_URL   http://127.0.0.1:<port>
  RAFIKI_WORKER_ADVISOR_ID    advisor to ask for knobs
  RAFIKI_WORKER_ADVISOR_SECRET shared secret (optional)

Device pinning is inherited from the environment the scheduler set
(JAX_PLATFORMS / XLA_FLAGS / TPU_VISIBLE_CHIPS…): this process sees
only its own chips, giving each trial an isolated XLA runtime — the
TPU-native answer to the reference's one-GPU-per-container isolation.

Exit codes: 0 = budget exhausted cleanly, 1 = crash,
17 = backend-init watchdog timeout (TPU runtime unreachable).
"""

from __future__ import annotations

import os
import sys
import time


def initialize_collective(initialize, coordinator: str, num_processes: int,
                          process_id: int) -> None:
    """Join the distributed cluster with retry + exponential backoff.

    Collective initialization is the flakiest moment of a multihost
    job: a follower that races the coordinator's bind, or a transient
    DCN hiccup, fails ``jax.distributed.initialize`` even though the
    pod is healthy. Bounded retries (``RAFIKI_COLLECTIVE_INIT_RETRIES``,
    backoff ``RAFIKI_COLLECTIVE_INIT_BACKOFF_S`` doubling per attempt)
    absorb that; exhaustion re-raises the last error so the scheduler's
    restart-with-backoff path takes over. The ``collective.init`` chaos
    site is armed once per attempt (error mode = injected init
    failure), keyed ``p<process_id>`` (docs/chaos.md).
    """
    from rafiki_tpu import chaos
    from rafiki_tpu.utils.events import events

    retries = int(os.environ.get("RAFIKI_COLLECTIVE_INIT_RETRIES", "3"))
    backoff = float(os.environ.get("RAFIKI_COLLECTIVE_INIT_BACKOFF_S", "0.5"))
    for attempt in range(retries + 1):
        try:
            chaos.hook("collective.init", key=f"p{process_id}")
            initialize(coordinator_address=coordinator,
                       num_processes=num_processes,
                       process_id=process_id)
            return
        except Exception as e:
            if attempt >= retries:
                raise
            events.emit("collective_init_retry", process_id=process_id,
                        attempt=attempt, error=str(e))
            time.sleep(backoff * (2 ** attempt))


def main() -> int:
    db_path = os.environ["RAFIKI_WORKER_DB"]
    params_dir = os.environ["RAFIKI_WORKER_PARAMS_DIR"]
    sub_job_id = os.environ["RAFIKI_WORKER_SUB_JOB_ID"]
    worker_id = os.environ.get("RAFIKI_WORKER_ID", f"pw-{os.getpid()}")
    service_id = os.environ.get("RAFIKI_WORKER_SERVICE_ID")
    advisor_url = os.environ["RAFIKI_WORKER_ADVISOR_URL"]
    advisor_id = os.environ["RAFIKI_WORKER_ADVISOR_ID"]
    secret = os.environ.get("RAFIKI_WORKER_ADVISOR_SECRET")

    # An explicit CPU request is applied before the first backend use.
    import jax

    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()

    # Backend-init watchdog: jax blocks indefinitely when the TPU
    # runtime is unreachable; a silent hang would stall the scheduler's
    # supervise loop with no diagnosis. Exit with a structured error
    # instead (the scheduler records it on the service row).
    import threading

    init_timeout = float(os.environ.get("RAFIKI_BACKEND_INIT_TIMEOUT_S", "180"))

    def _init_stuck():
        print(f"worker {worker_id}: FATAL backend init exceeded "
              f"{init_timeout:.0f}s (TPU runtime unreachable?) — exiting",
              flush=True)
        os._exit(17)

    watchdog = threading.Timer(init_timeout, _init_stuck)
    watchdog.daemon = True
    watchdog.start()

    # Persistent XLA compilation cache: a restarted (or sibling) worker
    # loads executables compiled by any previous process instead of
    # recompiling — the cross-process half of compile amortization (the
    # in-process half is ops.train's program cache).
    from rafiki_tpu.utils.backend import enable_compilation_cache

    enable_compilation_cache()

    # Multi-host pods: when the scheduler provides coordinator env, join
    # the jax.distributed cluster over DCN before touching devices —
    # this worker then sees its host's chips while collectives span the
    # pod (the reference's NCCL/MPI role is played by XLA here).
    # Process 0 of the group is the control-plane leader; the rest
    # mirror its trials compute-for-compute (worker/follower.py).
    coordinator = os.environ.get("RAFIKI_COORDINATOR_ADDRESS")
    if coordinator:
        from rafiki_tpu import chaos

        # jax gates cross-process CPU collectives behind a config
        # switch; without gloo a multi-process CPU group dies at first
        # program init with "Multiprocess computations aren't
        # implemented on the CPU backend". Must land before the backend
        # client is created; irrelevant (and skipped) on TPU platforms.
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

        process_id = int(os.environ["RAFIKI_PROCESS_ID"])
        # Start-skew site: a delay-mode fault here staggers this
        # process's arrival at the collective barrier (leader/follower
        # skew — docs/chaos.md).
        chaos.hook("mesh.skew", key=f"p{process_id}")
        initialize_collective(
            jax.distributed.initialize, coordinator,
            int(os.environ["RAFIKI_NUM_PROCESSES"]), process_id)

    jax.devices()  # force backend init under the watchdog
    watchdog.cancel()

    from rafiki_tpu.utils.events import configure_from_env, events

    configure_from_env()

    # Observability plane: per-process journal under RAFIKI_LOG_DIR
    # (spawn env), adopt the scheduler's RAFIKI_TRACE_ID as the process
    # default, dump a flight record on fatal/SIGTERM so a killed worker
    # leaves a reconstructible last-N trail (docs/observability.md).
    from rafiki_tpu import obs

    if obs.configure_from_env(role="train-worker"):
        obs.recorder.install()

    from rafiki_tpu.store import MetaStore, ParamsStore

    store = MetaStore(db_path)
    if coordinator:
        events.emit("multihost_init", worker_id=worker_id,
                    process_id=jax.process_index(),
                    process_count=jax.process_count(),
                    global_devices=len(jax.devices()),
                    local_devices=len(jax.local_devices()))
        from rafiki_tpu.parallel.multihost import is_leader

        if not is_leader():
            from rafiki_tpu.worker.follower import FollowerWorker

            n = FollowerWorker(
                store, sub_job_id,
                leader_worker_id=os.environ.get("RAFIKI_LEADER_WORKER_ID"),
                leader_service_id=os.environ.get("RAFIKI_LEADER_SERVICE_ID"),
            ).run()
            print(f"follower {worker_id}: mirrored {n} trials", flush=True)
            return 0

    from rafiki_tpu.advisor.app import HttpAdvisorHandle
    from rafiki_tpu.worker.train import build_worker_from_store

    params_store = ParamsStore(params_dir)
    advisor = HttpAdvisorHandle(advisor_url, advisor_id, secret=secret)
    worker = build_worker_from_store(
        store, params_store, sub_job_id, advisor,
        worker_id=worker_id, devices=jax.devices())
    worker.service_id = service_id
    try:
        # Restart path: this process replaces a crashed predecessor —
        # sweep every dead service row the scheduler recorded for this
        # slot and resume the orphaned trials bound to them (CAS-adopted
        # exactly once even against a racing recovery sweep).
        adopt_sids = os.environ.get("RAFIKI_WORKER_ADOPT_SERVICE_ID", "")
        for sid in filter(None, adopt_sids.split(",")):
            n_adopted = worker.adopt_orphans_of_service(sid)
            if n_adopted:
                print(f"worker {worker_id}: adopted {n_adopted} orphaned "
                      f"trial(s) of dead service {sid}", flush=True)
        n = worker.run()
    finally:
        if coordinator and service_id:
            # Tell our followers we're done BEFORE exiting — on the
            # crash path too: the scheduler only writes terminal
            # sub-job status after ALL group processes exit, so a
            # follower waiting on that (or on a service row a dead
            # leader never updated) would deadlock the group.
            from rafiki_tpu.constants import ServiceStatus

            store.update_service(service_id,
                                 status=ServiceStatus.STOPPED.value)
    print(f"worker {worker_id}: ran {n} trials", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
