"""Services manager: jobs → running services on the TPU host.

Reference parity: rafiki/admin/services_manager.py (unverified —
SURVEY.md §2): translates a train job into one advisor + N train-worker
services and an inference job into one predictor + one inference worker
per chosen trial, writing Service rows as it goes. The reference
materialises services as Docker Swarm containers; here a "service" is a
supervised thread (or, via ProcessScheduler, a subprocess pinned to a
chip) on the TPU host — chips are a host-local resource, so container
orchestration buys nothing and costs startup latency.

Train jobs run asynchronously: ``create_train_services`` returns
immediately and the scheduler drives the job to budget exhaustion in a
background thread (stoppable via ``stop_train_services``).

Inference jobs: per top-k trial, the trial's model class is re-loaded,
its knobs re-applied and its trained parameters restored, then an
InferenceWorker thread serves it off the bus; a Predictor fronts them
(optionally over HTTP — see rafiki_tpu/predictor/app.py).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional

from rafiki_tpu.advisor import AdvisorService
from rafiki_tpu.bus import InProcBus
from rafiki_tpu.config import Config, get_config
from rafiki_tpu.constants import (
    InferenceJobStatus,
    ServiceStatus,
    ServiceType,
    TrainJobStatus,
)
from rafiki_tpu.gateway import Gateway, GatewayConfig
from rafiki_tpu.model.base import load_model_class
from rafiki_tpu.obs.journal import journal as _journal
from rafiki_tpu.predictor.predictor import Predictor
from rafiki_tpu.scheduler.local import LocalScheduler
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.utils.events import events
from rafiki_tpu.worker.inference import InferenceWorker


class _TrainJobHandle:
    def __init__(self, thread: threading.Thread, stop_event: threading.Event):
        self.thread = thread
        self.stop_event = stop_event
        self.result = None
        self.error: Optional[BaseException] = None


class _InferenceJobHandle:
    def __init__(self):
        self.stop_event = threading.Event()
        self.worker_threads: List[threading.Thread] = []
        self.workers: List[InferenceWorker] = []
        self.predictor: Optional[Predictor] = None
        self.gateway: Optional[Gateway] = None
        self.http_server = None  # set when an HTTP frontend is attached
        # Autoscale attachment (docs/autoscale.md): the serving shape a
        # scale-up replica must reproduce, and the live controller.
        self.best_trials: List[dict] = []
        self.batch_size: int = 0
        self.stacked_route: bool = False
        self.autoscaler = None  # AutoscaleController when attached


class ServicesManager:
    def __init__(self, store: MetaStore, params_store: ParamsStore,
                 bus: Optional[InProcBus] = None,
                 advisor_service: Optional[AdvisorService] = None,
                 config: Optional[Config] = None):
        self.store = store
        self.params_store = params_store
        self.bus = bus or InProcBus()
        self.advisors = advisor_service or AdvisorService()
        self.config = config or get_config()
        self._train_jobs: Dict[str, _TrainJobHandle] = {}
        self._inference_jobs: Dict[str, _InferenceJobHandle] = {}
        self._lock = threading.Lock()
        # Fleet-level tenant arbitration (docs/multitenancy.md): when a
        # JobAdmissionGate is attached, create_inference_services runs
        # every NEW job's forecast through the serving twin and refuses
        # jobs whose load would breach an existing tenant's SLO.
        self.job_gate = None
        # Crash-recovery reaper state (docs/recovery.md).
        self._reaper_thread: Optional[threading.Thread] = None
        self._reaper_stop: Optional[threading.Event] = None
        self._resuming: set = set()

    # -- train services ------------------------------------------------------

    def create_train_services(self, job_id: str, n_workers: Optional[int] = None,
                              devices: Optional[List[Any]] = None,
                              devices_per_trial: int = 1,
                              advisor_kind: str = "gp") -> None:
        """Start the job's worker fleet in the background and return."""
        with self._lock:
            if job_id in self._train_jobs and self._train_jobs[job_id].thread.is_alive():
                raise ValueError(f"Train job {job_id} already has running services")
        scheduler = LocalScheduler(self.store, self.params_store, self.advisors)
        stop_event = threading.Event()

        def run():
            try:
                from rafiki_tpu.autoscale import controller as _asc

                if _asc.prewarm_enabled():
                    # Admission-time compile pre-warm (docs/autoscale.md):
                    # build each model's packed program (and persist the
                    # XLA artifacts) BEFORE the sweep starts, so a later
                    # scale-up lands on a warm compile. Best-effort by
                    # contract — admission never fails on it.
                    from rafiki_tpu.autoscale import prewarm as _prewarm

                    try:
                        _prewarm.prewarm_train_job(self.store, job_id)
                    except Exception:
                        from rafiki_tpu import telemetry

                        telemetry.inc("autoscale.prewarm_errors")
                handle.result = scheduler.run_train_job(
                    job_id, n_workers=n_workers, devices=devices,
                    devices_per_trial=devices_per_trial,
                    advisor_kind=advisor_kind, stop_event=stop_event)
            except BaseException as e:  # surfaced via wait_train_job
                handle.error = e
                self.store.update_train_job_status(job_id, TrainJobStatus.ERRORED.value)
                if not isinstance(e, Exception):
                    # Interrupts (SystemExit, KeyboardInterrupt) must
                    # keep propagating after being recorded: absorbing
                    # them here would leave the process undrainable
                    # (RF006).
                    raise

        thread = threading.Thread(target=run, name=f"train-job-{job_id[:8]}", daemon=True)
        handle = _TrainJobHandle(thread, stop_event)
        with self._lock:
            self._train_jobs[job_id] = handle
        thread.start()

    def stop_train_services(self, job_id: str, wait: bool = True,
                            timeout: float = 60.0) -> None:
        with self._lock:
            handle = self._train_jobs.get(job_id)
        if handle is None:
            # No live services in this process (e.g. admin restarted):
            # mark the job stopped — but never clobber a terminal state.
            job = self.store.get_train_job(job_id)
            if job is not None and job["status"] in (TrainJobStatus.STARTED.value,
                                                     TrainJobStatus.RUNNING.value):
                self.store.update_train_job_status(job_id,
                                                   TrainJobStatus.STOPPED.value)
            return
        handle.stop_event.set()
        if wait:
            handle.thread.join(timeout=timeout)

    def wait_train_job(self, job_id: str, timeout: Optional[float] = None):
        """Block until the job's services finish; returns TrainJobResult
        (None when the job already finished outside this process)."""
        with self._lock:
            handle = self._train_jobs.get(job_id)
        if handle is None:
            job = self.store.get_train_job(job_id)
            if job is not None and job["status"] in (TrainJobStatus.STARTED.value,
                                                     TrainJobStatus.RUNNING.value):
                raise RuntimeError(
                    f"Train job {job_id} is {job['status']} but has no services "
                    "in this process (created with start=False, or the admin "
                    "restarted); start it with create_train_services first")
            return None
        handle.thread.join(timeout=timeout)
        if handle.thread.is_alive():
            raise TimeoutError(f"Train job {job_id} still running after {timeout}s")
        if handle.error is not None:
            raise handle.error
        return handle.result

    # -- crash recovery (docs/recovery.md) -----------------------------------

    def start_resume_reaper(self, poll_s: Optional[float] = None,
                            stale_after_s: Optional[float] = None) -> None:
        """Watch for RUNNING jobs whose sweep supervisor stopped
        heartbeating (a crashed/SIGKILLed supervisor process leaves its
        SUPERVISOR service row going stale) and adopt them via
        ``resume_sweep``. Poll cadence from ``RAFIKI_RESUME_POLL_S``,
        liveness cutoff from ``RAFIKI_RESUME_STALE_S`` unless given
        explicitly. Idempotent: a second start while the reaper runs is
        a no-op, and a job being resumed (here or by a racing resumer —
        the CAS adoption settles that) is never picked up twice."""
        from rafiki_tpu.scheduler.recovery import (
            ENV_RESUME_POLL_S,
            ENV_RESUME_STALE_S,
            resume_sweep,
        )

        if self._reaper_thread is not None and self._reaper_thread.is_alive():
            return
        poll = float(poll_s if poll_s is not None
                     else os.environ.get(ENV_RESUME_POLL_S, "10"))
        stale = float(stale_after_s if stale_after_s is not None
                      else os.environ.get(ENV_RESUME_STALE_S, "30"))
        stop = threading.Event()

        def loop():
            while not stop.wait(poll):
                try:
                    dead = self.store.get_jobs_with_dead_supervisor(stale)
                except Exception:
                    continue  # transient store error: next tick retries
                for job in dead:
                    jid = job["id"]
                    with self._lock:
                        handle = self._train_jobs.get(jid)
                        if handle is not None and handle.thread.is_alive():
                            # Our own live services — the job is not
                            # actually abandoned, its heartbeat is.
                            continue
                        if jid in self._resuming:
                            continue
                        self._resuming.add(jid)
                    _journal.record("recovery", "reaper_detected",
                                    job_id=jid, stale_after_s=stale)
                    events.emit("supervisor_dead_detected", job_id=jid)
                    try:
                        resume_sweep(self.store, self.params_store, jid,
                                     stale_after_s=stale,
                                     advisor_service=self.advisors)
                    except Exception as e:
                        # A failed resume must not kill the reaper: the
                        # job stays adoptable and the next pass (or a
                        # manual `sweep_proc resume`) retries.
                        _journal.record("recovery", "reaper_resume_failed",
                                        job_id=jid, error=repr(e))
                    finally:
                        with self._lock:
                            self._resuming.discard(jid)

        self._reaper_stop = stop
        self._reaper_thread = threading.Thread(target=loop,
                                               name="resume-reaper",
                                               daemon=True)
        self._reaper_thread.start()

    def stop_resume_reaper(self, timeout: float = 10.0) -> None:
        if self._reaper_stop is not None:
            self._reaper_stop.set()
        if self._reaper_thread is not None:
            self._reaper_thread.join(timeout=timeout)
        self._reaper_thread = None
        self._reaper_stop = None

    # -- inference services --------------------------------------------------

    def attach_job_gate(self, gate) -> None:
        """Attach a :class:`~rafiki_tpu.tenancy.arbiter.
        JobAdmissionGate`: from now on every new inference job that
        declares a tenant is forecast through the twin first, and a
        job whose load would breach an existing tenant's p99 budget
        raises ``JobRejected`` instead of starting services."""
        self.job_gate = gate

    def create_inference_services(self, inference_job_id: str,
                                  best_trials: List[dict],
                                  batch_size: Optional[int] = None,
                                  serve_http: bool = True,
                                  gateway_overrides: Optional[Dict[str, Any]]
                                  = None,
                                  tenancy=None,
                                  tenant: Optional[str] = None,
                                  tier: Optional[str] = None,
                                  expected_qps: float = 0.0) -> Predictor:
        """One inference worker per trial + a predictor over the bus
        fronted by a serving Gateway (admission control, quorum
        fan-out, breakers — docs/serving.md), plus (by default) a
        published HTTP frontend whose host:port is recorded on the
        inference-job row — the reference's per-job predictor port.

        ``gateway_overrides`` lets a job pick its own routing policy
        and limits (e.g. ``{"policy": "least-loaded",
        "max_inflight": 4}``) over the framework-config defaults.

        Tenancy (docs/multitenancy.md): pass a ``TenantFabric`` as
        ``tenancy`` for a tenant-aware gateway (weighted-fair
        admission + per-tenant accounting). ``tenant``/``tier``/
        ``expected_qps`` declare whose load this job is — with a job
        gate attached, the declared load is twin-forecast against the
        fleet and the job can be REJECTED before any service starts."""
        if not best_trials:
            raise ValueError("No completed trials to serve")
        if self.job_gate is not None and tenant is not None:
            from rafiki_tpu.tenancy.qos import DEFAULT_TIER

            # Raises JobRejected (journaling tenancy/arbiter) when the
            # forecast breaches an existing tenant's budget.
            self.job_gate.admit_job(inference_job_id, tenant,
                                    tier or DEFAULT_TIER, expected_qps)
        handle = _InferenceJobHandle()
        batch_size = batch_size or self.config.inference_batch_size
        try:
            return self._start_inference(handle, inference_job_id, best_trials,
                                         batch_size, serve_http,
                                         gateway_overrides or {}, tenancy)
        except Exception:
            # Tear down whatever already started — otherwise worker
            # threads (each pinning a trained model) leak unreachably.
            handle.stop_event.set()
            for th in handle.worker_threads:
                if th.ident is not None:  # join only threads that started
                    th.join(timeout=5)
            if handle.http_server is not None:
                handle.http_server.shutdown()
                handle.http_server.server_close()
            raise

    def _start_inference(self, handle: "_InferenceJobHandle",
                         inference_job_id: str, best_trials: List[dict],
                         batch_size: int, serve_http: bool,
                         gateway_overrides: Dict[str, Any],
                         tenancy=None) -> Predictor:
        models = [self._load_trial_model(t) for t in best_trials]

        # Same-architecture top-k → ONE worker running a stacked vmapped
        # forward (k models, one XLA program); otherwise the
        # reference-shaped fallback of one worker per trial.
        # RAFIKI_STACKED_SERVING=0 forces the replicated route (ops
        # escape hatch, and the switch for an A/B of the two routes).
        from rafiki_tpu.parallel.serving import build_stacked

        stacked, route_reason = None, "disabled-by-env"
        if os.environ.get("RAFIKI_STACKED_SERVING", "1").lower() not in (
                "0", "false", "no", "off"):
            stacked, route_reason = build_stacked(best_trials, models,
                                                  batch_size=batch_size)
        serve_models = [stacked] if stacked is not None else models
        handle.best_trials = list(best_trials)
        handle.batch_size = batch_size
        handle.stacked_route = stacked is not None
        warmup_s = None
        if stacked is not None:
            # Pre-warm: the stacked program's XLA compile is paid HERE,
            # at service creation, never by the first live request.
            warmup_s = round(stacked.warmup(), 6)
            events.emit("inference_stacked", job_id=inference_job_id,
                        k=len(best_trials))
        # Route decision is journal-worthy: a post-mortem (and the
        # twin's calibration extractor) must see WHICH serving shape
        # this job got and why (docs/serving.md).
        _journal.record("serving", "route", job_id=inference_job_id,
                        route=("stacked" if stacked is not None
                               else "replicated"),
                        reason=route_reason, k=len(best_trials),
                        workers=len(serve_models), warmup_s=warmup_s)

        for i, model in enumerate(serve_models):
            worker_id = f"{inference_job_id[:8]}-iw{i}"
            service = self.store.create_service(
                ServiceType.INFERENCE_WORKER.value, job_id=inference_job_id,
                worker_index=i)
            worker = InferenceWorker(self.bus, inference_job_id, worker_id, model,
                                     batch_size=batch_size,
                                     stop_event=handle.stop_event)
            th = threading.Thread(target=self._run_inference_worker,
                                  args=(worker, service["id"]),
                                  name=worker_id, daemon=True)
            handle.workers.append(worker)
            handle.worker_threads.append(th)

        self.store.create_service(ServiceType.PREDICTOR.value, job_id=inference_job_id)
        handle.predictor = Predictor(self.bus, inference_job_id,
                                     timeout_s=self.config.predict_timeout_s)
        handle.gateway = Gateway(handle.predictor,
                                 GatewayConfig.from_config(
                                     self.config, **gateway_overrides),
                                 tenancy=tenancy)
        for th in handle.worker_threads:
            th.start()
        # Wait for workers to register so the first query doesn't race them.
        deadline = 5.0
        import time
        t0 = time.monotonic()
        while (len(self.bus.get_workers(inference_job_id)) < len(serve_models)
               # lint: disable=RF007 — bounded startup wait, not traced
               and time.monotonic() - t0 < deadline):
            time.sleep(0.01)
        predictor_host = None
        if serve_http:
            from rafiki_tpu.predictor.app import start_predictor_server

            handle.http_server, predictor_host = start_predictor_server(
                handle.gateway, host=self.config.admin_host)
            # A wildcard bind address is unroutable for clients: advertise
            # a reachable address instead.
            bind_host, _, port = predictor_host.rpartition(":")
            if bind_host in ("0.0.0.0", "::", ""):
                import socket

                try:
                    advertise = socket.gethostbyname(socket.gethostname())
                except OSError:
                    advertise = "127.0.0.1"
                predictor_host = f"{advertise}:{port}"
        self.store.update_inference_job(inference_job_id,
                                        status=InferenceJobStatus.RUNNING.value,
                                        predictor_host=predictor_host)
        events.emit("inference_job_started", job_id=inference_job_id,
                    n_workers=len(best_trials), predictor_host=predictor_host)
        with self._lock:
            self._inference_jobs[inference_job_id] = handle
        return handle.predictor

    # -- co-hosted serving (docs/multitenancy.md) ----------------------------

    def _make_program_loader(self, trials: List[dict], batch_size: int):
        """A lazy model loader for one co-hosted job: runs on residency
        MISS (first query, or re-activation after an LRU eviction),
        never at service creation — a cold job costs zero HBM until it
        is actually queried."""
        def load():
            models = [self._load_trial_model(t) for t in trials]
            if len(models) == 1:
                return models[0]
            from rafiki_tpu.parallel.serving import build_stacked

            stacked, _ = build_stacked(trials, models,
                                       batch_size=batch_size)
            return stacked if stacked is not None else models[0]

        return load

    def create_cohosted_inference_services(
            self, job_trials: Dict[str, List[dict]],
            batch_size: Optional[int] = None,
            gateway_overrides: Optional[Dict[str, Any]] = None,
            tenancy_for: Optional[Dict[str, Any]] = None,
            hbm_budget_bytes: Optional[int] = None) -> Dict[str, Predictor]:
        """ONE inference worker serving EVERY job in ``job_trials``
        behind a :class:`~rafiki_tpu.tenancy.hosting.ProgramHost`:
        models swap in and out of a shared HBM byte budget by LRU
        residency (journaled ``tenancy/residency``) instead of each
        job pinning a dedicated worker — the k-models-many-jobs
        generalization of the stacked route. Each job keeps its OWN
        Predictor + Gateway (admission, QoS and metrics stay per-job);
        the predictor tags queries with the job's program id and the
        host routes them. ``tenancy_for`` maps job id → TenantFabric
        for jobs that want tenant-aware gateways.

        Returns ``{job_id: Predictor}``. The shared worker is owned by
        the FIRST job's handle; the cohort shares one stop event, so
        stopping ANY co-hosted job stops serving for all of them —
        co-hosting trades blast-radius isolation for HBM efficiency
        and that trade is explicit here."""
        if not job_trials:
            raise ValueError("No jobs to co-host")
        from rafiki_tpu.tenancy.hosting import ProgramHost, ProgramSpec
        from rafiki_tpu.tenancy.residency import ResidencyManager

        batch_size = batch_size or self.config.inference_batch_size
        job_ids = list(job_trials)
        specs = []
        for job_id, trials in job_trials.items():
            if not trials:
                raise ValueError(f"Job {job_id} has no completed trials")
            # HBM charge estimate: the params blobs' on-disk bytes
            # (floored — an estimate of 0 would make eviction free).
            size = sum(self.params_store.size(t["params_id"])
                       for t in trials if t.get("params_id"))
            specs.append(ProgramSpec(
                program_id=job_id,
                loader=self._make_program_loader(list(trials), batch_size),
                size_bytes=max(size, 1 << 20)))
        host = ProgramHost(specs,
                           residency=ResidencyManager(hbm_budget_bytes))
        primary, extras = job_ids[0], job_ids[1:]
        worker_id = f"cohost-{primary[:8]}-iw0"
        stop_event = threading.Event()
        worker = InferenceWorker(self.bus, primary, worker_id, host,
                                 batch_size=batch_size,
                                 stop_event=stop_event,
                                 extra_job_ids=extras)
        service = self.store.create_service(
            ServiceType.INFERENCE_WORKER.value, job_id=primary,
            worker_index=0)
        th = threading.Thread(target=self._run_inference_worker,
                              args=(worker, service["id"]),
                              name=worker_id, daemon=True)
        th.start()
        _journal.record("tenancy", "cohost", worker_id=worker_id,
                        jobs=list(job_ids),
                        budget_bytes=host.residency.budget_bytes)
        # Wait for the worker to register under every co-hosted job id
        # so the first query doesn't race registration.
        import time
        t0 = time.monotonic()
        while (any(worker_id not in self.bus.get_workers(j)
                   for j in job_ids)
               # lint: disable=RF007 — bounded startup wait, not traced
               and time.monotonic() - t0 < 5.0):
            time.sleep(0.01)
        predictors: Dict[str, Predictor] = {}
        fabrics = tenancy_for or {}
        for job_id in job_ids:
            handle = _InferenceJobHandle()
            handle.stop_event = stop_event  # cohort-shared by design
            if job_id == primary:
                handle.workers.append(worker)
                handle.worker_threads.append(th)
            handle.best_trials = list(job_trials[job_id])
            handle.batch_size = batch_size
            self.store.create_service(ServiceType.PREDICTOR.value,
                                      job_id=job_id)
            handle.predictor = Predictor(
                self.bus, job_id, timeout_s=self.config.predict_timeout_s,
                program=job_id)
            handle.gateway = Gateway(handle.predictor,
                                     GatewayConfig.from_config(
                                         self.config,
                                         **(gateway_overrides or {})),
                                     tenancy=fabrics.get(job_id))
            self.store.update_inference_job(
                job_id, status=InferenceJobStatus.RUNNING.value,
                predictor_host=None)
            events.emit("inference_job_started", job_id=job_id,
                        n_workers=1, predictor_host=None)
            with self._lock:
                self._inference_jobs[job_id] = handle
            predictors[job_id] = handle.predictor
        return predictors

    def _run_inference_worker(self, worker: InferenceWorker, service_id: str) -> None:
        self.store.update_service(service_id, status=ServiceStatus.RUNNING.value)
        try:
            worker.run()
            self.store.update_service(service_id, status=ServiceStatus.STOPPED.value)
        except Exception:
            self.store.update_service(service_id, status=ServiceStatus.ERRORED.value)

    def _load_trial_model(self, trial: dict):
        """Rebuild a trained model from its trial row: class + knobs + params."""
        sub = self.store.get_sub_train_job(trial["sub_train_job_id"])
        if sub is None:  # data-integrity failure, not a caller mistake
            raise RuntimeError(f"Trial {trial['id']} has no sub train job")
        model_row = self.store.get_model(sub["model_id"])
        model_cls = load_model_class(model_row["model_file"], model_row["model_class"])
        model = model_cls(**trial["knobs"])
        if trial.get("params_id"):
            model.load_parameters(self.params_store.load(trial["params_id"]))
        return model

    def get_predictor(self, inference_job_id: str) -> Optional[Predictor]:
        with self._lock:
            handle = self._inference_jobs.get(inference_job_id)
        return handle.predictor if handle else None

    def get_gateway(self, inference_job_id: str) -> Optional[Gateway]:
        with self._lock:
            handle = self._inference_jobs.get(inference_job_id)
        return handle.gateway if handle else None

    # -- autoscale (docs/autoscale.md) ---------------------------------------

    def _spawn_scale_replica(self, handle: "_InferenceJobHandle",
                             inference_job_id: str, index: int):
        """Build one scale-up replica of the job's serving shape: the
        stacked ensemble when that route was taken (one worker = whole
        ensemble; its compile is warm via the stacked warmup + the
        persistent XLA cache), otherwise the best trial's model. Own
        stop event — the autoscaler drains replicas one at a time,
        never through the job-wide event."""
        if handle.stacked_route:
            from rafiki_tpu.parallel.serving import build_stacked

            models = [self._load_trial_model(t) for t in handle.best_trials]
            stacked, _ = build_stacked(handle.best_trials, models,
                                       batch_size=handle.batch_size)
            model = stacked if stacked is not None else models[0]
            if stacked is not None:
                stacked.warmup()
        else:
            model = self._load_trial_model(handle.best_trials[0])
        worker_id = f"{inference_job_id[:8]}-as{index}"
        service = self.store.create_service(
            ServiceType.INFERENCE_WORKER.value, job_id=inference_job_id,
            worker_index=1000 + index)
        worker = InferenceWorker(self.bus, inference_job_id, worker_id,
                                 model, batch_size=handle.batch_size)
        th = threading.Thread(target=self._run_inference_worker,
                              args=(worker, service["id"]),
                              name=worker_id, daemon=True)
        th.start()
        handle.workers.append(worker)
        handle.worker_threads.append(th)
        return worker_id, worker, th

    def attach_autoscaler(self, inference_job_id: str,
                          min_workers: Optional[int] = None,
                          max_workers: Optional[int] = None,
                          tick_s: Optional[float] = None,
                          pregate_fn=None, start: bool = True,
                          **controller_kwargs):
        """Close the loop over a running inference job: SLO burn +
        gateway sensors in, worker spawn/drain out, every decision
        journaled. The baseline fleet is the floor by default — the
        controller only drains replicas it spawned (they carry their
        own stop events; the original workers share the job-wide one).
        Returns the started :class:`AutoscaleController`."""
        from rafiki_tpu.autoscale import actuators as _actuators
        from rafiki_tpu.autoscale import controller as _asc

        with self._lock:
            handle = self._inference_jobs.get(inference_job_id)
        if handle is None:
            raise ValueError(f"Inference job {inference_job_id} has no "
                             "running services in this process")
        baseline = [(w.worker_id, w, None) for w in handle.workers]
        lane = _actuators.InferenceWorkerLane(
            self.bus, inference_job_id,
            spawn_fn=lambda i: self._spawn_scale_replica(
                handle, inference_job_id, i),
            initial=baseline)
        overrides: Dict[str, Any] = {
            "min_size": (len(baseline) if min_workers is None
                         else min_workers)}
        if max_workers is not None:
            overrides["max_size"] = max_workers
        spec = _asc.LaneSpec.from_env("inference", **overrides)
        if (handle.gateway is not None
                and getattr(handle.gateway, "tenancy", None) is not None):
            # Tenant-aware fleet (docs/multitenancy.md): the lane
            # scales on the WORST of the classic inference pressure
            # and the tenant aggregates (worst per-tenant burn /
            # tenant shed rate) — one tenant burning its p99 budget is
            # a capacity signal even while the global queue is calm.
            import dataclasses as _dc

            from rafiki_tpu.tenancy.arbiter import tenant_pressure

            base_fn = spec.pressure_fn

            def _tenant_aware(sensors, _base=base_fn):
                bp, breason = _base(sensors)
                tp, treason = tenant_pressure(sensors)
                if bp is None or (tp is not None and tp > bp):
                    return tp, treason
                return bp, breason

            spec = _dc.replace(spec, pressure_fn=_tenant_aware)
        controller = _asc.AutoscaleController(
            lanes=[spec],
            sensor_fn=lambda: _asc.read_sensors(gateway=handle.gateway),
            actuators={"inference": lane},
            tick_s=tick_s, pregate_fn=pregate_fn, **controller_kwargs)
        handle.autoscaler = controller
        if start:
            controller.start()
        return controller

    def attach_http_server(self, inference_job_id: str, server) -> None:
        with self._lock:
            handle = self._inference_jobs.get(inference_job_id)
        if handle is not None:
            handle.http_server = server

    def stop_inference_services(self, inference_job_id: str,
                                timeout: float = 10.0) -> None:
        with self._lock:
            handle = self._inference_jobs.pop(inference_job_id, None)
        if handle is None:
            self.store.update_inference_job(inference_job_id,
                                            status=InferenceJobStatus.STOPPED.value)
            return
        if handle.autoscaler is not None:
            # The control loop stops FIRST: a controller reacting to
            # the drain's shed spike would fight the teardown.
            handle.autoscaler.stop()
        if handle.gateway is not None:
            # Graceful drain BEFORE the workers stop: in-flight requests
            # finish against live workers; new arrivals shed immediately.
            handle.gateway.drain(timeout=min(timeout, 5.0))
        handle.stop_event.set()
        for th in handle.worker_threads:
            th.join(timeout=timeout)
        if handle.http_server is not None:
            handle.http_server.shutdown()
            handle.http_server.server_close()  # release the listening FD now
        self.store.update_inference_job(inference_job_id,
                                        status=InferenceJobStatus.STOPPED.value)
        events.emit("inference_job_stopped", job_id=inference_job_id)

    # -- teardown ------------------------------------------------------------

    def stop_all(self) -> None:
        self.stop_resume_reaper()
        with self._lock:
            train_ids = list(self._train_jobs)
            inf_ids = list(self._inference_jobs)
        for jid in train_ids:
            self.stop_train_services(jid, wait=False)
        for jid in inf_ids:
            self.stop_inference_services(jid)
        for jid in train_ids:
            self.stop_train_services(jid, wait=True)
