"""Process-per-chip scheduler: one OS process per worker, one (or k)
chips per process.

This is the production scheduler shape (SURVEY.md §7 "hard parts":
per-chip trial isolation). JAX wants one runtime per process —
concurrent trials in one process contend on compilation locks and
device memory. Spawning each worker as a subprocess whose environment
exposes only its own chip(s) gives the same isolation the reference
got from one-GPU-per-container (CUDA_VISIBLE_DEVICES), with none of
the container overhead:

  * TPU: ``TPU_VISIBLE_CHIPS=<i>`` (+ per-process bounds) pins a
    process to chip i; ``XLA_PYTHON_CLIENT_PREALLOCATE=false`` keeps
    N runtimes from fighting over HBM at startup.
  * CPU (tests / fake pod): each subprocess gets its own
    ``--xla_force_host_platform_device_count=k`` fake chips.

Coordination is exactly the reference's: the meta store (shared
sqlite, atomic trial claiming) is the source of truth and the advisor
is shared over loopback HTTP (reference: advisor container + REST).
"""

from __future__ import annotations

import contextlib
import os
import secrets as _secrets
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from rafiki_tpu import chaos
from rafiki_tpu.obs import context as trace_context
from rafiki_tpu.obs.journal import journal as _journal
from rafiki_tpu.advisor import AdvisorService
from rafiki_tpu.advisor.app import AdvisorApp
from rafiki_tpu.constants import ServiceStatus, ServiceType, TrainJobStatus, TrialStatus
from rafiki_tpu.model.base import load_model_class
from rafiki_tpu.scheduler.local import TrainJobResult
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.utils.events import events


_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _free_ports(n: int) -> List[int]:
    """n distinct free loopback ports: all probe sockets are held open
    until every port is chosen, so the OS cannot hand the same port to
    two groups (the residual race against unrelated processes between
    close and the coordinator's bind is inherent and accepted)."""
    import socket

    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def worker_device_env(platform: str, worker_index: int,
                      devices_per_trial: int = 1) -> Dict[str, str]:
    """Env vars that pin a worker subprocess to its own device set.

    ``JAX_PLATFORMS`` is set on both branches, so a worker that cannot
    get the backend the scheduler promised fails at start-up instead of
    training on whatever jax falls back to.
    """
    # lint: disable=RF002 — the scheduler names the platform it promises its workers; the installed jax registers the chip as "tpu"
    if platform == "tpu":
        first = worker_index * devices_per_trial
        chips = ",".join(str(first + j) for j in range(devices_per_trial))
        return {
            "JAX_PLATFORMS": "tpu",
            "TPU_VISIBLE_CHIPS": chips,
            "TPU_CHIPS_PER_PROCESS_BOUNDS": f"1,{devices_per_trial},1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
        }
    if platform != "cpu":
        raise ValueError(f"no worker environment for platform {platform!r} "
                         f"(known: 'tpu', 'cpu')")
    # cpu: every subprocess fakes its own `devices_per_trial` chips
    from rafiki_tpu.utils.backend import host_device_count_flag

    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": host_device_count_flag(devices_per_trial),
    }


def platform_from_env() -> str:
    """The platform the worker subprocesses should claim, read from the
    environment alone: the scheduler process must not initialise a jax
    backend — on a TPU host that would take the chip its workers need.
    An explicit ``JAX_PLATFORMS=cpu`` means CPU workers; anything else
    means the chips."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    return "cpu" if first == "cpu" else "tpu"


class _WorkerGroup:
    """One worker slot's process set (leader + multihost followers)
    plus its restart bookkeeping. procs[0] is always the leader."""

    def __init__(self, index: int):
        self.index = index
        self.procs: List[subprocess.Popen] = []
        self.out_files: list = []
        self.service: Optional[dict] = None
        self.leader_worker_id = ""
        self.restarts = 0
        self.respawn_at: Optional[float] = None  # monotonic; None = live
        # Monotonic time a follower was first seen exited rc=0 while the
        # leader still ran; None while the group is whole. See state().
        self.partial_exit_at: Optional[float] = None
        # Service rows of every dead predecessor in this slot: the
        # replacement must sweep them ALL — a restart that crashed
        # before adopting leaves the orphan bound to an older corpse.
        self.dead_services: List[str] = []

    # A follower that exits rc=0 mid-trial is just as gone as one that
    # crashed — the leader's next collective will never complete — but
    # a zero rc can also be the harmless tail of a clean group
    # shutdown racing the poll. The grace window separates the two:
    # long enough for the leader's own clean exit to land, far shorter
    # than the collective transport timeout (minutes) that used to be
    # the only thing ending the wedge (round-4 ADVICE d).
    FOLLOWER_EXIT_GRACE_S = 15.0

    def state(self) -> str:
        """'running' | 'ok' | 'failed'. A member dead non-zero while the
        leader hasn't exited cleanly fails the whole group immediately —
        the survivors are inside (or headed into) collectives their dead
        peer will never join, and waiting for the transport timeout to
        tell us so would wedge the job for minutes. A member dead rc=0
        while the leader lives fails the group too, after a bounded
        grace window (see FOLLOWER_EXIT_GRACE_S)."""
        rcs = [p.poll() for p in self.procs]
        if any(rc is None for rc in rcs):
            if any(rc not in (0, None) for rc in rcs) and rcs[0] != 0:
                return "failed"
            if rcs[0] is None and any(rc == 0 for rc in rcs[1:]):
                now = time.monotonic()
                if self.partial_exit_at is None:
                    self.partial_exit_at = now
                elif now - self.partial_exit_at > self._follower_exit_grace_s():
                    return "failed"
            else:
                self.partial_exit_at = None
            return "running"
        self.partial_exit_at = None
        return "ok" if rcs[0] == 0 else "failed"

    def _follower_exit_grace_s(self) -> float:
        return float(os.environ.get("RAFIKI_FOLLOWER_EXIT_GRACE_S",
                                    self.FOLLOWER_EXIT_GRACE_S))

    def terminate(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def collect(self, blame=lambda k, rc: rc != 0) -> List[str]:
        """Reap every process and read its output; returns descriptions
        of members the ``blame(member_index, rc)`` predicate selects."""
        msgs = []
        for k, (p, f) in enumerate(zip(self.procs, self.out_files)):
            try:
                rc = p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                rc = p.wait()
            f.seek(0)
            out = f.read()
            f.close()
            if blame(k, rc):
                label = (f"worker {self.index}" if k == 0
                         else f"worker {self.index} follower {k}")
                msgs.append(f"{label} rc={rc}: {out[-2000:]}")
        self.procs, self.out_files = [], []
        return msgs

    def shutdown(self) -> List[str]:
        """Kill survivors, reap everything; returns the ORIGINAL
        failures only — members this teardown killed are not blamed."""
        original = [p.poll() for p in self.procs]
        self.terminate()
        return self.collect(blame=lambda k, rc: original[k] not in (None, 0))


class ProcessScheduler:
    """Same run_train_job contract as LocalScheduler, subprocess workers."""

    def __init__(self, store: MetaStore, params_store: ParamsStore,
                 db_path: Optional[str] = None,
                 params_dir: Optional[str] = None,
                 advisor_service: Optional[AdvisorService] = None):
        self.store = store
        self.params_store = params_store
        # Subprocesses need filesystem paths, not live objects.
        self.db_path = str(db_path if db_path is not None else store.path)
        self.params_dir = str(params_dir if params_dir is not None
                              else params_store.directory)
        self.advisors = advisor_service or AdvisorService()

    # -- advisor server ------------------------------------------------------

    def _start_advisor_server(self):
        from werkzeug.serving import make_server

        secret = _secrets.token_hex(16)
        app = AdvisorApp(self.advisors, secret=secret)
        server = make_server("127.0.0.1", 0, app, threaded=True)
        thread = threading.Thread(target=server.serve_forever,
                                  name="advisor-http", daemon=True)
        thread.start()
        return server, thread, secret, f"http://127.0.0.1:{server.server_port}"

    # -- the job -------------------------------------------------------------

    def run_train_job(
        self,
        job_id: str,
        n_workers: int = 1,
        devices_per_trial: int = 1,
        advisor_kind: str = "gp",
        platform: Optional[str] = None,
        stop_event: Optional[threading.Event] = None,
        poll_s: float = 0.5,
        multihost_processes: int = 1,
    ) -> TrainJobResult:
        t0 = time.monotonic()
        job = self.store.get_train_job(job_id)
        if job is None:
            raise KeyError(f"No train job {job_id!r}")
        # Job-level trace: scheduler-side records (spawns, deaths,
        # restarts) stitch under one id; each trial still mints its own
        # trace (worker/train.py) and links back via trial_id fields.
        _trace_scope = contextlib.ExitStack()
        _trace_scope.enter_context(
            trace_context.trace(trace_context.new_trace_id()))
        self.store.update_train_job_status(job_id, TrainJobStatus.RUNNING.value)
        events.emit("train_job_started", job_id=job_id, app=job["app"],
                    budget=job["budget"], scheduler="process")
        stop_event = stop_event or threading.Event()
        if platform is None:
            platform = platform_from_env()

        budget = dict(job["budget"])
        chip_budget = budget.get("CHIP_COUNT") or budget.get("GPU_COUNT")
        if chip_budget:
            # Each worker group consumes devices_per_trial chips on EACH
            # of its multihost processes.
            per_group = devices_per_trial * max(1, multihost_processes)
            n_workers = min(n_workers, max(1, int(chip_budget) // per_group))

        server, thread, secret, advisor_url = self._start_advisor_server()
        errors: List[str] = []
        try:
            subs = self.store.get_sub_train_jobs(job_id)
            if not subs:
                raise ValueError(f"Train job {job_id} has no sub jobs")
            for sub in subs:
                if stop_event.is_set():
                    self.store.update_sub_train_job(
                        sub["id"], status=TrainJobStatus.STOPPED.value)
                    continue
                self._run_sub_job(sub, job, n_workers, devices_per_trial,
                                  advisor_kind, platform, advisor_url, secret,
                                  stop_event, poll_s, errors,
                                  multihost_processes=multihost_processes)
        except BaseException:
            # Never leave the job stuck in RUNNING: mark terminal, then
            # re-raise for the caller.
            self.store.update_train_job_status(job_id,
                                               TrainJobStatus.ERRORED.value)
            events.emit("train_job_finished", job_id=job_id,
                        status=TrainJobStatus.ERRORED.value,
                        # lint: disable=RF007 — job duration emitted into the event itself
                        duration_s=round(time.monotonic() - t0, 3))
            raise
        finally:
            server.shutdown()
            thread.join(timeout=5)
            _trace_scope.close()

        subs_after = self.store.get_sub_train_jobs(job_id)
        if stop_event.is_set():
            status = TrainJobStatus.STOPPED.value
        elif subs_after and all(s["status"] == TrainJobStatus.ERRORED.value
                                for s in subs_after):
            status = TrainJobStatus.ERRORED.value
        else:
            status = TrainJobStatus.COMPLETED.value
        self.store.update_train_job_status(job_id, status)
        # lint: disable=RF007 — job duration emitted into the event/result below
        dur_s = time.monotonic() - t0
        events.emit("train_job_finished", job_id=job_id, status=status,
                    duration_s=round(dur_s, 3))
        return TrainJobResult(
            job_id=job_id, status=status,
            trials=self.store.get_trials_of_train_job(job_id),
            best_trials=self.store.get_best_trials_of_train_job(job_id, limit=2),
            duration_s=dur_s, errors=errors)

    def _spawn_group(self, g: _WorkerGroup, ctx: dict,
                     port: Optional[int] = None) -> None:
        """(Re)spawn one worker group: a fresh service row, a fresh
        leader worker id (suffixed -r<attempt> on restarts), and — when
        this is a restart — the adopt hook env pointing at the dead
        predecessor's service row so the new leader resumes its
        orphaned trial."""
        import tempfile

        job, sub = ctx["job"], ctx["sub"]
        platform, mh = ctx["platform"], ctx["multihost"]
        g.partial_exit_at = None  # fresh process set, fresh grace
        service = self.store.create_service(
            ServiceType.TRAIN_WORKER.value, job_id=job["id"],
            worker_index=g.index, devices=[f"{platform}:{g.index}"])
        g.service = service
        # Multi-host dp group: N processes per worker — process 0 leads
        # (control plane), 1..N-1 follow (compute mirror,
        # worker/follower.py) — coordinated via jax.distributed on a
        # per-group loopback port (production pods use the pod's
        # coordinator host; same env contract).
        coordinator = f"127.0.0.1:{port}" if mh > 1 else None
        leader_worker_id = f"{job['id'][:8]}-p{g.index}" + (
            f"-r{g.restarts}" if g.restarts else "")
        g.leader_worker_id = leader_worker_id
        for j in range(mh):
            env = dict(os.environ)
            # `-m rafiki_tpu.worker.main` must find THIS package wherever
            # the scheduler's process was started from.
            env["PYTHONPATH"] = os.pathsep.join(
                [_PACKAGE_ROOT] + [p for p in env.get("PYTHONPATH", "").split(
                    os.pathsep) if p])
            if platform == "cpu" or mh <= 1:
                env.update(worker_device_env(
                    platform, g.index * mh + j, ctx["devices_per_trial"]))
            # else: a real multi-host TPU group must keep the pod
            # runtime's own topology env (TPU_WORKER_ID etc.) — a
            # flat per-process chip index + single-process bounds
            # would contradict the jax.distributed cluster.
            env.update({
                "RAFIKI_WORKER_DB": self.db_path,
                "RAFIKI_WORKER_PARAMS_DIR": self.params_dir,
                "RAFIKI_WORKER_SUB_JOB_ID": sub["id"],
                "RAFIKI_WORKER_ID": leader_worker_id + (
                    f".{j}" if mh > 1 and j > 0 else ""),
                "RAFIKI_WORKER_SERVICE_ID": service["id"] if j == 0 else "",
                "RAFIKI_WORKER_ADVISOR_URL": ctx["advisor_url"],
                "RAFIKI_WORKER_ADVISOR_ID": ctx["advisor_id"],
                "RAFIKI_WORKER_ADVISOR_SECRET": ctx["secret"],
            })
            if j == 0 and g.dead_services:
                env["RAFIKI_WORKER_ADOPT_SERVICE_ID"] = ",".join(g.dead_services)
            if coordinator is not None:
                env.update({
                    "RAFIKI_COORDINATOR_ADDRESS": coordinator,
                    "RAFIKI_NUM_PROCESSES": str(mh),
                    "RAFIKI_PROCESS_ID": str(j),
                    "RAFIKI_LEADER_WORKER_ID": leader_worker_id,
                    "RAFIKI_LEADER_SERVICE_ID": service["id"],
                })
            if events.path is not None:  # subprocess shares the event sink
                env["RAFIKI_EVENTS_DIR"] = str(events.path.parent)
            # Observability propagation: the child journals into the
            # same log dir and adopts this job's trace as its process
            # default — the spawn edge of cross-process stitching.
            if _journal.configured:
                env["RAFIKI_LOG_DIR"] = str(_journal.log_dir)
            _tid = trace_context.current_trace_id()
            if _tid:
                env["RAFIKI_TRACE_ID"] = _tid
            # Worker output goes to a temp file, not a pipe: a full
            # pipe buffer would block the worker's writes and
            # deadlock the supervise loop.
            out_f = tempfile.TemporaryFile(mode="w+t")
            g.out_files.append(out_f)
            g.procs.append(subprocess.Popen(
                [sys.executable, "-m", "rafiki_tpu.worker.main"],
                env=env, stdout=out_f, stderr=subprocess.STDOUT, text=True))
        self.store.update_service(service["id"],
                                  status=ServiceStatus.RUNNING.value)

    @staticmethod
    def _maybe_preempt(g: _WorkerGroup) -> None:
        """Enact a ``scheduler.preempt`` fault on a running group's
        leader: ``term`` = SIGTERM, ``kill`` = SIGKILL, ``preempt`` =
        SIGTERM now with a SIGKILL follow-up after the fault's
        ``delay`` grace — the maintenance-eviction shape (a real
        preemption notice gives the process a bounded window to die
        cleanly before the host yanks it)."""
        fault = chaos.decide("scheduler.preempt", key=f"w{g.index}")
        if fault is None or not g.procs:
            return
        leader = g.procs[0]
        events.emit("chaos_preempt", worker_index=g.index, mode=fault.mode)
        if fault.mode == "kill":
            leader.kill()
        elif fault.mode in ("term", "preempt"):
            leader.terminate()
            if fault.mode == "preempt":
                def _kill_after(p=leader, grace=fault.delay_s):
                    try:
                        p.wait(timeout=grace)
                    except subprocess.TimeoutExpired:
                        p.kill()

                threading.Thread(target=_kill_after, daemon=True,
                                 name=f"chaos-preempt-w{g.index}").start()

    def _run_sub_job(self, sub: dict, job: dict, n_workers: int,
                     devices_per_trial: int, advisor_kind: str, platform: str,
                     advisor_url: str, secret: str,
                     stop_event: threading.Event, poll_s: float,
                     errors: List[str], multihost_processes: int = 1) -> None:
        sub_errors: List[str] = []  # this sub job's failures only
        model_row = self.store.get_model(sub["model_id"])
        try:  # validate the template before spending processes on it
            model_cls = load_model_class(model_row["model_file"],
                                         model_row["model_class"])
        except Exception as e:
            self.store.update_sub_train_job(sub["id"],
                                            status=TrainJobStatus.ERRORED.value)
            errors.append(f"model {model_row['name']}: {e}")
            return
        advisor_id = self.advisors.create_advisor(
            model_cls.get_knob_config(),
            kind=advisor_kind, advisor_id=sub.get("advisor_id") or None)
        self.store.update_sub_train_job(sub["id"], advisor_id=advisor_id,
                                        status=TrainJobStatus.RUNNING.value)

        ctx = dict(sub=sub, job=job, platform=platform,
                   devices_per_trial=devices_per_trial,
                   multihost=multihost_processes, advisor_url=advisor_url,
                   advisor_id=advisor_id, secret=secret)
        ports = (_free_ports(n_workers) if multihost_processes > 1 else
                 [None] * n_workers)
        groups = []
        for i in range(n_workers):
            g = _WorkerGroup(i)
            self._spawn_group(g, ctx, port=ports[i])
            groups.append(g)

        # Supervise with in-job elasticity (SURVEY.md §5: the analog of
        # the reference's Swarm restart policy, which resurrected
        # crashed worker containers). A group any member of which dies
        # non-zero is torn down AT ONCE — survivors are killed rather
        # than left to stall until the collective transport timeout —
        # and respawned with exponential backoff, up to max_restarts
        # per group; the replacement leader CAS-adopts the dead
        # worker's orphaned RUNNING trial (worker/main.py adopt hook),
        # so the job still completes its full trial budget.
        max_restarts = int(os.environ.get("RAFIKI_WORKER_MAX_RESTARTS", "2"))
        backoff0 = float(os.environ.get("RAFIKI_WORKER_RESTART_BACKOFF_S", "0.5"))
        abandoned_services: set = set()  # corpses with no replacement coming
        while groups:
            if stop_event.is_set():
                for g in groups:
                    g.terminate()
                stopped_services = set()
                for g in groups:
                    g.collect(blame=lambda k, rc: False)
                    if g.respawn_at is None:
                        # Live group: its service row goes STOPPED. A
                        # group caught in its backoff window keeps the
                        # ERRORED corpse row; either way the group's
                        # orphaned trials are terminated below — no
                        # replacement is coming, and leaving one RUNNING
                        # would hand a trial of an explicitly-stopped
                        # job to the periodic recovery sweep.
                        self.store.update_service(
                            g.service["id"],
                            status=ServiceStatus.STOPPED.value)
                    stopped_services.add(g.service["id"])
                    stopped_services.update(g.dead_services)
                for t in self.store.get_trials_of_sub_train_job(sub["id"]):
                    if (t["status"] == TrialStatus.RUNNING.value
                            and t.get("service_id") in stopped_services):
                        self.store.mark_trial_as_terminated(t["id"])
                groups.clear()
                break
            now = time.monotonic()
            for g in list(groups):
                if g.respawn_at is not None:  # waiting out its backoff
                    if now < g.respawn_at:
                        continue
                    g.respawn_at = None
                    port = (_free_ports(1)[0]
                            if multihost_processes > 1 else None)
                    self._spawn_group(g, ctx, port=port)
                    events.emit("worker_restarted", job_id=job["id"],
                                worker_index=g.index, attempt=g.restarts,
                                adopt_service_ids=list(g.dead_services))
                    continue
                state = g.state()
                if state == "running":
                    # Chaos: simulated preemption/eviction of a live
                    # group, keyed w<index>, one hit per supervise poll.
                    # The normal failed→restart→adopt machinery below is
                    # exactly what the fault must exercise.
                    self._maybe_preempt(g)
                    continue
                if state == "ok":
                    # Non-zero follower exits AFTER a clean leader exit
                    # (budget drained) are shutdown noise, not job
                    # failures — recorded as events only.
                    for msg in g.collect():
                        events.emit("worker_exit_noise", job_id=job["id"],
                                    worker_index=g.index, detail=msg[:500])
                    self.store.update_service(
                        g.service["id"], status=ServiceStatus.STOPPED.value)
                    groups.remove(g)
                    continue
                # state == "failed": tear down, then restart or give up.
                failures = g.shutdown()
                if not failures and g.partial_exit_at is not None:
                    # rc=0 exits are never blamed by shutdown(), so the
                    # follower-gone-clean wedge needs its own message.
                    failures = [
                        f"worker {g.index}: follower exited rc=0 mid-trial "
                        f"while the leader lived; group failed after "
                        f"{g._follower_exit_grace_s():.0f}s grace"]
                g.partial_exit_at = None
                self.store.update_service(
                    g.service["id"], status=ServiceStatus.ERRORED.value)
                # Flight record on the dead child's behalf: a SIGKILLed
                # worker gets no in-process hook, so the scheduler — the
                # only survivor that saw the death — dumps what it knows.
                from rafiki_tpu.obs import recorder

                recorder.dump(
                    f"worker_died:{g.leader_worker_id}",
                    extra={"worker_index": g.index,
                           "service_id": g.service["id"],
                           "restarts": g.restarts,
                           "detail": (failures[0][:500] if failures else "")})
                if g.restarts < max_restarts:
                    g.restarts += 1
                    g.dead_services.append(g.service["id"])
                    backoff_s = backoff0 * (2 ** (g.restarts - 1))
                    g.respawn_at = now + backoff_s
                    # The death→respawn gap is capacity the job paid for
                    # and didn't use: charge it to the goodput ledger.
                    from rafiki_tpu.obs.ledger import ledger

                    ledger.add("downtime_s", backoff_s,
                               entity=f"job:{job['id']}")
                    events.emit("worker_died", job_id=job["id"],
                                worker_index=g.index,
                                restart_attempt=g.restarts,
                                max_restarts=max_restarts,
                                detail=(failures[0][:500] if failures else ""))
                else:
                    sub_errors.extend(failures)
                    events.emit("worker_failed_permanently", job_id=job["id"],
                                worker_index=g.index, restarts=g.restarts)
                    abandoned_services.update(g.dead_services)
                    abandoned_services.add(g.service["id"])
                    groups.remove(g)
            if groups:
                time.sleep(poll_s)
        if abandoned_services:
            # No replacement is coming for these corpses: their orphaned
            # RUNNING trials would otherwise hang the sub-job status in
            # limbo (and a later recovery sweep would re-run a trial
            # whose worker slot provably cannot stay alive).
            for t in self.store.get_trials_of_sub_train_job(sub["id"]):
                if (t["status"] == TrialStatus.RUNNING.value
                        and t.get("service_id") in abandoned_services):
                    self.store.mark_trial_as_errored(
                        t["id"], "worker died; restarts exhausted")
        errors.extend(sub_errors)

        trials = self.store.get_trials_of_sub_train_job(sub["id"])
        if stop_event.is_set():
            sub_status = TrainJobStatus.STOPPED.value
        elif trials and all(t["status"] == TrialStatus.ERRORED.value for t in trials):
            sub_status = TrainJobStatus.ERRORED.value
        elif not trials and sub_errors:  # only this sub job's failures count
            sub_status = TrainJobStatus.ERRORED.value
        else:
            sub_status = TrainJobStatus.COMPLETED.value
        self.store.update_sub_train_job(sub["id"], status=sub_status)
        self.advisors.delete_advisor(advisor_id)
