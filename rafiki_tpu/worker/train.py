"""Train worker: the trial loop.

Reference parity: rafiki/worker/train.py (unverified — SURVEY.md §3.1
is the call stack): poll budget → create Trial row → get knobs from
advisor → load model class → init(knobs) → train → evaluate →
dump_parameters → persist score+params → feedback; mark trial ERRORED
on exception and continue; stop when budget exhausted.

TPU-native specifics:
  * the worker owns a fixed set of jax devices (usually exactly one
    chip — "one trial per chip"); trials run under
    ``jax.default_device`` / a dp Mesh over those devices, so N workers
    in one process drive N chips concurrently, and process-per-chip
    workers isolate XLA runtimes entirely;
  * trial-time model logs are captured via ``logger.capture`` into
    TrialLog rows (same channel as the reference);
  * each trial records its compiled-shape signature so schedulers can
    measure and amortize XLA compile time across like-shaped trials.
"""

from __future__ import annotations

import io
import time
import traceback
from typing import Any, Dict, List, Optional, Protocol

from rafiki_tpu import chaos, telemetry
from rafiki_tpu.advisor.speculative import CurveCoordinator
from rafiki_tpu.constants import BudgetType, TrainJobStatus, TrialStatus
from rafiki_tpu.model.base import BaseModel, load_model_class
from rafiki_tpu.model.knobs import Knobs, knob_config_signature
from rafiki_tpu.model.log import logger
from rafiki_tpu.obs import context as trace_context
from rafiki_tpu.obs import health as _health
from rafiki_tpu.obs.journal import journal
from rafiki_tpu.obs.ledger import ledger
from rafiki_tpu.obs.search import audit as search_audit
from rafiki_tpu.store import MetaStore, ParamsStore
from rafiki_tpu.utils.events import events


class AdvisorHandle(Protocol):
    """What the worker needs from an advisor, local or remote.

    ``propose_batch`` is optional on third-party handles — the packed
    runner probes with getattr and falls back to n× ``propose``."""

    def propose(self) -> Knobs: ...

    def feedback(self, score: float, knobs: Knobs) -> None: ...


class InProcAdvisorHandle:
    def __init__(self, advisor_service, advisor_id: str):
        self._svc = advisor_service
        self._id = advisor_id

    def propose(self) -> Knobs:
        return self._svc.propose(self._id)

    def propose_batch(self, n: int) -> List[Knobs]:
        return self._svc.propose_batch(self._id, n)

    def feedback(self, score: float, knobs: Knobs) -> None:
        self._svc.feedback(self._id, score, knobs)

    def speculate(self, score: float, knobs: Knobs, fit=None) -> None:
        self._svc.speculate(self._id, score, knobs, fit=fit)


def _journal_epoch_eval(trial_id: str, entry: Dict[str, Any],
                        wall_s: Optional[float],
                        packed: bool = False) -> None:
    """Durable per-epoch learning-curve record (``trial/epoch_eval``):
    the substrate the learning-curve-predictive advisor needs — eval
    curves survive the worker process instead of living only in the
    sqlite trial log. No-op for non-epoch log entries and when no
    journal is configured."""
    if entry.get("type") != "values":
        return
    values = entry.get("values") or {}
    if "epoch" not in values:
        return
    score = values.get("acc", values.get("loss"))
    journal.record(
        "trial", "epoch_eval", trial_id=trial_id,
        epoch=int(values["epoch"]),
        score=None if score is None else float(score),
        loss=values.get("loss"), acc=values.get("acc"),
        wall_s=None if wall_s is None else round(float(wall_s), 6),
        packed=packed)


class PackAborted(RuntimeError):
    """A pack was torn down mid-train by its supervisor (chip lost,
    mesh preempt) rather than by a trial failure. The rows stay
    RUNNING — deliberately NOT marked errored — so the mesh scheduler
    can re-pack them onto surviving chips, where each resumes from its
    newest per-epoch packed checkpoint (docs/mesh_sweep.md)."""


class EarlyKilled(RuntimeError):
    """A serial trial condemned mid-flight by the learning-curve
    predictor (docs/early_kill.md): raised from the trial's log sink at
    an epoch boundary, caught by ``run_trial``'s dedicated arm, which
    marks the trial errored, charges the doomed bucket and routes the
    predicted score to the advisor as consolation feedback."""

    def __init__(self, fit, epoch: int, best: float):
        super().__init__(
            f"early-killed at epoch {epoch}: predicted final "
            f"{fit.predicted_final:.4f} (hi {fit.hi:.4f}) vs best {best:.4f}")
        self.fit = fit
        self.epoch = int(epoch)
        self.best = float(best)


class TrainWorker:
    def __init__(
        self,
        store: MetaStore,
        params_store: ParamsStore,
        sub_train_job_id: str,
        model_class: type,
        advisor: AdvisorHandle,
        train_dataset_uri: str,
        val_dataset_uri: str,
        budget: Dict[str, Any],
        worker_id: str = "worker-0",
        devices: Optional[List[Any]] = None,
        job_created_at: Optional[float] = None,
        service_id: Optional[str] = None,
        stop_event=None,
        async_persist: bool = True,
        checkpoint_every: Optional[int] = None,
        trial_pack: Optional[int] = None,
    ):
        if not (isinstance(model_class, type) and issubclass(model_class, BaseModel)):
            raise TypeError("model_class must subclass BaseModel")
        self.store = store
        self.params_store = params_store
        self.sub_id = sub_train_job_id
        self.model_class = model_class
        self.advisor = advisor
        self.train_uri = train_dataset_uri
        self.val_uri = val_dataset_uri
        self.budget = dict(budget or {})
        self.worker_id = worker_id
        self.devices = devices
        self.job_created_at = job_created_at or time.time()
        self.service_id = service_id
        self._stop = stop_event
        # Sweep WAL handle (scheduler/wal.py), set by the mesh scheduler
        # so the mid-pack backfill closure's budget claims are
        # intent/commit-bracketed like the supervisor's up-front ones.
        # None for standalone workers (no durable control plane to join).
        self.wal = None
        self.trials_run = 0
        self._saver = _AsyncSaver(self) if async_persist else None
        # Mid-trial checkpoint cadence (epochs); 0/None = off. Env
        # RAFIKI_CHECKPOINT_EVERY sets the fleet default.
        import os

        if checkpoint_every is None:
            checkpoint_every = int(os.environ.get("RAFIKI_CHECKPOINT_EVERY", "0"))
        self.checkpoint_every = int(checkpoint_every)
        # Trial packing width: k same-program trials vmapped into one
        # XLA program (docs/trial_packing.md). 1 = off (the default,
        # behavior-identical to the serial loop).
        if trial_pack is None:
            trial_pack = int(os.environ.get("RAFIKI_TRIAL_PACK", "1"))
        self.trial_pack = max(1, int(trial_pack))
        # Learning-curve kill/speculation coordinator (docs/
        # early_kill.md). None unless RAFIKI_CURVE_KILL or
        # RAFIKI_CURVE_SPECULATE is set — every consult site guards on
        # `is None`, so the off path is today's loop bit-exactly. The
        # mesh scheduler overwrites this with one coordinator shared
        # across its chip workers (cross-chip best-so-far + stragglers).
        self.curve = CurveCoordinator.from_env()
        from rafiki_tpu.config import get_config

        self.heartbeat_min_interval_s = get_config().trial_heartbeat_s
        self._last_heartbeat = 0.0

    # -- budget --------------------------------------------------------------

    def budget_exhausted(self) -> bool:
        """Non-consuming checks (stop flag, wall clock). The trial-count
        budget is enforced by the atomic claim in ``run()``."""
        if self._stop is not None and self._stop.is_set():
            return True
        hours = self.budget.get(BudgetType.TIME_HOURS.value)
        # lint: disable=RF009 — job age vs a persisted epoch timestamp: job_created_at survives restarts, so wall clock is the only shared basis
        if hours is not None and time.time() - self.job_created_at >= float(hours) * 3600:
            return True
        return False

    # -- one trial -----------------------------------------------------------

    def run_trial(self, knobs: Knobs,
                  resume_trial_id: Optional[str] = None,
                  budget_max: Optional[int] = None) -> Optional[dict]:
        knob_config = self.model_class.get_knob_config()
        sig = knob_config_signature(knob_config, knobs)
        resume = resume_trial_id is not None
        if resume:
            trial = self.store.get_trial(resume_trial_id)
            if trial is None:
                raise KeyError(f"No trial {resume_trial_id!r} to resume")
            # Adopt it: live again, stale crash error cleared, rebound
            # to this worker so recovery sweeps see a live owner.
            self.store.mark_trial_as_running(trial["id"],
                                             service_id=self.service_id,
                                             worker_id=self.worker_id)
        else:
            # budget_max makes row-insert + slot-claim one transaction:
            # None back = the budget drained under us, nothing to run.
            trial = self.store.create_trial(
                self.sub_id, self.model_class.__name__, knobs,
                worker_id=self.worker_id, shape_sig=sig,
                service_id=self.service_id, budget_max=budget_max)
            if trial is None:
                return None
        tid = trial["id"]
        t_trial0 = time.monotonic()

        def sink(entry):
            self.store.add_trial_log(tid, entry)
            _journal_epoch_eval(tid, entry,
                               # lint: disable=RF007 — epoch_eval wall field, already under trial.total
                               wall_s=time.monotonic() - t_trial0)
            if self.curve is not None and entry.get("type") == "values":
                values = entry.get("values") or {}
                # Higher-is-better curves only (acc); loss-only models
                # are never killed — the conservative default.
                if "epoch" in values and values.get("acc") is not None:
                    ep = int(values["epoch"])
                    self.curve.observe(knobs, ep, float(values["acc"]),
                                       trial_id=tid)
                    fit = self.curve.kill_verdict(knobs, ep, trial_id=tid)
                    if fit is not None:
                        raise EarlyKilled(fit, ep, self.curve.best_so_far)
            if self.service_id is not None:
                # Epoch logs double as liveness: long trials heartbeat
                # from inside, so failure detection doesn't flag them.
                # Throttled so chatty per-batch loggers don't turn every
                # log line into an extra sqlite write transaction.
                now = time.monotonic()
                if now - self._last_heartbeat >= self.heartbeat_min_interval_s:
                    self._last_heartbeat = now
                    self.store.update_service(self.service_id, heartbeat=True)

        import contextlib

        # One trial = one trace: spans, journal records and the goodput
        # ledger entity all stitch under it across processes
        # (docs/observability.md). A resumed trial mints a fresh trace —
        # the journal links the attempts through the trial_id field.
        _trace_scope = contextlib.ExitStack()
        _trace_scope.enter_context(
            trace_context.trace(trace_context.new_trace_id()))
        events.emit("trial_started", trial_id=tid, sub_job_id=self.sub_id,
                    model=self.model_class.__name__, worker_id=self.worker_id,
                    knobs=knobs)
        model: Optional[BaseModel] = None
        persisted_async = False
        try:
            with telemetry.span("trial.total", trial_id=tid,
                                worker_id=self.worker_id), \
                    ledger.entity(f"trial:{tid}"), \
                    logger.capture(sink), self._device_scope(), \
                    self._profile_scope(tid):
                # A leaf, except where a checkpoint's restore builds the
                # loop inside it (``train.init`` is the leaf then).
                with telemetry.span("trial.build", leaf=not resume,
                                    trial_id=tid):
                    model = self.model_class(**knobs)
                    if self.devices is not None and len(self.devices) > 1 and hasattr(model, "set_mesh"):
                        from rafiki_tpu.parallel.mesh import data_parallel_mesh

                        model.set_mesh(data_parallel_mesh(self.devices))
                    self._wire_checkpoints(model, tid, resume)
                with telemetry.span("trial.train", trial_id=tid):
                    model.train(self.train_uri)
                with telemetry.span("trial.evaluate", leaf=True, trial_id=tid):
                    score = float(model.evaluate(self.val_uri))
                # Scored: what only training needed can go before the dump
                # is handed on (a model that fills the chip cannot wait
                # for the saver beside the next trial's state).
                release = getattr(model, "release_train_state", None)
                if release is not None:
                    release()
            # The advisor hears the score immediately (it steers the next
            # proposal); parameter persistence is NOT on the critical
            # path — the saver thread dumps/writes/marks-completed while
            # this worker trains the next trial. Serial dump can cost as
            # much as a short trial's train+eval (device→host fetch +
            # serialize), so overlapping it nearly doubles short-trial
            # throughput.
            with telemetry.span("trial.advisor_feedback", leaf=True):
                self.advisor.feedback(score, knobs)
                if self.curve is not None:
                    self.curve.note_scored(knobs, score)
            telemetry.inc("worker.trials_succeeded")
            if self._saver is not None:
                self._saver.submit(tid, model, score, sink)
                persisted_async = True  # saver owns model.destroy() now
            else:
                with logger.capture(sink):
                    self._persist(tid, model, score)
            return self.store.get_trial(tid)
        except EarlyKilled as e:
            # Learning-curve kill (docs/early_kill.md): same shape as
            # the divergence arm — fail the trial FAST with a diagnosis,
            # charge the doomed bucket, keep the worker loop alive. The
            # consolation feedback carries the conservative PREDICTED
            # score (it can never beat best-so-far — the kill rule
            # required hi < best - margin), which steers the advisor
            # more honestly than a 0.0 floor and replays identically
            # from the audit journal on rehydration.
            fit = e.fit
            telemetry.inc("worker.trials_killed")
            self.store.mark_trial_as_errored(
                tid, f"early_killed: predicted {fit.predicted_final:.4f} "
                     f"(hi {fit.hi:.4f}) vs best {e.best:.4f} "
                     f"at epoch {e.epoch}")
            events.emit("trial_killed", trial_id=tid,
                        worker_id=self.worker_id, epoch=e.epoch,
                        predicted=fit.predicted_final)
            self.curve.note_done(knobs)
            search_audit.note_doomed(knobs)
            try:
                self.advisor.feedback(fit.predicted_final, knobs)
            except Exception:
                pass
            return self.store.get_trial(tid)
        except _health.DivergenceError as e:
            # Numerics containment (docs/health.md): the train loop
            # already journaled the divergence, banked the replay
            # capsule and charged the wasted wall to badput. The
            # worker's half of the contract is to fail the trial FAST
            # with the diagnosis (not a stack trace), steer the advisor
            # away from the region, and keep the worker loop alive.
            v = e.verdict
            telemetry.inc("worker.trials_errored")
            self.store.mark_trial_as_errored(tid, f"diverged: {e}")
            events.emit("trial_diverged", trial_id=tid,
                        worker_id=self.worker_id,
                        divergence=v.get("divergence"),
                        bad_step=v.get("bad_step"),
                        capsule=v.get("capsule"),
                        diagnosis=v.get("diagnosis"))
            _health.note_contained()
            if self.curve is not None:
                self.curve.note_done(knobs)
            # Doomed BEFORE the consolation feedback: the search ledger
            # charges this trial's wall to doomed_s, not scored_s.
            search_audit.note_doomed(knobs)
            try:
                self.advisor.feedback(0.0, knobs)
            except Exception:
                pass
            return self.store.get_trial(tid)
        except Exception:
            err = traceback.format_exc()
            telemetry.inc("worker.trials_errored")
            self.store.mark_trial_as_errored(tid, err)
            events.emit("trial_errored", trial_id=tid, worker_id=self.worker_id,
                        error=err.splitlines()[-1] if err else "")
            # Feed the advisor a floor score so it learns to avoid the
            # region instead of re-proposing it (reference just skips).
            if self.curve is not None:
                self.curve.note_done(knobs)
            search_audit.note_doomed(knobs)
            try:
                self.advisor.feedback(0.0, knobs)
            except Exception:
                pass
            return self.store.get_trial(tid)
        finally:
            _trace_scope.close()
            if model is not None and not persisted_async:
                model.destroy()

    def _wire_checkpoints(self, model: BaseModel, tid: str, resume: bool) -> None:
        """Attach mid-trial checkpointing (and restore on resume) when
        the model supports it and a cadence is configured."""
        import os as _os

        multihost = int(_os.environ.get("RAFIKI_NUM_PROCESSES", "1")) > 1
        if resume and hasattr(model, "restore_checkpoint") and not multihost:
            # Multihost groups must NOT restore: followers mirror an
            # adopted trial from epoch 0 (worker/follower.py has no
            # checkpoint channel), so a leader resuming mid-stream would
            # issue fewer collective programs than its followers replay
            # — SPMD pairing beats saved progress.
            latest = self.params_store.latest_checkpoint(tid)
            if latest is not None:
                epoch, blob = latest
                try:
                    start = model.restore_checkpoint(blob)
                    events.emit("trial_resumed", trial_id=tid,
                                from_epoch=start, worker_id=self.worker_id)
                except Exception:
                    # An unreadable checkpoint (e.g. written by an older
                    # state format) must not error the trial — the knobs
                    # are fine; rerun from scratch. Keep the cause: a
                    # systematic format regression must be tellable
                    # apart from one stale legacy blob.
                    events.emit("checkpoint_restore_failed", trial_id=tid,
                                worker_id=self.worker_id,
                                error=traceback.format_exc(limit=5))
        # The sink is also the per-epoch chaos hook site (worker.epoch:
        # kill-at-epoch-N faults), so it gets wired whenever a plane is
        # active even with checkpointing off.
        every = self.checkpoint_every
        if ((every > 0 or chaos.active() is not None)
                and hasattr(model, "set_checkpoint_sink")):
            def sink(epoch: int, make_blob) -> None:
                if every > 0 and (epoch + 1) % every == 0:
                    self._save_checkpoint(tid, epoch, make_blob)
                # AFTER the write: a kill-at-epoch-N fault lands with
                # epoch N's checkpoint already durable, which is the
                # contract resume scenarios assert.
                chaos.hook("worker.epoch", key=self.worker_id)

            model.set_checkpoint_sink(sink)

    def _save_checkpoint(self, tid: str, epoch: int, make_blob) -> None:
        """Write one mid-trial checkpoint, absorbing write failures: a
        checkpoint is an optimization, and a full disk (or an injected
        ``store.params_write`` fault) must cost resumability, not the
        trial — the training loop has the real result in device memory
        and must keep going."""
        t0 = time.monotonic()
        try:
            self.params_store.save_checkpoint(tid, epoch, make_blob())
            events.emit("checkpoint_written", trial_id=tid, epoch=epoch,
                        worker_id=self.worker_id)
        except Exception:
            telemetry.inc("worker.checkpoint_write_failed")
            events.emit("checkpoint_write_failed", trial_id=tid, epoch=epoch,
                        worker_id=self.worker_id,
                        error=traceback.format_exc(limit=3))
        finally:
            # lint: disable=RF007 — checkpoint_s ledger charge, not a span
            ledger.add("checkpoint_s", time.monotonic() - t0,
                       entity=f"trial:{tid}")

    def resume_trial(self, trial_id: str) -> dict:
        """Re-run an interrupted trial, continuing from its newest
        mid-trial checkpoint if one exists (fresh start otherwise). The
        reference cannot do this — a crashed trial is lost (SURVEY.md
        §5 'no mid-trial checkpointing')."""
        trial = self.store.get_trial(trial_id)
        if trial is None:
            raise KeyError(f"No trial {trial_id!r}")
        out = self.run_trial(trial["knobs"], resume_trial_id=trial_id)
        if self._saver is not None:
            # Recovery is a synchronous API: the caller wants the final
            # status, so drain the saver before reading the row.
            self._saver.flush()
            out = self.store.get_trial(trial_id)
        return out

    def _persist(self, tid: str, model: BaseModel, score: float) -> None:
        """Dump → write → mark completed (runs on the saver thread when
        async persistence is on)."""
        t0 = time.monotonic()
        try:
            # Encloses the leaf phases persist.fetch (the model's),
            # persist.write and persist.mark; on the saver thread when
            # persistence is async.
            with telemetry.span("trial.persist", trial_id=tid):
                params_id = save_parameters(self.params_store, model)
                with telemetry.span("persist.mark", leaf=True):
                    self.store.mark_trial_as_completed(tid, score, params_id)
                    self.params_store.delete_checkpoints(tid)  # superseded
            # Persist runs on the saver thread (no bound entity there),
            # so the charge names its trial explicitly.
            # lint: disable=RF007 — checkpoint_s ledger charge, not a span
            ledger.add("checkpoint_s", time.monotonic() - t0,
                       entity=f"trial:{tid}")
            events.emit("trial_completed", trial_id=tid, score=score,
                        worker_id=self.worker_id)
        except Exception:
            err = traceback.format_exc()
            self.store.mark_trial_as_errored(tid, f"params persist failed:\n{err}")
            events.emit("trial_errored", trial_id=tid, worker_id=self.worker_id,
                        error="params persist failed")

    def _device_scope(self):
        import contextlib

        if self.devices and len(self.devices) == 1:
            import jax

            return jax.default_device(self.devices[0])
        return contextlib.nullcontext()

    @staticmethod
    def _profile_scope(trial_id: str):
        """Per-trial XLA profiler trace when RAFIKI_PROFILE_DIR is set
        (SURVEY.md §5: "jax.profiler trace per trial"). Traces land in
        <dir>/<trial_id>/ viewable in TensorBoard / Perfetto."""
        import contextlib
        import os

        profile_dir = os.environ.get("RAFIKI_PROFILE_DIR")
        if not profile_dir:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.trace(os.path.join(profile_dir, trial_id))

    # -- the loop ------------------------------------------------------------

    def adopt_orphans_of_service(self, prev_service_id: str) -> int:
        """Resume RUNNING trials stranded by a dead predecessor worker.

        The in-job half of elastic recovery: when the scheduler restarts
        a crashed worker (scheduler/process.py supervise loop), the
        replacement CAS-adopts each trial still bound to the dead
        worker's service row — a racing periodic recovery sweep then
        loses the CAS, so every orphan is re-run exactly once — and
        re-runs it (from its newest mid-trial checkpoint when one
        exists). The predecessor already claimed these trials' budget
        slots, so the job still completes its exact trial count.
        """
        n = 0
        for t in self.store.get_trials_of_sub_train_job(self.sub_id):
            if (t["status"] != TrialStatus.RUNNING.value
                    or t.get("service_id") != prev_service_id):
                continue
            if not self.store.adopt_trial(t["id"], prev_service_id,
                                          self.service_id, self.worker_id):
                continue  # recovery sweep won the race; its re-run owns it
            self.resume_trial(t["id"])
            self.trials_run += 1
            n += 1
        return n

    def run(self) -> int:
        """Pull trials until the budget is exhausted. Returns #trials run."""
        max_trials = self.budget.get(BudgetType.MODEL_TRIAL_COUNT.value)
        budget_max = int(max_trials) if max_trials is not None else None
        packer = None
        if self.trial_pack > 1:
            packer = PackedTrialRunner(self, self.trial_pack)
            if not packer.eligible():
                packer = None  # serial loop below — packing silently off
        try:
            while not self.budget_exhausted():
                if packer is not None:
                    ran, drained = packer.run_round(budget_max)
                    self.trials_run += ran
                    if ran and self.service_id is not None:
                        self.store.update_service(self.service_id, heartbeat=True)
                    if drained:
                        break
                    continue
                with telemetry.span("trial.advisor_propose", leaf=True,
                                    worker_id=self.worker_id):
                    knobs = self.advisor.propose()
                # Slot-claim happens atomically inside the trial-row
                # insert (crash between claim and insert cannot leak a
                # budget slot); None back = budget drained, the unused
                # proposal is simply dropped.
                if self.run_trial(knobs, budget_max=budget_max) is None:
                    break
                self.trials_run += 1
                if self.service_id is not None:
                    self.store.update_service(self.service_id, heartbeat=True)
        finally:
            if self._saver is not None:
                # close() flushes first: every trial durable before we
                # return, and the saver thread actually exits (a bare
                # flush would leak one live thread per worker).
                self._saver.close()
        return self.trials_run


class PackedTrialRunner:
    """Drafts up to ``pack`` proposals per round, buckets them by
    packing key, and trains each multi-trial bucket as ONE vmapped XLA
    program (``JaxModel.train_packed``) on this worker's device.

    Every PER-TRIAL contract is preserved: store rows (one per trial,
    budget-claimed atomically at creation), scores, advisor feedback,
    TrialLog entries, params persistence and lifecycle events are
    exactly those of k serial trials — only the wall-clock is shared.
    Recovery, the predictor's top-k and the gateway therefore see no
    difference (docs/trial_packing.md).
    """

    def __init__(self, worker: "TrainWorker", pack: int):
        self.w = worker
        self.pack = max(1, int(pack))

    def eligible(self) -> bool:
        """Packing preconditions, checked once per run(): a packable
        JaxModel template, a single-device worker (the trial axis IS
        the parallelism — meshes and multihost SPMD groups must stay
        serial), and an unmasked train dataset."""
        import os

        from rafiki_tpu.model.base import JaxModel

        w = self.w
        if self.pack < 2:
            return False
        if not (isinstance(w.model_class, type)
                and issubclass(w.model_class, JaxModel)):
            return False
        if not w.model_class.packable():
            return False
        if w.devices is not None and len(w.devices) > 1:
            return False
        if int(os.environ.get("RAFIKI_NUM_PROCESSES", "1")) > 1:
            return False
        try:
            from rafiki_tpu.model.dataset import dataset_utils

            if dataset_utils.load(w.train_uri).mask is not None:
                return False
        except Exception:
            return False
        return True

    def run_round(self, budget_max: Optional[int]) -> "tuple[int, bool]":
        """One draft-bucket-train round. Returns (trials run, budget
        drained). Proposals whose packing key matches no other run
        serially; same-key groups run packed."""
        w = self.w
        with telemetry.span("trial.advisor_propose", leaf=True,
                            worker_id=w.worker_id):
            batch = getattr(w.advisor, "propose_batch", None)
            proposals = (batch(self.pack) if batch is not None
                         else [w.advisor.propose() for _ in range(self.pack)])
        buckets: Dict[Any, List[Knobs]] = {}
        order: List[Any] = []
        # One throw-away model a proposal, for its packing key.
        with telemetry.span("trial_pack.bucket", leaf=True):
            for kn in proposals:
                try:
                    m = w.model_class(**kn)
                    key = repr(m.packing_key(m._prepared_dataset(w.train_uri)))
                except Exception:
                    key = ("unpackable", id(kn))  # unique → runs serially
                if key not in buckets:
                    order.append(key)
                    buckets[key] = []
                buckets[key].append(kn)
        ran = 0
        for key in order:
            knobs_list = buckets[key]
            if len(knobs_list) == 1:
                if w.run_trial(knobs_list[0], budget_max=budget_max) is None:
                    return ran, True
                ran += 1
            else:
                n, drained = self._run_packed(knobs_list, budget_max)
                ran += n
                if drained:
                    return ran, True
        return ran, False

    def _run_packed(self, knobs_list: List[Knobs],
                    budget_max: Optional[int]) -> "tuple[int, bool]":
        w = self.w
        knob_config = w.model_class.get_knob_config()
        # Claim all rows up front (each claim is an atomic budget slot,
        # same transaction as the serial path); the pack shrinks to
        # whatever the budget still allows.
        rows: List["tuple[str, Knobs]"] = []
        drained = False
        with telemetry.span("trial.claim", leaf=True):
            for kn in knobs_list:
                trial = w.store.create_trial(
                    w.sub_id, w.model_class.__name__, kn,
                    worker_id=w.worker_id,
                    shape_sig=knob_config_signature(knob_config, kn),
                    service_id=w.service_id, budget_max=budget_max)
                if trial is None:
                    drained = True
                    break
                rows.append((trial["id"], kn))
        if not rows:
            return 0, True
        if len(rows) == 1:
            # Budget pressure shrank the pack to one: run it serially,
            # reusing the already-claimed row via the resume path.
            out = w.run_trial(rows[0][1], resume_trial_id=rows[0][0])
            return (1 if out is not None else 0), drained
        return self._train_rows(rows, budget_max, drained)

    def run_assigned(self, rows: "List[tuple[str, Knobs]]",
                     budget_max: Optional[int] = None,
                     abort=None) -> int:
        """Train an externally-claimed set of trial rows as one pack
        (the mesh scheduler's entry point — it creates rows up front
        and assigns them chip by chip). ``abort`` is a threading.Event:
        when set, the pack raises :class:`PackAborted` at the next
        epoch boundary — AFTER that epoch's checkpoints are durable —
        leaving every row RUNNING for re-packing. Returns the number
        of rows carried to completion (success or errored)."""
        if not rows:
            return 0
        n, _ = self._train_rows(list(rows), budget_max, False, abort=abort)
        return n

    def _train_rows(self, rows: "List[tuple[str, Knobs]]",
                    budget_max: Optional[int], drained: bool,
                    abort=None) -> "tuple[int, bool]":
        w = self.w
        knob_config = w.model_class.get_knob_config()
        k = len(rows)
        telemetry.observe("trial_pack.size", float(k))
        telemetry.observe("trial_pack.fill_ratio", k / float(self.pack))
        for tid, kn in rows:
            events.emit("trial_started", trial_id=tid, sub_job_id=w.sub_id,
                        model=w.model_class.__name__, worker_id=w.worker_id,
                        knobs=kn)
        models: List[BaseModel] = []
        # model_index -> condemning CurveFit; filled by kill_pred below,
        # read by on_evict (bookkeeping) and the post-train loop (skip).
        killed: Dict[int, Any] = {}
        pack_entity = f"pack:{w.worker_id}:k{k}"
        try:
            # One pack = one trace + one ledger entity: the pack's
            # compile/step/feed/checkpoint split is shared cost across
            # its k trials (docs/observability.md).
            with trace_context.trace(trace_context.new_trace_id()), \
                    telemetry.span("trial_pack.total", worker_id=w.worker_id,
                                   k=k), \
                    ledger.entity(pack_entity), w._device_scope():
                with telemetry.span("trial_pack.build", leaf=True):
                    models = [w.model_class(**kn) for _, kn in rows]

                t_pack0 = time.monotonic()
                round_walls: List[float] = []

                def heartbeat(_epoch: int) -> None:
                    # Pack-relative wall at each round boundary: the
                    # post-hoc epoch_eval journal replay (below) joins
                    # member epoch -> round position -> this wall.
                    # lint: disable=RF007 — epoch_eval wall field, already under trial_pack.total
                    round_walls.append(time.monotonic() - t_pack0)
                    # Abort lands at the epoch boundary AFTER the
                    # checkpoint sink ran, so the newest epoch of every
                    # member is durable before the pack unwinds.
                    if abort is not None and abort.is_set():
                        raise PackAborted(
                            f"pack on {w.worker_id} aborted at epoch boundary")
                    if w.service_id is not None:
                        now = time.monotonic()
                        if now - w._last_heartbeat >= w.heartbeat_min_interval_s:
                            w._last_heartbeat = now
                            w.store.update_service(w.service_id, heartbeat=True)

                def on_evict(mi: int, epoch: int, reason: str) -> None:
                    events.emit("pack_member_evicted", trial_id=rows[mi][0],
                                epoch=epoch, reason=reason,
                                worker_id=w.worker_id)
                    if reason != "killed":
                        return
                    # Early-kill bookkeeping runs HERE — before the
                    # backfill closure proposes into the freed slot —
                    # so the replacement proposal is steered by this
                    # trial's consolation feedback (the conservative
                    # predicted score; same contract as the serial
                    # EarlyKilled arm, docs/early_kill.md).
                    tid_k, kn_k = rows[mi]
                    fit = killed.get(mi)
                    pred = fit.predicted_final if fit is not None else 0.0
                    telemetry.inc("worker.trials_killed")
                    w.store.mark_trial_as_errored(
                        tid_k, f"early_killed: predicted {pred:.4f} "
                               f"at epoch {epoch}")
                    events.emit("trial_killed", trial_id=tid_k,
                                worker_id=w.worker_id, epoch=epoch,
                                predicted=pred)
                    w.curve.note_done(kn_k)
                    search_audit.note_doomed(kn_k)
                    try:
                        w.advisor.feedback(pred, kn_k)
                    except Exception:
                        pass

                kill_pred = None
                if w.curve is not None:
                    def kill_pred(mi: int, epoch: int, metrics) -> bool:
                        # Feed the live packed curve point, then ask.
                        # Same higher-is-better guard as the serial
                        # sink: loss-only packs are never killed.
                        tid_k, kn_k = rows[mi]
                        acc = (metrics or {}).get("acc")
                        if acc is None:
                            return False
                        w.curve.observe(kn_k, epoch, float(acc),
                                        trial_id=tid_k)
                        fit = w.curve.kill_verdict(kn_k, epoch,
                                                   trial_id=tid_k)
                        if fit is None:
                            return False
                        killed[mi] = fit
                        return True

                def backfill(n: int) -> List[BaseModel]:
                    """Fill freed pack slots with freshly proposed
                    trials mid-pack. Proposals whose packing_key differs
                    from the live pack's are dropped (they'd need their
                    own program; the next round picks them up via the
                    normal draft path) BEFORE any row is claimed."""
                    nonlocal drained
                    if drained or w.advisor is None:
                        return []
                    # Speculative scoring (docs/early_kill.md): feed
                    # the advisor predicted scores for pack-mates still
                    # mid-flight so this proposal doesn't draft blind
                    # next to the constant-liar floor. No-op unless
                    # RAFIKI_CURVE_SPECULATE is set.
                    if w.curve is not None:
                        w.curve.speculate_inflight(w.advisor)
                    pack_key = repr(models[0].packing_key(
                        models[0]._prepared_dataset(w.train_uri)))
                    out: List[BaseModel] = []
                    for _ in range(n):
                        try:
                            kn = w.advisor.propose()
                            m2 = w.model_class(**kn)
                            if repr(m2.packing_key(
                                    m2._prepared_dataset(w.train_uri))) != pack_key:
                                telemetry.inc("trial_pack.backfill_key_mismatch")
                                continue
                        except Exception:
                            continue
                        wal = getattr(w, "wal", None)
                        txn = None if wal is None else wal.intent(
                            "backfill", sub_id=w.sub_id,
                            knobs_hash=search_audit.knobs_hash(kn))
                        trial = w.store.create_trial(
                            w.sub_id, w.model_class.__name__, kn,
                            worker_id=w.worker_id,
                            shape_sig=knob_config_signature(knob_config, kn),
                            service_id=w.service_id, budget_max=budget_max)
                        if trial is None:
                            if txn is not None:
                                wal.commit(txn, "backfill", denied=True)
                            drained = True
                            break
                        if txn is not None:
                            wal.commit(txn, "backfill",
                                       trial_id=trial["id"])
                        rows.append((trial["id"], kn))
                        events.emit("trial_started", trial_id=trial["id"],
                                    sub_job_id=w.sub_id,
                                    model=w.model_class.__name__,
                                    worker_id=w.worker_id, knobs=kn)
                        out.append(m2)
                    return out

                # Per-epoch checkpoints for the WHOLE pack: each trial
                # gets its own serial-format checkpoint sliced out of
                # the live pack, so a killed pack resumes every member
                # independently (serially) from its newest epoch — the
                # pack itself is never serialized. Wired whenever a
                # cadence is set, and whenever a chaos plane is active
                # (the sink doubles as the worker.epoch fault site, same
                # as the serial path).
                every = w.checkpoint_every
                ckpt_sink = None
                if every > 0 or chaos.active() is not None:
                    def ckpt_sink(epoch: int, make_blobs) -> None:
                        if every > 0 and (epoch + 1) % every == 0:
                            self._save_pack_checkpoints(rows, epoch, make_blobs)
                        # AFTER the writes: a kill-at-epoch-N fault lands
                        # with every member's epoch-N snapshot durable.
                        chaos.hook("worker.epoch", key=w.worker_id)

                # Encloses the leaf phases trial_pack.init and
                # train.packed_epoch (model/base.py, ops/train.py).
                with telemetry.span("trial_pack.train"):
                    histories = w.model_class.train_packed(
                        models, w.train_uri, on_epoch=heartbeat,
                        checkpoint_sink=ckpt_sink,
                        backfill=backfill, on_evict=on_evict,
                        kill_predicate=kill_pred)
                # Numerics containment (docs/health.md): members the
                # pack evicted for divergence carry a verdict and hold
                # their params as-of the bad epoch — they must not
                # reach evaluation (a NaN score row would poison the
                # advisor's scale). Survivors evaluate as usual.
                verdicts = [getattr(m, "_health_verdict", None)
                            for m in models]
                # Killed members skip evaluation too — scoring them
                # would spend exactly the wall the kill saved.
                healthy_idx = [i for i, v in enumerate(verdicts)
                               if v is None and i not in killed]
                # Persist's only device work for this round, dispatched
                # now so that the copy of the stacked parameters runs
                # under the evaluation and is over before the next
                # round's epoch program is queued.
                w.model_class.stage_packed_dump(
                    [models[i] for i in healthy_idx])
                with telemetry.span("trial_pack.evaluate", leaf=True):
                    healthy_scores = (w.model_class.evaluate_packed(
                        [models[i] for i in healthy_idx], w.val_uri)
                        if healthy_idx else [])
                scores: List[Optional[float]] = [None] * len(models)
                for j, i in enumerate(healthy_idx):
                    scores[i] = healthy_scores[j]
        except PackAborted:
            # Supervisor-driven teardown: rows STAY RUNNING (the mesh
            # re-packs them onto surviving chips), device state is
            # released, and the abort propagates to the caller.
            for m in models:
                try:
                    m.destroy()
                except Exception:
                    pass
            raise
        except Exception:
            err = traceback.format_exc()
            for i, (tid, kn) in enumerate(rows):
                if i in killed:
                    # Already marked errored + fed back in on_evict.
                    continue
                telemetry.inc("worker.trials_errored")
                w.store.mark_trial_as_errored(tid, err)
                events.emit("trial_errored", trial_id=tid, worker_id=w.worker_id,
                            error=err.splitlines()[-1] if err else "")
                # Same floor-score contract as the serial path: the
                # advisor learns to avoid the region.
                search_audit.note_doomed(kn)
                try:
                    w.advisor.feedback(0.0, kn)
                except Exception:
                    pass
            for m in models:
                try:
                    m.destroy()
                except Exception:
                    pass
            return len(rows), drained

        # Completed packs supersede their mid-trial checkpoints the same
        # way serial trials do (_persist deletes them per trial below).
        # Per-trial bookkeeping in creation order — logs, feedback,
        # persistence — indistinguishable from k serial trials.
        for i, (tid, kn) in enumerate(rows):
            def sink(entry, _tid=tid):
                w.store.add_trial_log(_tid, entry)

            with telemetry.span("trial.log", leaf=True), logger.capture(sink):
                logger.define_plot("Training", ["loss", "acc"], x_axis="epoch")
                for pos, h in enumerate(histories[i]):
                    logger.log(**h)
                    # Position in a member's history == the round it
                    # ran at (exact for whole-pack members; backfilled
                    # members join mid-pack, so their early positions
                    # borrow the pack's early-round walls — close, and
                    # honest about being pack-relative).
                    _journal_epoch_eval(
                        tid, {"type": "values", "values": h},
                        wall_s=(round_walls[pos]
                                if pos < len(round_walls) else None),
                        packed=True)
            if i in killed:
                # Store row, doomed charge and consolation feedback all
                # happened in on_evict (pre-backfill); the epoch_eval
                # journal replay above still ran — the curve prefix is
                # exactly what `obs curves --predicted` audits a kill
                # against.
                try:
                    models[i].destroy()
                except Exception:
                    pass
                continue
            if verdicts[i] is not None:
                # Same contract as the serial DivergenceError arm:
                # ERRORED with the diagnosis, floor score to the
                # advisor, containment counted — and no persistence
                # (the params ARE the divergent state; the capsule is
                # the forensic artifact, not the params store).
                v = verdicts[i]
                telemetry.inc("worker.trials_errored")
                w.store.mark_trial_as_errored(
                    tid, f"diverged: {v.get('diagnosis')}")
                events.emit("trial_diverged", trial_id=tid,
                            worker_id=w.worker_id,
                            divergence=v.get("divergence"),
                            bad_step=v.get("bad_step"),
                            capsule=v.get("capsule"),
                            diagnosis=v.get("diagnosis"))
                _health.note_contained()
                if w.curve is not None:
                    w.curve.note_done(kn)
                search_audit.note_doomed(kn)
                try:
                    w.advisor.feedback(0.0, kn)
                except Exception:
                    pass
                try:
                    models[i].destroy()
                except Exception:
                    pass
                continue
            score = float(scores[i])
            # (A Gaussian-process advisor refits on every call.)
            with telemetry.span("trial.advisor_feedback", leaf=True):
                w.advisor.feedback(score, kn)
                if w.curve is not None:
                    w.curve.note_scored(kn, score)
            telemetry.inc("worker.trials_succeeded")
            telemetry.inc("worker.packed_trials")
            if w._saver is not None:
                w._saver.submit(tid, models[i], score, None)
            else:
                w._persist(tid, models[i], score)
        telemetry.inc("worker.packed_rounds")
        return len(rows), drained

    def _save_pack_checkpoints(self, rows, epoch: int, make_blobs) -> None:
        """Write one epoch's per-trial checkpoints for the pack, with
        the serial path's durability contract: a failed write (full
        disk, injected ``store.params_write`` fault) costs that trial's
        resumability, never the pack — training has the real state in
        device memory and must keep going."""
        w = self.w
        t0 = time.monotonic()
        try:
            blobs = make_blobs()
        except Exception:
            telemetry.inc("worker.checkpoint_write_failed")
            events.emit("checkpoint_write_failed", epoch=epoch,
                        worker_id=w.worker_id, trial_id=rows[0][0],
                        error=traceback.format_exc(limit=3))
            # lint: disable=RF007 — checkpoint_s ledger charge, not a span
            ledger.add("checkpoint_s", time.monotonic() - t0)
            return
        # make_blobs() yields (model_index, member_epoch, blob) — each
        # member's checkpoint is filed under its OWN epoch counter
        # (evicted/backfilled members drift from the pack round index).
        for mi, member_epoch, blob in blobs:
            tid = rows[mi][0]
            try:
                w.params_store.save_checkpoint(tid, member_epoch, blob)
                events.emit("checkpoint_written", trial_id=tid,
                            epoch=member_epoch, worker_id=w.worker_id)
            except Exception:
                telemetry.inc("worker.checkpoint_write_failed")
                events.emit("checkpoint_write_failed", trial_id=tid,
                            epoch=member_epoch, worker_id=w.worker_id,
                            error=traceback.format_exc(limit=3))
        # Charged to the bound pack entity (the sink runs inside it).
        # lint: disable=RF007 — checkpoint_s ledger charge, not a span
        ledger.add("checkpoint_s", time.monotonic() - t0)


def save_parameters(params_store: ParamsStore, model: BaseModel) -> str:
    """A trained model's parameters into the store, durable on return;
    the params id. Streamed where the model offers its blob in parts
    (``JaxModel.dump_parameter_parts``): one pass from the fetched
    leaves to the file, hashed while it is written, no copy of the blob.
    A model with only ``dump_parameters() -> bytes`` takes the bytes
    road. ``persist.write`` is the host's side of either."""
    offer = getattr(model, "dump_parameter_parts", None)
    parts = offer() if offer is not None else None
    if parts is None:
        telemetry.inc("persist.buffered")
        parts = (model.dump_parameters(),)
    else:
        telemetry.inc("persist.streamed")
    with telemetry.span("persist.write", leaf=True):
        return params_store.save_parts(parts)


def _round_copy_of(model: BaseModel):
    """The host copy of a finished pack round that this member's dump
    reads (``JaxModel.stage_packed_dump``), or None where the dump makes
    a device fetch of its own: a serial trial, a member detached from
    its pack, a model that is not a ``JaxModel``."""
    return getattr(getattr(model, "_loop", None), "host_copy", None)


class _AsyncSaver:
    """One background thread persisting trial parameters off the
    critical path, bounded by what a pending save keeps alive.

    A save that fetches from the device on its own keeps a parameter set
    alive there, so one such save may be pending: at most two sets at
    once (the one being written and the one training), as ever. The
    members of a finished pack round are dumped from the round's one
    host copy and do no device work, so they queue behind each other
    freely: ``submit`` blocks such a member only while a save of an
    EARLIER round (or a save of the other kind) is unwritten, so at most
    two rounds' host copies are alive: the one being written and the one
    just evaluated. Either way memory stays flat and a slow disk
    degrades to serial, never unbounded.
    """

    def __init__(self, worker: "TrainWorker"):
        import collections
        import threading

        self._worker = worker
        # Saves submitted and not yet written, oldest first: (trial_id,
        # model, score, sink, round copy). The head stays while it is
        # being written (``_writing``); None stops the thread.
        self._unwritten: "collections.deque" = collections.deque()
        self._writing = False
        self._cv = threading.Condition()
        self._thread = threading.Thread(target=self._loop,
                                        name=f"saver-{worker.worker_id}",
                                        daemon=True)
        self._thread.start()

    def _must_wait(self, round_copy) -> bool:
        if round_copy is None:
            return len(self._unwritten) - self._writing >= 1
        return any(item[4] is not round_copy for item in self._unwritten)

    def submit(self, trial_id: str, model: BaseModel, score: float,
               sink=None) -> None:
        import threading

        if not self._thread.is_alive():
            # close()d by a previous run(); restart for the new caller
            # (single-producer, so no start race).
            self._thread = threading.Thread(
                target=self._loop, name=self._thread.name, daemon=True)
            self._thread.start()
        round_copy = _round_copy_of(model)
        # Persist's share of the critical path: the caller blocked behind
        # the one pending save, or behind the round before its own.
        with telemetry.span("trial.persist_wait", leaf=True), self._cv:
            self._cv.wait_for(lambda: not self._must_wait(round_copy))
            self._unwritten.append((trial_id, model, score, sink, round_copy))
            self._cv.notify_all()

    def _loop(self) -> None:
        while self._save_next():
            pass

    def _save_next(self) -> bool:
        """Write the oldest save. A call of its own so that what it holds
        (the model, the round's host copy) goes when it returns: an idle
        saver keeps nothing alive."""
        import contextlib

        with self._cv:
            self._cv.wait_for(lambda: self._unwritten)
            item = self._unwritten[0]
            if item is None:
                self._unwritten.popleft()
                return False
            self._writing = True
            self._cv.notify_all()
        trial_id, model, score, sink, _round_copy = item
        try:
            # Re-enter the trial's log capture on this thread so
            # logger.log() calls during dump still land in TrialLog.
            scope = (logger.capture(sink) if sink is not None
                     else contextlib.nullcontext())
            with scope:
                self._worker._persist(trial_id, model, score)
        except Exception:
            # _persist already contains failures; the saver thread
            # must never die — but what it absorbs gets counted
            # (RF006: a silent swallow in a long-running loop hides
            # every failure the loop will ever have).
            telemetry.inc("worker.saver_errors")
        finally:
            try:
                model.destroy()
            # lint: disable=RF006 — a throwing user destroy() must not kill the saver; nothing to recover
            except Exception:
                pass
            with self._cv:
                self._unwritten.popleft()
                self._writing = False
                self._cv.notify_all()
        return True

    def flush(self) -> None:
        """Block until all submitted saves are durable."""
        with telemetry.span("trial.persist_wait", leaf=True), self._cv:
            self._cv.wait_for(lambda: not self._unwritten)

    def close(self) -> None:
        self.flush()
        with self._cv:
            self._unwritten.append(None)
            self._cv.notify_all()
        self._thread.join(timeout=10)


def build_worker_from_store(store: MetaStore, params_store: ParamsStore,
                            sub_train_job_id: str, advisor: AdvisorHandle,
                            worker_id: str = "worker-0", devices=None,
                            stop_event=None, async_persist: bool = True) -> TrainWorker:
    """Reconstruct a TrainWorker from meta-store rows (the entrypoint a
    subprocess worker uses, mirroring the reference's env-var-driven
    container entrypoint)."""
    sub_row = store.get_sub_train_job(sub_train_job_id)
    if sub_row is None:
        raise KeyError(f"No sub train job {sub_train_job_id!r}")
    job = store.get_train_job(sub_row["train_job_id"])
    model = store.get_model(sub_row["model_id"])
    model_cls = load_model_class(model["model_file"], model["model_class"])
    return TrainWorker(
        store, params_store, sub_train_job_id, model_cls, advisor,
        job["train_dataset_uri"], job["val_dataset_uri"], job["budget"],
        worker_id=worker_id, devices=devices, job_created_at=job["created_at"],
        stop_event=stop_event, async_persist=async_persist,
    )
