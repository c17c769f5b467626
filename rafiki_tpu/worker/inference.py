"""Inference worker: serves one trained trial.

Reference parity: rafiki/worker/inference.py (unverified — SURVEY.md
§3.2): load the trial's params, register as running in the bus, then
loop: pop a query batch from this worker's queue → model.predict →
push predictions keyed by query id.

TPU note: ``pop_queries`` drains the queue after the first query
arrives, so concurrent requests are micro-batched into one forward
pass — the device sees large batches, not query-at-a-time traffic.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, List, Optional

import numpy as np

from rafiki_tpu import chaos, telemetry
from rafiki_tpu.model.base import BaseModel
from rafiki_tpu.obs import context as trace_context
from rafiki_tpu.obs.anatomy import hops as _hops
from rafiki_tpu.obs.journal import journal as _journal
from rafiki_tpu.predictor.predictor import BATCH_KEY


class InferenceWorker:
    def __init__(self, bus, job_id: str, worker_id: str, model: BaseModel,
                 batch_size: int = 64, stop_event: Optional[threading.Event] = None,
                 extra_job_ids: Optional[List[str]] = None,
                 device: Any = None):
        self.bus = bus
        # The chip this replica serves from: its forwards run under
        # ``jax.default_device(device)``, so a model the caller loaded
        # under the same scope stays on that chip. None = jax's default
        # device (every replica of a multi-chip host on the first chip).
        self.device = device
        self.job_id = job_id
        # Co-hosted serving (docs/multitenancy.md): one worker process
        # can serve SEVERAL jobs' models behind a ProgramHost. The
        # worker registers (and heartbeats) under every co-hosted job
        # id with the SAME worker id — each job's predictor fans out to
        # the same queue, and the program tag on each query routes it.
        self.job_ids = [job_id] + [j for j in (extra_job_ids or [])
                                   if j != job_id]
        self.worker_id = worker_id
        self.model = model
        self.batch_size = batch_size
        self._stop = stop_event or threading.Event()
        # Drain contract (docs/autoscale.md): set only after the serve
        # loop exited AND the bus registration is gone — every popped
        # query has had its prediction published and the lease cannot
        # route new work here. The autoscale drain path waits on this
        # before counting the slot freed.
        self.drained = threading.Event()
        # First successful forward on this worker pays the compile; the
        # hop chain splits it out as forward_cold vs forward so a cold
        # hit cannot masquerade as a warm-path tail.
        self._warm = False

    HEARTBEAT_S = 0.5

    def stop(self) -> None:
        self._stop.set()

    def _beat(self) -> None:
        """Liveness lease refresher. A separate daemon thread, not the
        serve loop: model.predict can hold the loop for seconds (first
        forward pays the XLA compile) and the lease must stay fresh
        through it. XLA/numpy release the GIL, so this thread runs even
        mid-forward; SIGKILL stops it with the process — which is
        exactly the signal the predictor's max_age_s filter consumes."""
        while not self._stop.wait(self.HEARTBEAT_S):
            try:
                for job_id in self.job_ids:
                    self.bus.heartbeat(job_id, self.worker_id)
            except Exception:  # manager teardown mid-beat: exit quietly
                return

    def run(self) -> None:
        for job_id in self.job_ids:
            self.bus.add_worker(job_id, self.worker_id)
        threading.Thread(target=self._beat, name=f"beat-{self.worker_id}",
                         daemon=True).start()
        try:
            while not self._stop.is_set():
                items = self.bus.pop_queries(self.worker_id, max_n=self.batch_size,
                                             timeout=0.1)
                if not items:
                    continue
                # Envelopes are (qid, query) or traced (qid, query, trace)
                # — see bus/queues.py. A micro-batch can mix traces; the
                # forward span binds to the first one, and every traced
                # query gets its own journal hop so each trace stitches.
                qids = [item[0] for item in items]
                queries = [item[1] for item in items]
                traces = [item[2] if len(item) > 2 else None
                          for item in items]
                lead = next((t for t in traces if t), None)
                # Hop chains (docs/serving_anatomy.md): continue each
                # traced query's envelope marks with this worker's leg.
                # Batch-shared marks (deq/fwds/forward end) are stamped
                # once and appended to every chain in the micro-batch.
                deq = _hops.mark("deq")
                chains = [list(tr["hops"]) + [deq]
                          if tr and tr.get("hops") else None
                          for tr in traces]
                for qid, tr in zip(qids, traces):
                    if tr:
                        # lint: disable=RF014 — read by trace id, not by kind: `obs trace <id>` stitches it into the trace (tests/test_obs.py)
                        _journal.record(
                            "bus", "pop_query", query_id=qid,
                            worker_id=self.worker_id,
                            trace_id=tr.get("trace_id"),
                            parent_span=tr.get("parent_span"))
                bind = (trace_context.trace(lead.get("trace_id")) if lead
                        else contextlib.nullcontext())
                # fwds opens the forward segment BEFORE the chaos hook:
                # an injected inference.forward delay must land inside
                # the forward hop, where tail attribution can see it.
                fwds = _hops.mark("fwds")
                was_cold = not self._warm
                # Microbatch envelopes (predictor.BATCH_KEY) carry a
                # whole gateway batch as ONE query: expand them into the
                # flat forward batch, then regroup so a batch envelope
                # gets a per-query prediction LIST back while plain
                # envelopes keep their scalar reply shape.
                flat: List[Any] = []
                spans = []  # (offset, n, is_batch) per envelope
                for q in queries:
                    if isinstance(q, dict) and BATCH_KEY in q:
                        group = list(q[BATCH_KEY])
                        spans.append((len(flat), len(group), True))
                        flat.extend(group)
                    else:
                        spans.append((len(flat), 1, False))
                        flat.append(q)
                try:
                    # Chaos: a delay here is a latency spike / stuck
                    # replica (the lease stays fresh — the beat thread
                    # runs on); an error is a poisoned forward. Both
                    # exercise the gateway's quorum + breaker paths.
                    chaos.hook("inference.forward", self.worker_id)
                    with bind, telemetry.span("inference.forward",
                                              worker_id=self.worker_id):
                        flat_preds = self._predict(flat)
                    telemetry.inc("inference.queries_served", len(flat))
                    self._warm = True
                except Exception as e:  # a bad query batch must not kill the worker
                    telemetry.inc("inference.batch_errors")
                    flat_preds = [{"error": str(e)}] * len(flat)
                preds = [list(flat_preds[off:off + n]) if is_batch
                         else flat_preds[off]
                         for off, n, is_batch in spans]
                fwd_end = _hops.mark("fwdc" if was_cold else "fwd")
                for qid, pred, chain in zip(qids, preds, chains):
                    if chain is None:
                        self.bus.put_prediction(qid, self.worker_id, pred)
                    else:
                        chain.append(fwds)
                        chain.append(fwd_end)
                        chain.append(_hops.mark("reply"))
                        self.bus.put_prediction(qid, self.worker_id, pred,
                                                hops=chain)
        finally:
            for job_id in self.job_ids:
                self.bus.remove_worker(job_id, self.worker_id)
            self.drained.set()

    def _predict(self, queries: List[Any]) -> List[Any]:
        # Always the contract API: predict() owns query semantics
        # (classification probs, tag sequences, ...). JaxModel.predict
        # already batches the device forward internally, so the whole
        # popped micro-batch still runs as one XLA program.
        if self.device is None:
            return self.model.predict(queries)
        import jax

        with jax.default_device(self.device):
            return self.model.predict(queries)


def run_inference_worker_process(bus, meta_path: str, params_path: str,
                                 trial_id: str, job_id: str, worker_id: str,
                                 batch_size: int = 64) -> None:
    """Entrypoint for an inference worker as its OWN process (spawn
    target; the mp-bus proxies pickle across). Rebuilds the trial's
    model from the store — class bytes + knobs + trained params — then
    serves until killed. This is the deployment shape the reference
    gets from one-container-per-trial (SURVEY.md §3.2), and the unit
    the serve-path elasticity test SIGKILLs."""
    # FIRST, before anything touches jax: a spawned child re-imports
    # everything fresh, so an explicit CPU request must be applied here
    # too, before the first backend use (admin/app.py and
    # worker/main.py do the same).
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()

    # Observability plane: journal under RAFIKI_LOG_DIR (inherited via
    # the spawn env), adopt RAFIKI_TRACE_ID, dump a flight record on
    # fatal/SIGTERM (docs/observability.md).
    from rafiki_tpu import obs

    if obs.configure_from_env(role="infer"):
        obs.recorder.install()

    from rafiki_tpu.model.base import load_model_class
    from rafiki_tpu.store import MetaStore, ParamsStore

    store = MetaStore(meta_path)
    params_store = ParamsStore(params_path)
    trial = store.get_trial(trial_id)
    sub = store.get_sub_train_job(trial["sub_train_job_id"])
    model_row = store.get_model(sub["model_id"])
    cls = load_model_class(model_row["model_file"], model_row["model_class"])
    model = cls(**trial["knobs"])
    if trial.get("params_id"):
        model.load_parameters(params_store.load(trial["params_id"]))
    InferenceWorker(bus, job_id, worker_id, model,
                    batch_size=batch_size).run()
