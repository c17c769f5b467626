"""The bus: per-worker query queues + per-query prediction slots.

Interface (mirrors the reference's Cache verbs, SURVEY.md §2):
  add_worker(job_id, worker_id)          — register a live worker
  get_workers(job_id, max_age_s=None)    — running-worker set
  remove_worker(job_id, worker_id)
  heartbeat(job_id, worker_id)           — refresh the liveness lease
  add_query(worker_id, query_id, query)  — predictor → worker fan-out
  pop_queries(worker_id, max_n, timeout) — worker batch pull
  put_prediction(query_id, worker_id, prediction)
  get_predictions(query_id, n, timeout)  — predictor gather-wait

Trace envelopes (docs/observability.md): when a trace context is
active (or an explicit ``trace`` dict is passed), ``add_query``
enqueues ``(query_id, query, trace)`` instead of the bare 2-tuple, and
``pop_queries`` hands the envelope through — the inference worker
re-binds the trace so its spans/journal records stitch into the same
end-to-end trace as the gateway's. Untraced messages stay 2-tuples, so
the wire format is backward compatible in both bus implementations.

Liveness: registration is a LEASE, not a fact. A SIGKILLed worker
process never runs its ``remove_worker`` cleanup (the reference has
the same hole: its Redis running-worker set outlives the container),
so each worker refreshes a heartbeat timestamp from a tiny daemon
thread and readers pass ``max_age_s`` to see only workers whose lease
is fresh — the predictor stops fanning out to (and waiting on) a dead
worker within one lease TTL. ``reap_stale(max_age_s)`` is the janitor
half: once a lease is several TTLs old the corpse's registration,
timestamp and pending-query queue are deleted outright (counted in
telemetry as ``bus.reaped_workers``), so dead ids stop accumulating.

Chaos hooks (docs/chaos.md): ``bus.add_query`` (drop/delay a fan-out
message), ``bus.put_prediction`` (drop/delay a reply) and
``bus.heartbeat`` (skip a lease refresh — how scenarios simulate a
stalled or dead worker without killing the thread), all keyed by
worker id; plus ``bus.proxy`` on the mp bus (an injected
Manager-proxy fault at the IPC round-trip, keyed by the bus verb).
All inert no-ops unless ``RAFIKI_CHAOS`` is set.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from collections import deque

from rafiki_tpu import telemetry
from rafiki_tpu.chaos import hook as _chaos
from rafiki_tpu.obs import context as _trace_context
from rafiki_tpu.obs.anatomy import hops as _hops
from rafiki_tpu.obs.journal import journal as _journal


def _current_trace() -> Optional[Dict[str, Any]]:
    """The active trace as a plain picklable envelope field (None when
    untraced — the message stays a 2-tuple)."""
    tid = _trace_context.current_trace_id()
    if tid is None:
        return None
    trace: Dict[str, Any] = {"trace_id": tid}
    parent = telemetry.current_span_id()
    if parent:
        trace["parent_span"] = parent
    # The tenant tag rides the same envelope field as the trace (and
    # the PR 6 back-compat rule: absent key = untagged, old consumers
    # ignore it) so worker-side records can attribute work per tenant.
    tenant = _trace_context.current_tenant()
    if tenant:
        trace["tenant"] = tenant
    return trace


def _envelope(query_id: str, query: Any,
              trace: Optional[Dict[str, Any]]) -> tuple:
    trace = trace or _current_trace()
    if trace is None:
        return (query_id, query)
    if "hops" not in trace:
        # Hop marks ride the envelope (docs/serving_anatomy.md): the
        # gateway's thread-local prefix (admit/queue), then the enqueue
        # mark stamped here. Copy before annotating — an explicit trace
        # arg may be a caller-owned dict shared across queries.
        trace = dict(trace)
        trace["hops"] = _hops.prefix_marks() + [_hops.mark("enq")]
    # Journal the fan-out hop so the bus appears in the stitched trace.
    # lint: disable=RF014 — read by trace id, not by kind: `obs trace <id>` prints every record of a trace (tests/test_obs.py asserts the bus hop)
    _journal.record("bus", "add_query", query_id=query_id,
                    trace_id=trace.get("trace_id"),
                    parent_span=trace.get("parent_span"))
    return (query_id, query, trace)


class InProcBus:
    _EXPIRED_CAP = 4096  # remembered timed-out query ids (leak guard)
    # Auto-janitor factor: get_workers reaps any lease older than
    # REAP_FACTOR × the caller's max_age_s on sight, so corpse queues
    # cannot grow unboundedly under worker churn even when nothing ever
    # calls reap_stale explicitly. Well above the liveness TTL: a busy
    # host starving a worker for a beat or two must not lose its queue.
    # Env override: RAFIKI_BUS_REAP_FACTOR.
    REAP_FACTOR = 6.0

    def __init__(self):
        import os

        self._reap_factor = float(
            os.environ.get("RAFIKI_BUS_REAP_FACTOR", str(self.REAP_FACTOR)))
        # Queues exist exactly while their worker is registered:
        # created in add_worker, destroyed in remove_worker, and
        # add_query drops (rather than resurrects) queries to dead
        # workers — otherwise repeated inference-job cycles would leak
        # one queue per retired worker id.
        self._queues: Dict[str, queue.Queue] = {}
        # Running total of enqueued-not-yet-popped queries. add_query
        # used to recompute it by summing qsize() over EVERY worker
        # queue under the bus lock — O(workers) on the hot path. The
        # counter can drift slightly (pop_queries drains outside the
        # lock, so a concurrent remove_worker may double-subtract);
        # it feeds a gauge and the least-loaded router, both of which
        # tolerate approximation, so we clamp at 0 rather than pay a
        # stricter protocol.
        self._depth = 0
        self._preds: Dict[str, list] = {}
        self._pred_cv = threading.Condition()
        # Plain dict, NOT defaultdict: read paths (heartbeat of a
        # removed worker, get_workers of a finished job) used to
        # materialize an empty set per probed job id — a slow leak
        # under repeated job cycles.
        self._workers: Dict[str, set] = {}
        self._worker_ts: Dict[Tuple[str, str], float] = {}
        self._expired: "deque[str]" = deque(maxlen=self._EXPIRED_CAP)
        self._expired_set: set = set()
        self._lock = threading.Lock()

    # -- worker registry -----------------------------------------------------

    def add_worker(self, job_id: str, worker_id: str) -> None:
        with self._lock:
            self._workers.setdefault(job_id, set()).add(worker_id)
            self._worker_ts[(job_id, worker_id)] = time.monotonic()
            self._queues.setdefault(worker_id, queue.Queue())

    def remove_worker(self, job_id: str, worker_id: str) -> None:
        with self._lock:
            self._workers.get(job_id, set()).discard(worker_id)
            self._worker_ts.pop((job_id, worker_id), None)
            q = self._queues.pop(worker_id, None)
            if q is not None:  # pending queries die with the queue
                self._depth = max(0, self._depth - q.qsize())

    def heartbeat(self, job_id: str, worker_id: str) -> None:
        if _chaos("bus.heartbeat", worker_id) == "skip":
            return  # injected missed beat: the lease ages as if dead
        with self._lock:
            if worker_id in self._workers.get(job_id, ()):  # never resurrect
                self._worker_ts[(job_id, worker_id)] = time.monotonic()

    def get_workers(self, job_id: str,
                    max_age_s: Optional[float] = None) -> List[str]:
        with self._lock:
            ws = self._workers.get(job_id, ())
            if max_age_s is None:
                return sorted(ws)
            # lint: disable=RF007 — lease cutoff timestamp, not a duration
            cutoff = time.monotonic() - max_age_s
            # Auto-janitor: any lease REAP_FACTOR×TTL old is a corpse
            # (a SIGKILLed worker never runs remove_worker) — reap its
            # registration, timestamp and pending-query queue on sight,
            # inline under the same lock (calling reap_stale here would
            # deadlock on the non-reentrant bus lock).
            self._reap_locked(cutoff - max_age_s * (self._reap_factor - 1.0),
                              [job_id])
            return sorted(w for w in ws
                          if self._worker_ts.get((job_id, w), 0.0) >= cutoff)

    def _reap_locked(self, cutoff: float,
                     jobs: List[str]) -> List[Tuple[str, str]]:
        """Delete registrations with leases older than ``cutoff``.
        Caller holds ``self._lock``."""
        reaped: List[Tuple[str, str]] = []
        for j in jobs:
            ws = self._workers.get(j)
            if not ws:
                continue
            for w in [w for w in ws
                      if self._worker_ts.get((j, w), 0.0) < cutoff]:
                ws.discard(w)
                # lint: disable=RF004 — caller holds self._lock (see docstring)
                self._worker_ts.pop((j, w), None)
                # lint: disable=RF004 — caller holds self._lock (see docstring)
                q = self._queues.pop(w, None)
                if q is not None:
                    self._depth = max(0, self._depth - q.qsize())
                reaped.append((j, w))
        if reaped:
            telemetry.inc("bus.reaped_workers", len(reaped))
        return reaped

    def reap_stale(self, max_age_s: float,
                   job_id: Optional[str] = None) -> List[Tuple[str, str]]:
        """Janitor: delete every registration whose lease is older than
        ``max_age_s`` — worker set entry, timestamp AND pending-query
        queue, so a SIGKILLed worker's leftovers stop accumulating.
        Callers pick max_age_s well above the liveness TTL (the
        predictor uses k×TTL): reaping is for corpses, not for workers
        a busy host merely starved for one beat. ``get_workers`` also
        runs this automatically at REAP_FACTOR× the caller's TTL."""
        # lint: disable=RF007 — lease cutoff timestamp, not a duration
        cutoff = time.monotonic() - max_age_s
        with self._lock:
            jobs = [job_id] if job_id is not None else list(self._workers)
            return self._reap_locked(cutoff, jobs)

    # -- queries -------------------------------------------------------------

    def add_query(self, worker_id: str, query_id: str, query: Any,
                  trace: Optional[Dict[str, Any]] = None) -> None:
        if _chaos("bus.add_query", worker_id) == "drop":
            telemetry.inc("bus.queries_dropped_chaos")
            return  # injected loss: the gather just sees one fewer reply
        item = _envelope(query_id, query, trace)
        with self._lock:
            q = self._queues.get(worker_id)
            if q is not None:
                q.put(item)  # unbounded Queue: put never blocks
                self._depth += 1
                depth = self._depth
        if q is not None:  # dead worker → drop; the gather just sees n-1
            telemetry.inc("bus.queries_added")
            telemetry.set_gauge("bus.queue_depth", depth)
        else:
            telemetry.inc("bus.queries_dropped_dead_worker")

    def queue_depth(self, worker_id: str) -> int:
        """Pending (unpopped) queries for one worker — the signal the
        gateway's least-loaded router keys on."""
        with self._lock:
            q = self._queues.get(worker_id)
            return q.qsize() if q is not None else 0

    def pop_queries(self, worker_id: str, max_n: int = 64,
                    timeout: float = 0.1) -> List[tuple]:
        """Block up to ``timeout`` for the first query, then drain up to
        max_n without blocking — natural micro-batching for the device.
        Items are ``(qid, query)`` or traced ``(qid, query, trace)``."""
        with self._lock:
            q = self._queues.get(worker_id)
        if q is None:  # not registered (stopped): nothing to serve
            time.sleep(min(timeout, 0.05))
            return []
        out: List[tuple] = []
        try:
            out.append(q.get(timeout=timeout))
        except queue.Empty:
            return out
        while len(out) < max_n:
            try:
                out.append(q.get_nowait())
            except queue.Empty:
                break
        with self._lock:
            self._depth = max(0, self._depth - len(out))
        telemetry.inc("bus.queries_popped", len(out))
        telemetry.observe("bus.pop_batch_size", len(out))
        return out

    # -- predictions ---------------------------------------------------------

    def put_prediction(self, query_id: str, worker_id: str, prediction: Any,
                       hops: Optional[list] = None) -> None:
        if _chaos("bus.put_prediction", worker_id) == "drop":
            return  # injected reply loss
        # Reply-leg hop carriage, back-compat like the query-leg trace
        # 3-tuple: plain replies stay (worker_id, prediction); a worker
        # with a hop chain appends it as an optional third element.
        item = ((worker_id, prediction) if hops is None
                else (worker_id, prediction, hops))
        with self._pred_cv:
            if query_id in self._expired_set:
                return  # late answer to a timed-out query: drop, don't leak
            self._preds.setdefault(query_id, []).append(item)
            self._pred_cv.notify_all()

    def get_predictions(self, query_id: str, n: int,
                        timeout: float = 10.0,
                        min_n: Optional[int] = None,
                        grace_s: Optional[float] = None) -> List[Tuple[str, Any]]:
        """Wait until n predictions arrived (or timeout); pops the slot.
        After this returns, late answers for query_id are discarded.

        Quorum gather: with ``min_n`` (and optionally ``grace_s``), the
        wait relaxes once ``min_n`` replies are in — from that moment
        at most ``grace_s`` more seconds are granted for stragglers
        before the partial set is returned. This is how the gateway
        keeps p99 tracking the median replica instead of the slowest.
        """
        deadline = time.monotonic() + timeout
        quorum = n if min_n is None else max(1, min(min_n, n))
        quorum_at: Optional[float] = None
        with self._pred_cv:
            while True:
                got = len(self._preds.get(query_id, []))
                if got >= n:
                    break
                now = time.monotonic()
                limit = deadline
                if got >= quorum:
                    if quorum_at is None:
                        quorum_at = now
                    if grace_s is not None:
                        limit = min(limit, quorum_at + grace_s)
                if now >= limit:
                    break
                self._pred_cv.wait(limit - now)
            if len(self._expired) == self._expired.maxlen:
                self._expired_set.discard(self._expired[0])
            self._expired.append(query_id)
            self._expired_set.add(query_id)
            return self._preds.pop(query_id, [])


def make_mp_bus(manager=None):
    """A multiprocessing-shared bus with the same interface.

    Built on a ``multiprocessing.Manager`` so predictor and inference
    workers can run as separate processes on the TPU host — the
    deployment shape the reference achieves with Redis.
    """
    import multiprocessing as mp

    # spawn, not fork: JAX is multithreaded and fork() can deadlock.
    manager = manager or mp.get_context("spawn").Manager()
    return _MpBus(manager)


class _LeasedLock:
    """A ``manager.Lock`` that survives its holder being SIGKILLed.

    A process killed inside a ``with lock:`` section never releases, and
    every other process then blocks in ``acquire`` forever — the whole
    serving plane wedged by one dead worker (and, in the suite, a test
    that SIGKILLs a worker hung until the run was cut). Every section of
    the bus is a handful of manager round-trips (milliseconds; the chaos
    delays sit outside the lock), so a lock still held after ``LEASE_S``
    belongs to a dead process and the waiter breaks it. Two waiters
    whose leases expire within one round-trip of each other can both
    release; the cost is one transiently unguarded copy-on-write update
    (at worst a lost beat or query, which leases and gather deadlines
    already absorb), against a permanent wedge.
    """

    LEASE_S = 1.0

    def __init__(self, lock):
        self._lock = lock

    def __enter__(self):
        while not self._lock.acquire(True, self.LEASE_S):
            telemetry.inc("bus.lock_broken")
            try:
                self._lock.release()
            except Exception:  # another waiter broke it first
                pass
        return self

    def __exit__(self, *exc):
        try:
            self._lock.release()
        except Exception:  # broken under us by a waiter that gave up
            pass


class _MpBus:
    """Cross-process bus over Manager dict/Lock proxies ONLY.

    Every shared structure is a manager.dict holding PLAIN values
    updated copy-on-write (read, rebuild, reassign under the lock) —
    no nested proxies and no manager handle needed after construction,
    so the bus object itself pickles into spawn children (the Manager
    object does not pickle; nested list/Queue proxies would force
    children to create new shared objects through it). Manager ops are
    IPC round-trips either way, so polling instead of blocking
    Queue.get costs nothing extra at this bus's scale.
    """

    _EXPIRED_CAP = 4096  # remembered gathered/timed-out query ids
    REAP_FACTOR = 6.0    # same auto-janitor contract as InProcBus
    # Poll period for pop/gather waits. This is a FLOOR under every
    # serving hop that crosses the bus (enq→deq and reply→gather): at
    # the old 5ms, a k=3 replicated fan-out paid ~2×5ms of pure polling
    # per query — most of the fanout_cost_s the stacked route exists to
    # collapse. 1ms keeps the Manager round-trip rate trivial (~1k/s
    # per idle waiter) while cutting the wire-tax floor 5×.
    _POLL_S = 0.001

    def __init__(self, manager):
        import os

        self._reap_factor = float(
            os.environ.get("RAFIKI_BUS_REAP_FACTOR", str(self.REAP_FACTOR)))
        self._manager = manager         # keepalive only; dropped on pickle
        self._queues = manager.dict()   # worker_id -> tuple of (qid, query)
        self._preds = manager.dict()    # query_id -> tuple of (worker, pred)
        self._workers = manager.dict()  # job_id -> tuple of worker ids
        self._worker_ts = manager.dict()  # "job|worker" -> epoch seconds
        self._expired = manager.dict()  # gathered/timed-out query ids
        self._expired_cap = self._EXPIRED_CAP  # instance-level for tests
        self._lock = _LeasedLock(manager.Lock())

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_manager"] = None  # children use proxies, never the manager
        return state

    @staticmethod
    def _proxy(op: str):
        """``bus.proxy`` chaos site (docs/chaos.md): an injected
        Manager-proxy fault at the start of an IPC round-trip, keyed by
        the bus verb. ``error`` raises ChaosError in the calling
        process (a dead manager / broken pipe), ``delay`` stalls the
        round-trip; the caller's own error handling — breakers, quorum
        gathers, lease expiry — must absorb it."""
        return _chaos("bus.proxy", op)

    def add_worker(self, job_id, worker_id):
        with self._lock:
            ws = self._workers.get(job_id, ())
            if worker_id not in ws:
                self._workers[job_id] = ws + (worker_id,)
            self._queues.setdefault(worker_id, ())
            # time.time(), not monotonic: leases are compared across
            # processes and wall clock is the shared clock here.
            self._worker_ts[f"{job_id}|{worker_id}"] = time.time()

    def remove_worker(self, job_id, worker_id):
        with self._lock:
            ws = self._workers.get(job_id, ())
            if worker_id in ws:
                self._workers[job_id] = tuple(w for w in ws if w != worker_id)
            self._worker_ts.pop(f"{job_id}|{worker_id}", None)
            self._queues.pop(worker_id, None)

    def heartbeat(self, job_id, worker_id):
        if _chaos("bus.heartbeat", worker_id) == "skip":
            return  # injected missed beat (chaos fires in the CALLING process)
        with self._lock:
            if worker_id in self._workers.get(job_id, ()):  # never resurrect
                self._worker_ts[f"{job_id}|{worker_id}"] = time.time()

    def get_workers(self, job_id, max_age_s=None):
        self._proxy("get_workers")
        ws = self._workers.get(job_id, ())
        if max_age_s is None:
            return sorted(ws)
        # lint: disable=RF009 — lease cutoff vs cross-process wall-clock beats, not a duration
        cutoff = time.time() - max_age_s
        ts = dict(self._worker_ts)
        # Auto-janitor (same contract as InProcBus.get_workers): the
        # stale set is computed from this read's snapshot, then reaped
        # through reap_stale — a lock-free read here, so no deadlock.
        reap_age = max_age_s * self._reap_factor
        # lint: disable=RF009 — reap cutoff vs cross-process wall-clock beats, not a duration
        if any(ts.get(f"{job_id}|{w}", 0.0) < time.time() - reap_age
               for w in ws):
            self.reap_stale(reap_age, job_id)
        return sorted(w for w in ws
                      if ts.get(f"{job_id}|{w}", 0.0) >= cutoff)

    def reap_stale(self, max_age_s, job_id=None):
        """Same janitor contract as InProcBus.reap_stale, over the
        manager proxies (copy-on-write tuple rebuild under the lock).
        The reap counter is per-process — whichever process runs the
        janitor (normally the predictor's) observes the reaps."""
        # lint: disable=RF009 — lease cutoff vs cross-process wall-clock beats, not a duration
        cutoff = time.time() - max_age_s
        reaped = []
        with self._lock:
            jobs = [job_id] if job_id is not None else list(self._workers.keys())
            ts = dict(self._worker_ts)
            for j in jobs:
                ws = self._workers.get(j, ())
                dead = tuple(w for w in ws
                             if ts.get(f"{j}|{w}", 0.0) < cutoff)
                if not dead:
                    continue
                self._workers[j] = tuple(w for w in ws if w not in dead)
                for w in dead:
                    self._worker_ts.pop(f"{j}|{w}", None)
                    self._queues.pop(w, None)
                    reaped.append((j, w))
        if reaped:
            telemetry.inc("bus.reaped_workers", len(reaped))
        return reaped

    def add_query(self, worker_id, query_id, query, trace=None):
        if _chaos("bus.add_query", worker_id) == "drop":
            telemetry.inc("bus.queries_dropped_chaos")
            return
        self._proxy("add_query")
        item = _envelope(query_id, query, trace)
        with self._lock:
            pending = self._queues.get(worker_id)
            if pending is None:  # dead worker → drop; gather sees n-1
                return
            self._queues[worker_id] = pending + (item,)

    def queue_depth(self, worker_id):
        """Pending (unpopped) queries for one worker (least-loaded
        routing signal). One proxy read; no lock needed for a gauge."""
        return len(self._queues.get(worker_id, ()))

    def pop_queries(self, worker_id, max_n=64, timeout=0.1):
        self._proxy("pop_queries")
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                pending = self._queues.get(worker_id)
                if pending:
                    self._queues[worker_id] = pending[max_n:]
                    return list(pending[:max_n])
            if pending is None:  # not registered (stopped)
                time.sleep(min(timeout, 0.05))
                return []
            if time.monotonic() >= deadline:
                return []
            time.sleep(self._POLL_S)

    def put_prediction(self, query_id, worker_id, prediction, hops=None):
        if _chaos("bus.put_prediction", worker_id) == "drop":
            return
        self._proxy("put_prediction")
        # Same optional-3rd-element reply shape as InProcBus.
        item = ((worker_id, prediction) if hops is None
                else (worker_id, prediction, hops))
        with self._lock:
            if query_id in self._expired:
                return  # late answer to a timed-out query: drop, don't leak
            self._preds[query_id] = (self._preds.get(query_id, ())
                                     + (item,))

    def get_predictions(self, query_id, n, timeout=10.0, min_n=None,
                        grace_s=None):
        """Same contract as InProcBus.get_predictions, including the
        quorum/hedge relaxation, over polling instead of a condvar."""
        deadline = time.monotonic() + timeout
        quorum = n if min_n is None else max(1, min(min_n, n))
        quorum_at = None
        while True:
            preds = self._preds.get(query_id, ())
            now = time.monotonic()
            if len(preds) >= n:
                break
            limit = deadline
            if len(preds) >= quorum:
                if quorum_at is None:
                    quorum_at = now
                if grace_s is not None:
                    limit = min(limit, quorum_at + grace_s)
            if now >= limit:
                break
            time.sleep(self._POLL_S)
        with self._lock:
            preds = self._preds.pop(query_id, ())
            self._expired[query_id] = True
            overflow = len(self._expired) - self._expired_cap
            if overflow > 0:
                # Insertion-ordered trim (manager dicts keep insert
                # order), mirroring InProcBus's deque+set pair. The old
                # coarse `.clear()` forgot EVERY expired id at once,
                # reopening the late-answer leak for all inflight
                # gathers the moment the cap was hit.
                for old in list(self._expired.keys())[:overflow]:
                    del self._expired[old]
        return list(preds)
