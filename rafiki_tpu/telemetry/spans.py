"""Lightweight span tracer: where did this trial's wall-clock go?

``span("trial.train", trial_id=...)`` is a nestable context manager.
Nesting is tracked per thread (worker threads each carry their own
stack), so a span records its parent's name and depth — enough to
reassemble a trial's phase tree from the flat JSONL export without a
distributed-tracing dependency.

Costs: two ``time`` calls plus one locked deque append per span — spans
wrap phases (compile, epoch, persist, gather), never per-step device
work.

A phase that someone else timed and that has already ended (a stage of a
jax compile, reported by ``jax.monitoring`` with its duration) is written
with ``Tracer.record_span(name, dur_s, **tags)``: the same record, ring,
aggregates and journal, its start reckoned back from now on both clocks,
its parent the span open on the calling thread. Such a record is never a
leaf (a finished phase cannot enter the profiler) and may lie inside one.
jax reports a thousand traces of a few microseconds in one set-up (every
inner jitted function of a model, once a caller), so a phase under a
millisecond that is given a ``small`` name is not written by itself: the
span open on its thread adds it up and writes ONE record of that name,
tagged ``n``, when it closes. Nothing is dropped and the ring keeps its
room for phases.

Leaf phases and the profiler's clock: ``span(name, leaf=True)`` marks a
phase that encloses no other leaf phase on its thread. Such a span also
enters the process's *annotator* (``Tracer.install_annotator``) — in a
jax process ``jax.profiler.TraceAnnotation``, installed by
``ops/train.py`` on import, so this package stays off jax — and the
phase becomes an event on its thread's line of a running profiler
trace, stamped by the profiler itself. Enclosing spans (``trial.total``,
``trial_pack.train``, ``trial.persist``) are never bridged: a reader
that names an idle gap after the host event covering most of it would
name every gap after them. With no annotator installed a leaf span
costs one attribute test more than a plain one.

Exports:
  * per-name aggregates (count / total_s / min / max) for snapshots;
  * a bounded ring of finished span records for ``dump_jsonl`` — old
    spans fall off instead of growing the process (same philosophy as
    the bus's expired-query ring).
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from rafiki_tpu.obs import context as _trace_context
from rafiki_tpu.obs.journal import journal as _journal


class Span:
    """Context manager recording one timed, possibly-nested phase."""

    __slots__ = ("name", "tags", "leaf", "_tracer", "_t0", "_start_ts",
                 "_parent", "_span_id", "_parent_id", "_trace_id",
                 "_annotation", "_small")

    def __init__(self, tracer: "Tracer", name: str, tags: Dict[str, Any],
                 leaf: bool = False):
        self._tracer = tracer
        self.name = name
        self.tags = tags
        self.leaf = leaf
        self._annotation = None
        # name -> [count, seconds] of the finished phases under a
        # millisecond folded into this span (``Tracer.record_span``)
        self._small: Optional[Dict[str, List[float]]] = None
        self._t0 = 0.0
        self._start_ts = 0.0
        self._parent: Optional[str] = None
        self._span_id = ""
        self._parent_id: Optional[str] = None
        self._trace_id: Optional[str] = None

    @property
    def span_id(self) -> str:
        return self._span_id

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        if stack:
            self._parent, self._parent_id = stack[-1].name, stack[-1]._span_id
        self._span_id = uuid.uuid4().hex[:16]
        self._trace_id = _trace_context.current_trace_id()
        stack.append(self)
        self._start_ts = time.time()
        self._t0 = time.monotonic()
        annotator = self._tracer._annotator if self.leaf else None
        if annotator is not None:
            # Telemetry never raises into its callers: a profiler that
            # refuses an annotation costs the trace that one event.
            try:
                annotation = annotator(self.name)
                annotation.__enter__()
                self._annotation = annotation
            except Exception:
                pass
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            except Exception:
                pass
        dur = time.monotonic() - self._t0
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        for name, (n, secs) in (self._small or {}).items():
            self._tracer._record(
                name, self._start_ts + dur - secs, self._t0 + dur - secs, secs,
                self.name, self._span_id, self._trace_id, {"n": int(n)})
        self._tracer._record(
            self.name, self._start_ts, self._t0, dur, self._parent,
            self._parent_id, self._trace_id, self.tags, span_id=self._span_id,
            leaf=self.leaf, error=exc_type is not None)
        return False  # never swallow


class Tracer:
    _RECORD_CAP = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        # name -> [count, total_s, min_s, max_s]
        self._agg: Dict[str, List[float]] = {}
        self._records: "deque[Dict[str, Any]]" = deque(maxlen=self._RECORD_CAP)
        # name -> context manager; None until a module that owns a
        # profiler installs one (see the module docstring).
        self._annotator: Optional[Callable[[str], Any]] = None

    def install_annotator(self, factory: Optional[Callable[[str], Any]]) -> None:
        """``factory(name)`` returns a context manager that a leaf span
        enters after its own clock starts and leaves before it stops.
        None uninstalls."""
        self._annotator = factory

    #: a finished phase shorter than this is folded (``record_span``)
    _SMALL_S = 1e-3

    def _stack(self) -> list:
        """Per-thread stack of the open spans."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def current_span_id(self) -> Optional[str]:
        """The innermost open span's id on this thread (trace
        propagation: the bus envelope carries it as parent_span)."""
        stack = self._stack()
        return stack[-1]._span_id if stack else None

    def span(self, name: str, leaf: bool = False, **tags: Any) -> Span:
        return Span(self, name, tags, leaf)

    def record_span(self, name: str, dur_s: float,
                    small: Optional[str] = None, **tags: Any) -> None:
        """A finished phase of ``dur_s`` seconds that ends now: a span
        record like any other (module docstring). Such records may nest
        (jax traces an inner jitted function inside its caller's trace and
        reports the inner one first), so whoever sums them by name takes
        the union of their intervals on a thread, never the sum of their
        durations. With ``small`` given, a phase under a millisecond inside
        an open span is added to that span's one record of that name.
        Never raises into its caller."""
        try:
            dur = max(0.0, float(dur_s))
            stack = self._stack()
            if small is not None and dur < self._SMALL_S and stack:
                top = stack[-1]
                if top._small is None:
                    top._small = {}
                fold = top._small.setdefault(small, [0, 0.0])
                fold[0] += 1
                fold[1] += dur
                return
            parent, parent_id = ((stack[-1].name, stack[-1]._span_id)
                                 if stack else (None, None))
            # (the phase's START on both clocks, reckoned back from now)
            ts, mono = time.time(), time.monotonic()
            self._record(name, ts - dur, mono - dur, dur, parent, parent_id,
                         _trace_context.current_trace_id(), tags)
        except Exception:
            pass

    def _record(self, name: str, start_ts: float, t0: float, dur_s: float,
                parent: Optional[str], parent_id: Optional[str],
                trace_id: Optional[str], tags: Dict[str, Any],
                span_id: Optional[str] = None, leaf: bool = False,
                error: bool = False) -> None:
        span_id = span_id or uuid.uuid4().hex[:16]
        rec: Dict[str, Any] = {
            "type": "span",
            "name": name,
            "ts": start_ts,
            # ``ts`` is time.time() and may step; ``mono`` is the same
            # start on time.monotonic(), so one thread's spans can be
            # laid end to end (``mono`` + ``dur_s`` is the end).
            "mono": t0,
            "thread": threading.current_thread().name,
            "dur_s": round(dur_s, 6),
            "parent": parent,
            "span_id": span_id,
            "parent_id": parent_id,
        }
        if trace_id:
            rec["trace_id"] = trace_id
        if leaf:
            rec["leaf"] = True
        if tags:
            rec["tags"] = tags
        if error:
            rec["error"] = True
        # Durable copy first (journal has its own lock; no-op when the
        # process hasn't opted in via RAFIKI_LOG_DIR).
        _journal.record(
            "span", name, ts=start_ts,
            dur_s=rec["dur_s"], span_id=span_id,
            parent_id=parent_id, trace_id=trace_id,
            **({"tags": tags} if tags else {}),
            **({"error": True} if error else {}))
        with self._lock:
            agg = self._agg.get(name)
            if agg is None:
                self._agg[name] = [1, dur_s, dur_s, dur_s]
            else:
                agg[0] += 1
                agg[1] += dur_s
                agg[2] = min(agg[2], dur_s)
                agg[3] = max(agg[3], dur_s)
            self._records.append(rec)

    # -- reads ---------------------------------------------------------------

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "count": int(c),
                    "total_s": round(total, 6),
                    "min_s": round(mn, 6),
                    "max_s": round(mx, 6),
                }
                for name, (c, total, mn, mx) in self._agg.items()
            }

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def reset(self) -> None:
        with self._lock:
            self._agg.clear()
            self._records.clear()
