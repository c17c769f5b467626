"""Generic jit'd training machinery shared by all JAX model templates.

Reference contrast: in Rafiki the inner epoch/step loop lives inside
each model template's ``train()`` (TF session.run / torch .backward(),
100% of GPU time — SURVEY.md §3.1). Here the loop is first-party and
TPU-shaped:

  * one compiled XLA program per *program key* — NOT per trial. The
    compiled steps live in a :class:`Program`, cached process-wide by
    :func:`get_program`, so back-to-back trials whose traced
    computation is identical reuse the same executables with zero
    retrace/recompile (SURVEY.md §7 "compile-time vs trial throughput:
    this is where the ≥8x trials/hour target is won or lost");
  * high-churn continuous hyperparameters (learning rate, warmup
    horizon, dropout rate) are *dynamic*: they ride in the train state
    as traced f32 scalars instead of baking into the XLA program, so
    an AutoML sweep over them hits one compiled program;
  * the step is ``jax.jit`` with donated carry state, so params /
    opt-state stay resident in HBM and the host only ships batches;
  * optional within-trial data parallelism: pass a ``Mesh`` and batches
    are sharded over the ``"dp"`` axis while state is replicated — XLA
    inserts the gradient all-reduce (psum over ICI) automatically from
    the sharding annotations (no hand-written collectives needed);
  * compute dtype is bfloat16 by default (MXU-native), parameters and
    the optimizer state stay float32.
"""

from __future__ import annotations

import contextlib
import inspect
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Hashable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rafiki_tpu import telemetry
from rafiki_tpu.obs.health import DivergenceError, HealthMonitor
from rafiki_tpu.obs.health import sentinel as _sentinel

# One clock (docs/telemetry.md): this module owns jax for the train
# path, so it hands the span tracer the profiler's annotation. A leaf
# span then shows on its thread's line of a running jax.profiler trace;
# with no session open TraceAnnotation is a flag test.
telemetry.install_annotator(jax.profiler.TraceAnnotation)

# -- compile stages as span records (docs/telemetry.md) ------------------------
#
# jax times every stage of a compile and says which function it was for
# (``jax.monitoring``, from dispatch.py, pjit.py, pxla.py, compiler.py).
# Each duration event becomes a finished span record, a child of whatever
# span is open on the thread: the listeners fire on jax's slow path alone
# (a trace, a lowering, a compile); a call through an executable or a
# jitted function's C++ fast path emits nothing. Trace events NEST (an
# inner jitted function is traced inside its caller's trace and reported
# first), so a sum of ``compile.*`` seconds is the union of their intervals
# on a thread (``_union_s``), never the sum of their durations. A stage
# under a millisecond (jax re-traces hundreds of tiny inner functions in
# one set-up) is folded into the open span's one ``compile.small`` record.

_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAXPR_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _CompileWatch(threading.local):
    """A thread's view of the compile in flight."""

    #: what the persistent cache said of it (the cache's events precede the
    #: ``backend_compile_duration`` they belong to, on the same thread):
    #: the tags of the next ``compile.backend`` record
    cache: Optional[Dict[str, Any]] = None
    #: (start, end) on time.monotonic() of each stage recorded while an
    #: epoch span is open on this thread (``_compile_seconds``); else None
    stages: Optional[list] = None


_watch = _CompileWatch()


def _on_compile_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_ASKED:
        _watch.cache = {"cache_hit": False}
    elif event == _CACHE_HIT:
        _watch.cache = {"cache_hit": True}


def _on_compile_duration(event: str, secs: float, **kw: Any) -> None:
    if event == _CACHE_RETRIEVAL:
        if _watch.cache is not None:
            _watch.cache["retrieval_s"] = round(float(secs), 6)
        return
    fun = str(kw.get("fun_name", ""))
    if event == _JAXPR_TRACE:
        telemetry.record_span("compile.trace", secs, "compile.small", fun=fun)
    elif event == _JAXPR_TO_MLIR:
        telemetry.record_span("compile.lower", secs, "compile.small", fun=fun)
    elif event == _BACKEND_COMPILE:
        cache, _watch.cache = _watch.cache or {}, None
        telemetry.record_span("compile.backend", secs, "compile.small",
                              fun=fun, **cache)
    else:
        return
    if _watch.stages is not None:
        end = time.monotonic()
        _watch.stages.append((end - max(0.0, float(secs)), end))


def _union_s(intervals) -> float:
    """Seconds that the (start, end) intervals cover, each counted once."""
    total, at = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        total += max(0.0, hi - max(lo, at))
        at = max(at, hi)
    return total


@contextlib.contextmanager
def _compile_seconds(span: telemetry.Span):
    """Tags an open epoch span ``compile_s``: the seconds of the compile
    stages recorded on this thread while it was open. 0.0 in every epoch
    of a window; in a cold epoch, how much of the span was not training."""
    _watch.stages = []
    try:
        yield
    finally:
        stages, _watch.stages = _watch.stages, None
        span.tags["compile_s"] = round(_union_s(stages), 6)


# Once a process, however often this module is imported or reloaded: a
# reload runs this body again in the namespace that holds the flag, and the
# pair registered first goes on reading that namespace.
if not globals().get("_compile_listeners_registered"):
    jax.monitoring.register_event_listener(_on_compile_event)
    jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
    _compile_listeners_registered = True

Batch = Dict[str, np.ndarray]
Params = Any
# Canonical loss signature: (params, batch, rng, hyper) -> (loss, metrics).
# 3-arg (params, batch, rng) losses are auto-wrapped for compatibility.
LossFn = Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]

# Knob names that are structurally dynamic in the standard template
# path: they reach the computation only through the traced hyper dict
# (lr / warmup via the update scaling, dropout via apply), or never
# reach the trace at all (epochs = python loop count, seed = init rng).
# Model templates must not bake these into module attributes.
DYNAMIC_KNOBS = frozenset({"learning_rate", "warmup_steps", "dropout", "epochs", "seed",
                           "label_smoothing"})

# Device-side counts that ride a step's metric dict (as the health
# sentinels do): a ``count.<name>`` metric is summed over the epoch's
# steps and lands in the counter ``<name>``; a ``gauge.<name>`` metric's
# last step sets the gauge ``<name>``. Neither reaches a trial's log.
COUNT_PREFIX, GAUGE_PREFIX = "count.", "gauge."


def publish_counts(out: Dict[str, float]) -> None:
    """Pop the ``count.`` / ``gauge.`` keys of an epoch's host metrics
    into the telemetry registry."""
    for key in [k for k in out if k.startswith((COUNT_PREFIX, GAUGE_PREFIX))]:
        value = out.pop(key)
        if key.startswith(COUNT_PREFIX):
            # lint: disable=RF008 — bounded: the names a template's loss puts in its metric dict (docs/telemetry.md lists them)
            telemetry.inc(key[len(COUNT_PREFIX):], value)
        else:
            # lint: disable=RF008 — bounded: as above
            telemetry.set_gauge(key[len(GAUGE_PREFIX):], value)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       valid: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked softmax cross entropy + accuracy.

    logits: (..., C) float; labels: (...) int32, -1 = ignore;
    valid: optional (...) bool combined with the label mask.
    Returns (mean loss, mean accuracy) over unmasked elements.
    """
    mask = labels >= 0
    if valid is not None:
        mask = jnp.logical_and(mask, valid)
    labels_safe = jnp.where(mask, labels, 0)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1)
    loss = jnp.where(mask, nll, 0.0).sum() / denom
    correct = (jnp.argmax(logits, axis=-1) == labels_safe) & mask
    acc = correct.sum() / denom
    return loss, acc


def dropout(x: jnp.ndarray, rate, rng, deterministic: bool) -> jnp.ndarray:
    """Inverted dropout with a *traced* rate.

    Unlike ``flax.linen.Dropout`` (whose rate is a static module
    attribute → every distinct rate is a distinct XLA program), the
    rate here may be a traced scalar, so an AutoML sweep over dropout
    reuses one compiled program.
    """
    if deterministic or rng is None:
        return x
    rate = jnp.asarray(rate, jnp.float32)
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    scale = jnp.where(rate < 1.0, 1.0 / jnp.maximum(1.0 - rate, 1e-6), 0.0)
    return jnp.where(keep, x * scale.astype(x.dtype), jnp.zeros_like(x))


@dataclass
class _ShardingPlan:
    """Shardings for (state, batch) on an optional dp mesh."""

    mesh: Optional[Mesh]
    state_sharding: Optional[NamedSharding]
    batch_sharding: Optional[NamedSharding]

    @classmethod
    def build(cls, mesh: Optional[Mesh]) -> "_ShardingPlan":
        if mesh is None:
            return cls(None, None, None)
        return cls(
            mesh=mesh,
            state_sharding=NamedSharding(mesh, P()),           # replicated
            batch_sharding=NamedSharding(mesh, P("dp")),        # batch-sharded
        )

    def put_batch(self, batch: Batch) -> Dict[str, jax.Array]:
        if self.batch_sharding is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        if not self.batch_sharding.is_fully_addressable:
            # Mesh spans processes (multi-host dp): device_put cannot
            # target non-addressable devices; materialize only this
            # process's shards of the (identical-everywhere) batch.
            from rafiki_tpu.parallel.multihost import global_put

            return global_put(batch, self.batch_sharding)
        return {k: jax.device_put(v, self.batch_sharding) for k, v in batch.items()}

    def put_state(self, state):
        if self.state_sharding is None:
            return state
        if not self.state_sharding.is_fully_addressable:
            # Multi-host: leave host leaves alone — jit treats host
            # values as replicated, and device leaves were produced by
            # the jitted init with the right global sharding already.
            return state
        return jax.device_put(state, self.state_sharding)


def _as_hyper_loss(loss_fn: LossFn) -> LossFn:
    """Accept both (params, batch, rng) and (params, batch, rng, hyper)."""
    try:
        n = len(inspect.signature(loss_fn).parameters)
    except (TypeError, ValueError):
        n = 4
    if n >= 4:
        return loss_fn
    return lambda params, batch, rng, hyper: loss_fn(params, batch, rng)


def effective_lr(hyper: Dict[str, jnp.ndarray], step_i) -> jnp.ndarray:
    """Linear warmup to hyper["lr"] over hyper["warmup"] steps — all
    traced, so warmup horizon and peak lr never force a recompile."""
    warmup = jnp.maximum(hyper.get("warmup", jnp.float32(1.0)), 1.0)
    frac = jnp.minimum((step_i.astype(jnp.float32) + 1.0) / warmup, 1.0)
    return hyper["lr"] * frac


# Fixed scope names of the step outside the model's own modules (flax
# scopes the forward pass by module name). Metadata only: they reach the
# lowered program's locations and the profiler's operation names, never
# the arithmetic. Listed in docs/telemetry.md.
SCOPE_GATHER = "rafiki.batch_gather"
SCOPE_LOSS = "rafiki.loss"
SCOPE_OPTIMIZER = "rafiki.optimizer"
SCOPE_HEALTH = "rafiki.health"
SCOPE_EVAL_COUNT = "rafiki.eval_count"
STEP_SCOPES = (SCOPE_GATHER, SCOPE_LOSS, SCOPE_OPTIMIZER, SCOPE_HEALTH,
               SCOPE_EVAL_COUNT)


def _gather_batch(X, Y, ib) -> Dict[str, jnp.ndarray]:
    """One step's batch out of the device-resident data set."""
    with jax.named_scope(SCOPE_GATHER):
        return {"x": jnp.take(X, ib, axis=0), "y": jnp.take(Y, ib, axis=0)}


def _make_step_fns(init_fn, apply_fn, loss_fn: LossFn,
                   optimizer: optax.GradientTransformation,
                   dynamic_lr: bool, eval_count=None):
    """The single-trial step closures shared by :class:`Program` and
    :class:`PackedProgram`: (train_step, eval_step, predict, init_all).
    Pure per-trial functions — the packed path vmaps them over a
    leading trial axis instead of re-deriving the math.

    ``eval_count(params, batch) -> (correct, counted)``, where a template
    gives one, is the evaluation's step in place of an argmax over
    ``apply_fn``'s whole logits (a language model counts a block of the
    sequence at a time: its logits never exist whole)."""
    loss4 = _as_hyper_loss(loss_fn)

    def train_step(state, batch):
        params, opt_state, step_i, rng, hyper = state
        batch = dict(batch)
        poison = batch.pop("_health_poison", None)
        if poison is not None and getattr(poison, "ndim", 0):
            # dp-mesh batches carry the poison as a batch-length column
            # (a rank-0 leaf cannot satisfy the P("dp") batch-sharding
            # prefix); every element is the same step multiplier.
            poison = poison[0]
        rng, sub = jax.random.split(rng)
        with jax.named_scope(SCOPE_LOSS):
            (loss, metrics), grads = jax.value_and_grad(loss4, has_aux=True)(
                params, batch, sub, hyper)
        if poison is not None:
            # Chaos ``train.nan`` carrier (docs/chaos.md): the poison is
            # a per-step f32 multiplier, 1.0 everywhere except the
            # target step (NaN). Multiply-by-1.0 is IEEE bit-exact, so
            # unpoisoned steps — and unpoisoned pack members, whose
            # whole column is ones — stay bit-identical to a clean run.
            grads = jax.tree.map(lambda g: g * poison.astype(g.dtype), grads)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            if dynamic_lr:
                lr = effective_lr(hyper, step_i)
                updates = jax.tree.map(
                    lambda u: (-lr).astype(u.dtype) * u, updates)
            params = optax.apply_updates(params, updates)
        # Health sentinels ride the metric dict as device scalars —
        # unconditionally, so every cached program shares one trace and
        # one metric structure; they read the step's intermediates but
        # never touch the rng chain or the update math (bit-neutral).
        with jax.named_scope(SCOPE_HEALTH):
            health = _sentinel.bundle(loss, grads, updates, params)
        metrics = dict(metrics, loss=loss, **health)
        return (params, opt_state, step_i + 1, rng, hyper), metrics

    def eval_step(params, batch):
        if eval_count is not None:
            with jax.named_scope(SCOPE_EVAL_COUNT):
                return eval_count(params, batch)
        # The barrier (an identity) keeps XLA from fusing the forward's
        # tail with the argmax below. On the v5e (libtpu 0.0.34) that
        # fusion is miscompiled when this step is vmapped over a pack
        # whose width is a multiple of 8 at batch >= 256: members 0-3 of
        # every 8 score as if they always answered class 0, while the
        # same logits copied to the host are right (PERF.md, PR 22).
        logits = jax.lax.optimization_barrier(apply_fn(params, batch))
        labels = batch["y"]
        mask = labels >= 0
        if "valid" in batch:
            v = batch["valid"]
            mask = jnp.logical_and(mask, v.reshape(v.shape + (1,) * (mask.ndim - v.ndim)))
        with jax.named_scope(SCOPE_EVAL_COUNT):
            labels_safe = jnp.where(mask, labels, 0)
            correct = (jnp.argmax(logits, axis=-1) == labels_safe) & mask
            return correct.sum(), mask.sum()

    def predict(params, batch):
        logits = apply_fn(params, batch)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    def init_all(rng):
        params = init_fn(rng)
        return params, optimizer.init(params)

    return train_step, eval_step, predict, init_all


class Program:
    """The compiled, trial-independent half of a training loop.

    Holds the jit'd init / train / eval / predict callables plus the
    optimizer and sharding plan. A Program is safe to share across
    trials (and across worker threads) whose traced computation is
    identical: per-trial state (params, opt state, rng, hyper scalars)
    lives in :class:`TrainLoop`, never here.

    Two lr modes:
      * ``dynamic_lr=True`` (standard template path): ``optimizer`` is
        lr-free (e.g. ``optax.scale_by_adam()``); the step scales
        updates by ``-effective_lr(hyper, step)``. Trials differing in
        lr / warmup share this Program.
      * ``dynamic_lr=False`` (custom ``make_optimizer`` overrides): the
        optimizer carries its own lr; reuse requires identical knobs.
    """

    def __init__(self, init_fn, apply_fn, loss_fn: LossFn,
                 optimizer: optax.GradientTransformation,
                 plan: _ShardingPlan, dynamic_lr: bool = True,
                 eval_count=None):
        self.plan = plan
        self.optimizer = optimizer
        self.dynamic_lr = dynamic_lr
        self.apply_fn = apply_fn
        train_step, eval_step, predict, init_all = _make_step_fns(
            init_fn, apply_fn, loss_fn, optimizer, dynamic_lr, eval_count)

        # Whole-epoch programs over a DEVICE-RESIDENT dataset (single-
        # device path): one lax.scan per epoch, per-step batches
        # gathered on device from shuffled indices — the host ships
        # only the permutation, not n_steps batches. Over a slow
        # host<->device link the per-step feed dominates the step
        # itself; on real hardware this still removes n_steps dispatch
        # round-trips per epoch.
        def train_epoch(state, X, Y, idx, poison=None):
            # ``poison`` is the optional (n_steps,) chaos train.nan
            # column; None (a leafless scan xs node) and array calls
            # are two separate traces of one Program, so clean runs
            # never carry the poison multiply.
            def body(st, xs):
                ib, pz = xs
                batch = _gather_batch(X, Y, ib)
                if pz is not None:
                    batch["_health_poison"] = pz
                return train_step(st, batch)

            state, ms = jax.lax.scan(body, state, (idx, poison))
            # Final-step metrics are the epoch result (parity with the
            # python-loop path); the health series reduces on-device to
            # its epoch-boundary summary (docs/health.md).
            rest, health = _sentinel.split(ms)
            out = {k: v.sum() if k.startswith(COUNT_PREFIX) else v[-1]
                   for k, v in rest.items()}
            with jax.named_scope(SCOPE_HEALTH):
                out.update(_sentinel.reduce_epoch(health))
            return state, out

        def eval_epoch(params, X, Y, idx):
            def body(carry, ib):
                batch = _gather_batch(X, Y, ib)
                c, n = eval_step(params, batch)
                return (carry[0] + c, carry[1] + n), None

            zero = jnp.zeros((), jnp.int32)
            (c, n), _ = jax.lax.scan(body, (zero, zero), idx)
            return c, n

        tkw: Dict[str, Any] = {}
        ekw: Dict[str, Any] = {}
        ikw: Dict[str, Any] = {}
        if plan.mesh is not None:
            tkw = dict(in_shardings=(plan.state_sharding, plan.batch_sharding),
                       out_shardings=(plan.state_sharding, plan.state_sharding))
            ekw = dict(in_shardings=(plan.state_sharding, plan.batch_sharding))
            ikw = dict(out_shardings=plan.state_sharding)
        self.train_step = jax.jit(train_step, donate_argnums=(0,), **tkw)
        self.eval_step = jax.jit(eval_step, **ekw)
        self.predict = jax.jit(predict, **ekw)
        self.init = jax.jit(init_all, **ikw)
        self.train_epoch = jax.jit(train_epoch, donate_argnums=(0,))
        self.eval_epoch = jax.jit(eval_epoch)
        self.compiled_steps: Dict[Hashable, Any] = {}
        self._compiled_lock = threading.Lock()

    def compiled_step(self, state, batch):
        """``train_step`` compiled ahead of time for arguments of these
        shapes: built once a Program (every trial that shares it calls the
        same executable), and kept, so that its cost analysis and its text
        can be read without building it again."""
        leaves, tree = jax.tree.flatten((state, batch))
        key = (tree, tuple((np.shape(a), str(a.dtype)) for a in leaves))
        with self._compiled_lock:
            if key not in self.compiled_steps:
                self.compiled_steps[key] = self.train_step.lower(state, batch).compile()
            return self.compiled_steps[key]


# ---------------------------------------------------------------------------
# Process-wide program cache
# ---------------------------------------------------------------------------
#
# Key insight for AutoML throughput: a worker process runs many trials
# back to back; without reuse, every trial pays a full XLA retrace +
# recompile (measured ~13s for VGG16 on a v5e chip vs ~1.2s of actual
# training). The cache below makes the second same-key trial free.
#
# Granularity note: the per-key lock deduplicates *Program
# construction* (the traced-closure objects); the XLA executables
# inside compile lazily at each jitted callable's first call per
# (shape, device) signature. That is the right granularity here:
# LocalScheduler's concurrent worker threads run on *different*
# devices, whose executables are necessarily distinct compiles, while
# same-device repeat trials (the steady state) hit the jit cache.
# Cross-process dedup is the persistent XLA compilation cache's job
# (utils.backend.enable_compilation_cache).
#
# The cache is capped (LRU): a long sweep over shape-affecting knobs
# evicts the oldest programs instead of pinning every compiled
# executable for the process lifetime. Live TrainLoops keep their
# Program via their own reference, so eviction is always safe.

_PROGRAM_CACHE_CAP = 64

_programs: "Dict[Hashable, Program]" = {}  # insertion-ordered → LRU via re-insert
_build_locks: Dict[Hashable, threading.Lock] = {}
# last_miss_ts (epoch seconds, comparable to the meta store's trial
# timestamps) lets the bench separate trials that ran entirely after
# the final cold compile — the honest steady-state population.
_stats = {"hits": 0, "misses": 0, "evictions": 0, "last_miss_ts": 0.0}
_guard = threading.Lock()


def mesh_cache_key(mesh: Optional[Mesh]) -> Hashable:
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(str(d) for d in mesh.devices.flat))


def get_program(key: Hashable, builder: Callable[[], Program]) -> Program:
    """Return the cached Program for ``key``, building it (once, even
    under concurrent callers) if absent.

    Contract: ``key`` must fully determine the builder's inputs
    (init/apply/loss closures, optimizer, sharding plan) — on a hit the
    caller's builder is IGNORED in favor of the cached Program. The
    JaxModel path guarantees this by keying every knob that can reach
    the trace; direct callers must do the same.
    """
    with _guard:
        prog = _programs.get(key)
        if prog is not None:
            _programs[key] = _programs.pop(key)  # refresh LRU position
            _stats["hits"] += 1
            telemetry.inc("program_cache.hits")
            return prog
        lock = _build_locks.setdefault(key, threading.Lock())
    with lock:
        with _guard:
            prog = _programs.get(key)
            if prog is not None:
                _stats["hits"] += 1
                telemetry.inc("program_cache.hits")
                return prog
        try:
            with telemetry.span("program.build"):
                prog = builder()
        except BaseException:
            # Drop the build lock entry when the builder raises (e.g. a
            # knob combo whose trace fails) — _build_locks must not
            # outgrow the LRU-capped _programs.
            with _guard:
                _build_locks.pop(key, None)
            raise
        with _guard:
            # Publish and retire the build lock atomically: popping the
            # lock before publishing would let a concurrent caller
            # install a fresh lock and build a duplicate.
            _programs[key] = prog
            _stats["misses"] += 1
            _stats["last_miss_ts"] = time.time()
            _build_locks.pop(key, None)
            evicted = 0
            while len(_programs) > _PROGRAM_CACHE_CAP:
                _programs.pop(next(iter(_programs)))
                _stats["evictions"] += 1
                evicted += 1
        telemetry.inc("program_cache.misses")
        if evicted:
            telemetry.inc("program_cache.evictions", evicted)
    return prog


def program_cache_stats() -> Dict[str, int]:
    with _guard:
        return dict(_stats, size=len(_programs))


# The cache's lifetime stats surface through the telemetry registry
# too: /metrics and BENCH snapshots see hit/miss/eviction/size without
# a second bookkeeping path (the counters above cover deltas; this
# collector is the authoritative absolute view, reset-proof).
telemetry.register_collector("program_cache", program_cache_stats)


def clear_program_cache() -> None:
    with _guard:
        _programs.clear()
        _build_locks.clear()
        _stats.update(hits=0, misses=0, evictions=0, last_miss_ts=0.0)


# ---------------------------------------------------------------------------
# Device-resident datasets
# ---------------------------------------------------------------------------
#
# The epoch-scan fast path wants the whole dataset in HBM. Device
# copies are cached ON the (host-side, LRU-cached) Dataset object, so
# their lifetime follows the dataset cache's: trials of one job reuse
# one upload, and eviction of the host dataset frees the device
# arrays. NOTE this only amortizes when callers pass the SAME Dataset
# object across trials — JaxModel guarantees it for identity
# preprocess (see _prepared_dataset); a knob-dependent custom
# preprocess re-uploads per call by design.

_DEVICE_DATASET_MAX_MB_ENV = "RAFIKI_DEVICE_DATASET_MAX_MB"
_DEVICE_DATASET_MAX_MB_DEFAULT = 2048


def device_dataset_cap_bytes() -> int:
    import os

    return int(float(os.environ.get(_DEVICE_DATASET_MAX_MB_ENV,
                                    _DEVICE_DATASET_MAX_MB_DEFAULT)) * 1e6)


def _default_device_key():
    dev = getattr(jax.config, "jax_default_device", None)
    return dev if dev is not None else jax.devices()[0]


def get_device_dataset(dataset) -> Tuple[jax.Array, jax.Array]:
    """The dataset's (x, y) as device arrays, cached per target device.

    setdefault keeps concurrent first-touchers (worker threads on
    different devices sharing one LRU-cached dataset) from replacing
    each other's cache dict; a same-device double upload is a benign
    last-writer-wins."""
    cache = dataset.__dict__.setdefault("_device_arrays", {})
    key = _default_device_key()
    if key not in cache:
        # A miss is set-up's (a plain span: it happens inside the leaf
        # phase that first touches the data set); a hit records nothing.
        with telemetry.span("data.upload",
                            bytes=int(dataset.x.nbytes + dataset.y.nbytes)):
            cache[key] = (jnp.asarray(dataset.x), jnp.asarray(dataset.y))
    return cache[key]


# ---------------------------------------------------------------------------
# TrainLoop: per-trial state driving a (possibly shared) Program
# ---------------------------------------------------------------------------


class TrainLoop:
    """Drives epochs of jit'd steps over a Dataset for one trial.

    Parameters
    ----------
    init_fn: rng -> params
    apply_fn: (params, batch) -> logits
    loss_fn: (params, batch, rng[, hyper]) -> (loss, metrics dict)
    optimizer: optax transform. With ``hyper`` containing "lr" this
        must be lr-free (default: ``optax.scale_by_adam()``); without
        hyper it is a complete optimizer (default: adam(1e-3)).
    mesh: optional dp Mesh (within-trial data parallelism). With a mesh
        of k devices the global batch is sharded k ways; gradients are
        all-reduced over ICI by XLA (from sharding annotations).
    hyper: optional dict of dynamic f32 scalars carried in the state
        ("lr", "warmup", "dropout", ...). These are traced, so trials
        differing only in them share one compiled program.
    program_key: optional hashable. When given, the compiled Program is
        fetched from / stored in the process-wide cache under
        (program_key, mesh) — the compile-amortization path.
    epoch_program: whether an epoch over a data set that fits the device
        runs as one program (a scan over its steps). False runs it step by
        step: ONE compiled step whatever the train set's length.
    initial_state: optional full (params, opt_state, step, rng, hyper)
        tuple to adopt INSTEAD of running init — the detached-member
        path: a trial evicted from a pack mid-sweep continues (or just
        evaluates/serves) through an ordinary serial loop holding the
        state sliced out of the stacked pack.
    """

    def __init__(self, init_fn, apply_fn, loss_fn, optimizer=None,
                 mesh: Optional[Mesh] = None, seed: int = 0,
                 hyper: Optional[Dict[str, float]] = None,
                 program_key: Optional[Hashable] = None,
                 initial_state=None, eval_count=None,
                 epoch_program: bool = True):
        dynamic_lr = hyper is not None and "lr" in hyper
        # False: epochs run step by step even over a device-resident data
        # set (``JaxModel.epoch_program``: templates whose step takes seconds).
        self.epoch_program = bool(epoch_program)
        if optimizer is None:
            optimizer = optax.scale_by_adam() if dynamic_lr else optax.adam(1e-3)

        def build() -> Program:
            return Program(init_fn, apply_fn, loss_fn, optimizer,
                           _ShardingPlan.build(mesh), dynamic_lr=dynamic_lr,
                           eval_count=eval_count)

        if program_key is not None:
            self._perf_key = (program_key, mesh_cache_key(mesh), dynamic_lr)
            self.program = get_program(self._perf_key, build)
        else:
            self._perf_key = ("serial", "anon", id(self))
            self.program = build()
        self.plan = self.program.plan
        self.apply_fn = apply_fn
        self.optimizer = self.program.optimizer
        # Numerics health plane (docs/health.md): consumes the in-graph
        # sentinel scalars at each epoch boundary; serial loops fail
        # fast (DivergenceError) on divergence.
        self.health = HealthMonitor(str(self._perf_key))

        if initial_state is not None:
            self.state = self.plan.put_state(initial_state)
            return
        # The host's side of a serial trial's initialisation (the packed
        # lane's ``trial_pack.init`` is this and more): tracing, building
        # or loading and enqueueing the init program. Nothing waits for
        # the device here: its side is inside the first step's wait.
        with telemetry.span("train.init", leaf=True):
            hyper_dev = {k: jnp.float32(v) for k, v in (hyper or {}).items()}
            rng = jax.random.PRNGKey(seed)
            rng, init_rng = jax.random.split(rng)
            params, opt_state = self.program.init(init_rng)
            self.state = self.plan.put_state(
                (params, opt_state, jnp.zeros((), jnp.int32), rng, hyper_dev))

    @property
    def params(self):
        return self.state[0]

    @params.setter
    def params(self, params):
        _, opt_state, step, rng, hyper = self.state
        self.state = (self.plan.put_state(params), opt_state, step, rng, hyper)

    @property
    def hyper(self) -> Dict[str, jax.Array]:
        return self.state[4]

    #: a dispatched host copy of the parameters (``release_to_host``), or None
    host_copy = None

    def state_bytes(self) -> int:
        """Bytes of the train state on the device."""
        return sum(int(x.nbytes) for x in jax.tree.leaves(self.state))

    def release_to_host(self, cast_f32_to_bf16: bool) -> None:
        """For a trial that is trained and scored: dispatch ONE
        device-to-host copy of the parameters, cast to what a dump
        stores, and let the device state go. What stays on the device is
        the cast copy until ``host_copy.fetch`` has it (a sixth of a
        float32 Adam state), so the next trial's state fits beside a dump
        in flight where two states would not. The loop can dump after
        this (``JaxModel.dump_parameters`` reads ``host_copy``) and
        nothing else."""
        from rafiki_tpu.utils.serial import StackedHostCopy

        self.host_copy = StackedHostCopy(self.state[0], cast_f32_to_bf16)
        self.state = None

    def _fits_device_fast_path(self, dataset) -> bool:
        """Single-device x/y datasets small enough to live in HBM run
        as one lax.scan per epoch over a device-resident copy."""
        return (self.plan.mesh is None
                and getattr(dataset, "mask", None) is None
                and dataset.x.nbytes + dataset.y.nbytes <= device_dataset_cap_bytes())

    def run_epoch(self, dataset, batch_size: int, epoch_seed: int,
                  on_metrics: Optional[Callable[[int, Dict[str, float]], None]] = None) -> Dict[str, float]:
        if dataset.size < batch_size:
            raise ValueError(
                f"Dataset has {dataset.size} examples < batch_size={batch_size}; "
                f"the epoch would run zero steps")
        if self.plan.mesh is not None:
            # Chaos site for collective streams: every epoch of a dp
            # (possibly multi-process) run passes through here, so a
            # kill keyed to a follower process lands while its peers
            # are inside (or about to enter) the epoch's all-reduces —
            # the distributed-training failure mode the scheduler's
            # whole-group teardown exists for. Keyed by process index
            # AND worker id (the id carries the -rN restart suffix, so
            # `unless=-r` scopes a kill to the first incarnation).
            import os as _os

            from rafiki_tpu import chaos as _chaos

            _chaos.hook("collective.step",
                        key=f"p{jax.process_index()}:"
                            f"{_os.environ.get('RAFIKI_WORKER_ID', '')}")
        fast = (on_metrics is None and self.epoch_program
                and self._fits_device_fast_path(dataset))
        # Pre-epoch host snapshot for the replay capsule: the epoch
        # program donates its input buffers, so the "state before the
        # bad epoch" must be banked BEFORE dispatch — and before the
        # timer, so the copy never pollutes step_s or the perf
        # sentinel's step-time distribution. No-op when capsules are
        # off, and skipped on the python path (no index matrix there,
        # so no replayable capsule to bank state for).
        with telemetry.span("train.health_snapshot", leaf=True):
            snap = self.health.snapshot_state(self.state) if fast else None
        t_epoch = time.monotonic()
        # Chaos site INSIDE the timed region (unlike collective.step
        # above): an injected delay here inflates the measured epoch
        # wall, which is exactly what the perf sentinel's anomaly
        # detector watches — tests/test_perf.py drives it through this site.
        from rafiki_tpu import chaos as _chaos

        _chaos.hook("train.epoch", key=str(self._perf_key))
        n_steps = dataset.size // batch_size
        poison = self._chaos_poison(n_steps)
        if fast:
            X, Y = get_device_dataset(dataset)
            perm = np.random.default_rng(epoch_seed).permutation(dataset.size)
            idx = perm[: n_steps * batch_size].reshape(
                n_steps, batch_size).astype(np.int32)
            cold = not getattr(self, "_warm", False)
            if cold:
                from rafiki_tpu.obs.perf import profiler as _profiler

                _profiler.capture_cost(self._perf_key,
                                       self.program.train_epoch,
                                       self.state, X, Y, idx, poison)
            # The epoch as the host waits for it, dispatch to metrics on
            # the host: the serial lane's twin of ``train.packed_epoch``.
            with telemetry.span("train.epoch", leaf=True, cold=cold,
                                steps=n_steps) as sp, _compile_seconds(sp):
                self.state, metrics = self.program.train_epoch(
                    self.state, X, Y, idx, poison)
                out = {k: float(v) for k, v in jax.device_get(metrics).items()}
            publish_counts(out)
            self._record_epoch(t_epoch, feed_s=0.0)
            self._health_check(out, t_epoch, epoch_seed, idx, poison, snap)
            return out
        count = 0
        metrics = None
        feed_s = 0.0
        health_steps = []
        # One-slot prefetch (double buffering): batch i+1's host→device
        # put is issued right after step i is DISPATCHED — jit dispatch
        # is async, so the transfer overlaps the device step instead of
        # serializing with it (on datasets that miss the device-resident
        # path the feed stops adding to the step).
        batches = dataset.batches(batch_size, shuffle=True, seed=epoch_seed,
                                  drop_remainder=True)

        def put_next():
            nonlocal feed_s
            batch = next(batches, None)
            if batch is None:
                return None
            batch.pop("valid", None)
            t_feed = time.monotonic()
            dev = self.plan.put_batch(batch)
            # lint: disable=RF007 — feed_s accumulator for the ledger split
            feed_s += time.monotonic() - t_feed
            return dev

        dev_batch = put_next()
        cold = not getattr(self, "_warm", False)
        step = self.program.train_step
        steps = []      # each step's metric dict, device scalars
        with telemetry.span("train.epoch", leaf=True, cold=cold,
                            steps=n_steps) as sp, _compile_seconds(sp):
            while dev_batch is not None:
                if poison is not None and count < n_steps:
                    pz = jnp.float32(poison[count])
                    if self.plan.mesh is not None:
                        # The dp batch sharding is a rank-≥1 prefix; ship the
                        # step multiplier as a batch-length column it can
                        # shard (train_step reads one element back out).
                        pz = jnp.full((batch_size,), pz, jnp.float32)
                    dev_batch = dict(dev_batch, _health_poison=pz)
                if count == 0:
                    step = self._step_callable(dev_batch, cold)
                self.state, metrics = step(self.state, dev_batch)
                # Device scalars kept as they are: the per-step series comes
                # to the host ONCE, at the epoch boundary.
                steps.append(metrics)
                dev_batch = put_next()  # overlaps the in-flight step
                if on_metrics is not None and (count % 50 == 0):
                    on_metrics(count, {k: float(v) for k, v in metrics.items()
                                       if not k.startswith(_sentinel.PREFIX)})
                count += 1
            # One host sync an epoch: every step's scalars in one fetch, and
            # the reductions below in numpy (no device program whose shape
            # is the number of steps).
            steps = jax.device_get(steps)
        # Final-step metrics are the epoch result; counts are summed over
        # the steps, as the epoch program sums them.
        out = {k: (float(sum(st[k] for st in steps)) if k.startswith(COUNT_PREFIX)
                   else float(v))
               for k, v in steps[-1].items()
               if not k.startswith(_sentinel.PREFIX)} if count else {}
        publish_counts(out)
        self._record_epoch(t_epoch, feed_s)
        if count:
            series = {k: np.stack([st[k] for st in steps])
                      for k in steps[0] if k.startswith(_sentinel.PREFIX)}
            out.update({k: float(v) for k, v
                        in _sentinel.reduce_epoch(series).items()})
            # No index matrix on this path -> detection and containment
            # only; the monitor skips the replay capsule.
            self._health_check(out, t_epoch, epoch_seed, None, poison, None)
        return out

    def _step_callable(self, dev_batch, cold: bool):
        """What the step-by-step path calls, and (``cold``) the profiler's
        cost capture of it. A loop that runs step by step by choice
        (``epoch_program`` false: a step of seconds, a compile of minutes)
        calls ONE executable built ahead of time, which the cost capture
        reads too: the jitted step and an ahead-of-time compile of it are
        two compiles wherever the persistent cache cannot hold the
        executable (measured on a v5e: 96 s, twice). By fallback (a mesh, a
        mask, ``on_metrics``) it is the jitted step, as ever."""
        from rafiki_tpu.obs.perf import profiler as _profiler

        if self.epoch_program:
            if cold:
                _profiler.capture_cost(self._perf_key, self.program.train_step,
                                       self.state, dev_batch)
            return self.program.train_step
        exe = self.program.compiled_step(self.state, dev_batch)
        if cold:
            _profiler.capture_cost(self._perf_key, self.program.train_step, compiled=exe)
        return exe

    def _chaos_poison(self, n_steps: int) -> np.ndarray:
        """Chaos site ``train.nan``: when an active plane arms it for
        this loop's key, corrupt ONE step's gradients (step
        ``n_steps // 2``) via a per-step poison multiplier column
        (docs/chaos.md). The column is ALWAYS present (all-ones when
        quiet): multiplying grads by a runtime operand changes XLA's
        fusion of the surrounding reductions, so a poison-free trace
        would NOT be bit-identical to the 1.0-multiplier trace. One
        uniform trace keeps clean epochs, faulted-run survivors, and
        capsule replays all in the same program — the bit-parity the
        health plane's replay verification depends on."""
        from rafiki_tpu import chaos as _chaos

        poison = np.ones(n_steps, np.float32)
        if (_chaos.active() is not None
                and _chaos.hook("train.nan",
                                key=str(self._perf_key)) is not None):
            poison[n_steps // 2] = np.nan
        return poison

    def _health_check(self, out: Dict[str, float], t0: float,
                      epoch_seed: int, idx, poison, snapshot) -> None:
        """Epoch-boundary health gate: strip the sentinel keys from the
        caller-visible metric dict (the JaxModel/logger contract
        predates the health plane) and fail the trial fast on a
        divergence verdict."""
        health = {k: out.pop(k) for k in list(out)
                  if k.startswith(_sentinel.PREFIX)}
        verdict = self.health.observe(health, t0=t0, epoch_seed=epoch_seed,
                                      idx=idx, poison=poison,
                                      snapshot=snapshot)
        if verdict is not None:
            raise DivergenceError(verdict)

    def _record_epoch(self, t0: float, feed_s: float) -> None:
        """Compile-vs-step-vs-feed attribution at epoch granularity: the
        first epoch of a TrainLoop pays the XLA compile (or the program-
        cache hit), so its wall-clock lands in a separate histogram
        instead of polluting the steady-state distribution.

        The same split feeds the goodput ledger (docs/observability.md):
        a cold epoch's non-feed wall is billed as compile (it contains
        the program build), warm epochs as productive step time."""
        from rafiki_tpu.obs.ledger import ledger

        # lint: disable=RF007 — epoch wall split into ledger buckets
        dt = time.monotonic() - t0
        cold = not getattr(self, "_warm", False)
        self._warm = True
        telemetry.observe("train.cold_epoch_s" if cold else "train.epoch_s", dt)
        if feed_s > 0.0:
            ledger.add("feed_s", feed_s)
        ledger.add("compile_s" if cold else "step_s", max(dt - feed_s, 0.0))
        # Perf sentinel: step sampling + EWMA/MAD anomaly detection per
        # program, and an SLO evaluation tick (both cheap when idle).
        from rafiki_tpu.obs.perf import profiler, slo

        profiler.note_epoch(self._perf_key, dt, feed_s=feed_s, cold=cold)
        slo.maybe_tick()

    def evaluate(self, dataset, batch_size: int) -> float:
        total_correct = jnp.zeros((), jnp.int32)
        total = jnp.zeros((), jnp.int32)
        start = 0
        if self._fits_device_fast_path(dataset) and dataset.size >= batch_size:
            # Full batches in one device-side scan; the remainder falls
            # through to the per-batch path below.
            X, Y = get_device_dataset(dataset)
            n_steps = dataset.size // batch_size
            idx = np.arange(n_steps * batch_size, dtype=np.int32).reshape(
                n_steps, batch_size)
            c, n = self.program.eval_epoch(self.state[0], X, Y, idx)
            total_correct, total = total_correct + c, total + n
            start = n_steps * batch_size
        # (correct, valid) accumulate as device scalars; the adds
        # dispatch asynchronously and the host syncs ONCE at the end
        # (a per-batch int() sync would serialize host<->device).
        for batch in dataset.batches(batch_size, shuffle=False, drop_remainder=False,
                                     start=start):
            dev_batch = self.plan.put_batch(batch)
            c, n = self.program.eval_step(self.state[0], dev_batch)
            total_correct = total_correct + c
            total = total + n
        return int(total_correct) / max(int(total), 1)

    def predict_proba(self, x: np.ndarray, batch_size: int, extra: Optional[Batch] = None) -> np.ndarray:
        """Forward a query array; pads to full batches, returns (N, ..., C) probs."""
        n = x.shape[0]
        outs = []
        for start in range(0, n, batch_size):
            chunk = x[start : start + batch_size]
            pad = batch_size - chunk.shape[0]
            if pad:
                chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
            batch = {"x": chunk}
            if extra:
                batch.update(extra)
            probs = np.asarray(self.program.predict(self.state[0], self.plan.put_batch(batch)))
            outs.append(probs[: batch_size - pad] if pad else probs)
        return np.concatenate(outs) if outs else np.zeros((0,))


# ---------------------------------------------------------------------------
# Trial packing: k same-program trials vectorized into one XLA program
# ---------------------------------------------------------------------------
#
# The program cache makes back-to-back same-shape trials compile-free,
# but one Rafiki-scale trial stream nowhere near saturates a chip's
# MXU. PackedProgram vmaps the SAME per-trial step closures over a
# leading trial axis: k learning rates, warmups, dropouts and rng
# streams advance in lockstep inside one jit'd (donated) program, and
# the pack shares one device-resident dataset upload. Per-trial
# identity is preserved exactly — trial i's params, rng chain and
# shuffle order match what a serial TrainLoop(seed_i) would produce —
# so scores are comparable to serial runs within numeric tolerance.
#
# Packing composes with the program cache, not with the dp mesh:
# a packed trial is single-device by construction (the trial axis IS
# the parallelism), and multihost SPMD groups must keep packing off
# (docs/trial_packing.md).


class PackedProgram:
    """The compiled half of a k-trial pack: vmapped, jit'd steps.

    Safe to share (via the process-wide program cache) across packs
    whose traced computation AND pack width k are identical; per-pack
    state lives in :class:`PackedTrainLoop`.
    """

    def __init__(self, init_fn, apply_fn, loss_fn: LossFn,
                 optimizer: optax.GradientTransformation, k: int,
                 dynamic_lr: bool = True):
        if k < 1:
            raise ValueError(f"pack width k={k} must be >= 1")
        self.k = k
        self.plan = _ShardingPlan.build(None)  # packing is single-device
        self.optimizer = optimizer
        self.dynamic_lr = dynamic_lr
        self.apply_fn = apply_fn
        train_step, eval_step, predict, init_all = _make_step_fns(
            init_fn, apply_fn, loss_fn, optimizer, dynamic_lr)

        # Trial axis 0 everywhere in the carried state; eval/predict
        # share one batch across trials (in_axes=(0, None)) while the
        # train step feeds each trial ITS OWN batch so per-trial
        # shuffle order matches a serial run.
        v_train = jax.vmap(train_step)
        v_eval = jax.vmap(eval_step, in_axes=(0, None))
        v_predict = jax.vmap(predict, in_axes=(0, None))
        v_init = jax.vmap(init_all)

        def packed_train_epoch(state, X, Y, idx, poison=None):
            # idx: (n_steps, k, batch) int32 — per-trial permutations.
            # poison: optional (n_steps, k) chaos train.nan multipliers;
            # vmap hands each member its own column, so one sick member
            # cannot perturb its pack-mates (ones-column = bit-exact).
            def body(st, xs):
                ib, pz = xs
                batch = _gather_batch(X, Y, ib)
                if pz is not None:
                    batch["_health_poison"] = pz
                return v_train(st, batch)

            state, ms = jax.lax.scan(body, state, (idx, poison))
            # Final-step metrics per trial: each value is (k,); the
            # health series reduces per member on-device.
            rest, health = _sentinel.split(ms)
            out = {key: v[-1] for key, v in rest.items()}
            with jax.named_scope(SCOPE_HEALTH):
                out.update(_sentinel.reduce_epoch(health))
            return state, out

        def packed_eval_epoch(params, X, Y, idx):
            # idx: (n_steps, batch) — eval order is shared (no shuffle).
            def body(carry, ib):
                batch = _gather_batch(X, Y, ib)
                c, n = v_eval(params, batch)
                return (carry[0] + c, carry[1] + n), None

            zero = jnp.zeros((k,), jnp.int32)
            (c, n), _ = jax.lax.scan(body, (zero, zero), idx)
            return c, n

        self.train_step = jax.jit(v_train, donate_argnums=(0,))
        self.eval_step = jax.jit(v_eval)
        self.predict = jax.jit(v_predict)
        self.init = jax.jit(v_init)
        self.train_epoch = jax.jit(packed_train_epoch, donate_argnums=(0,))
        self.eval_epoch = jax.jit(packed_eval_epoch)


def packed_program_key(program_key: Hashable, k: int, dynamic_lr: bool) -> Hashable:
    """Cache key for a PackedProgram. Structurally distinct from the
    unpacked key form ``(program_key, mesh_key, dynamic_lr)`` — the
    leading tag guarantees packed and unpacked programs never collide
    in the process-wide cache even for identical base keys."""
    return ("packed", int(k), program_key, bool(dynamic_lr))


class PackedTrainLoop:
    """Per-pack state driving a (possibly cached) PackedProgram.

    Parameters mirror :class:`TrainLoop`, pluralized: ``seeds`` is the
    k per-trial init seeds; ``hypers`` the k per-trial dynamic-scalar
    dicts (identical key sets — a structural requirement, since the
    hyper dict's keys are part of the trace). Trial i of the pack is
    bit-for-bit the same *computation* as ``TrainLoop(seed=seeds[i],
    hyper=hypers[i])`` — only batched.
    """

    def __init__(self, init_fn, apply_fn, loss_fn, optimizer=None,
                 seeds: Optional[list] = None,
                 hypers: Optional[list] = None,
                 program_key: Optional[Hashable] = None,
                 packing_key: Optional[str] = None):
        if not seeds:
            raise ValueError("PackedTrainLoop needs at least one seed")
        # The repr of the members' shared Model.packing_key — stamped
        # onto every perf/step record so the train twin can bucket
        # step-time calibration per (packing_key, k) (docs/twin.md).
        self.packing_key = packing_key
        self.k = len(seeds)
        hypers = hypers if hypers is not None else [{} for _ in seeds]
        if len(hypers) != self.k:
            raise ValueError(f"{len(hypers)} hyper dicts for {self.k} seeds")
        keysets = {tuple(sorted(h)) for h in hypers}
        if len(keysets) != 1:
            raise ValueError(
                f"pack members carry different hyper keys {sorted(keysets)}; "
                f"the hyper dict's key set is part of the traced program")
        dynamic_lr = "lr" in hypers[0]
        if optimizer is None:
            optimizer = optax.scale_by_adam() if dynamic_lr else optax.adam(1e-3)
        # The build inputs outlive __init__: evict/admit change the pack
        # width k, and width is part of the packed program key, so every
        # re-pack fetches (or builds) the program at the new width.
        self._fns = (init_fn, apply_fn, loss_fn, optimizer)
        self._program_key = program_key
        self._dynamic_lr = dynamic_lr
        self._set_program()
        # Per-member numerics health (docs/health.md): a pack never
        # raises on divergence — run_epoch stashes per-member verdicts
        # on ``last_verdicts`` and the pack driver (train_packed)
        # evicts only the sick member.
        self.health = HealthMonitor(str(self._perf_key), k=self.k)
        self.last_verdicts: list = [None] * self.k

        # Per-trial rng derivation matches TrainLoop exactly: key(seed)
        # split once; row 0 carries on as the step rng, row 1 seeds init.
        # A member at a time, as TrainLoop does it: through a vmap over the
        # keys jax traced the split anew in every round (a compile stage
        # in each hand-over of a window); the bits are the same.
        split = jnp.stack([jax.random.split(jax.random.PRNGKey(int(s)))
                           for s in seeds])  # (k, 2, key)
        rngs, init_rngs = split[:, 0], split[:, 1]
        params, opt_state = self.program.init(init_rngs)
        hyper_dev = {name: jnp.asarray([float(h[name]) for h in hypers],
                                       jnp.float32)
                     for name in hypers[0]}
        self.state = (params, opt_state, jnp.zeros((self.k,), jnp.int32),
                      rngs, hyper_dev)

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value) -> None:
        self._state = value
        # The finished round's host copy of ``state[0]`` (see
        # stage_host_params): whatever replaces the state drops it.
        self.host_copy = None

    def _set_program(self) -> None:
        """(Re)fetch the PackedProgram at the CURRENT width self.k —
        the packed cache key includes k, so a width change after
        evict/admit compiles (once, then cached) a new program while
        per-trial math stays bit-identical (vmap width never enters the
        per-trial computation)."""
        init_fn, apply_fn, loss_fn, optimizer = self._fns
        k, dynamic_lr = self.k, self._dynamic_lr

        def build() -> PackedProgram:
            return PackedProgram(init_fn, apply_fn, loss_fn, optimizer, k,
                                 dynamic_lr=dynamic_lr)

        if self._program_key is not None:
            self._perf_key = packed_program_key(self._program_key, k, dynamic_lr)
            self.program = get_program(self._perf_key, build)
        else:
            self._perf_key = ("packed", "anon", id(self), k)
            self.program = build()
        self.plan = self.program.plan
        self.optimizer = self.program.optimizer

    # -- elastic membership (docs/mesh_sweep.md) -----------------------------

    def evict(self, i: int):
        """Slice member ``i`` out of the stacked state and narrow the
        pack to k-1. Returns the evicted member's serial-shaped state
        (leading trial axis removed) — exactly what a serial
        ``TrainLoop`` carrying that trial would hold, so the caller can
        adopt it via ``TrainLoop(initial_state=...)`` or checkpoint it.

        Used for straggler eviction (a member's early-stop fires epochs
        before its pack-mates) and for re-packing after a lost chip.
        """
        if not (0 <= i < self.k):
            raise IndexError(f"evict {i} out of pack of {self.k}")
        if self.k == 1:
            raise ValueError("cannot evict the last pack member")
        evicted = jax.tree.map(lambda a: a[i], self.state)
        self.state = jax.tree.map(
            lambda a: jnp.concatenate([a[:i], a[i + 1:]], axis=0), self.state)
        self.k -= 1
        self._set_program()
        self.health.evict_member(i)
        if i < len(self.last_verdicts):
            self.last_verdicts.pop(i)
        telemetry.inc("trial_pack.evictions")
        return evicted

    def admit(self, seed: int, hyper: Dict[str, float]) -> int:
        """Backfill one slot: append a fresh member initialized exactly
        as a serial ``TrainLoop(seed=seed, hyper=hyper)`` would be and
        widen the pack to k+1. Returns the new member's slot index.

        The hyper key set must match the pack's (it is part of the
        traced state structure).
        """
        have = tuple(sorted(self.state[4]))
        want = tuple(sorted(hyper))
        if have != want:
            raise ValueError(
                f"backfill hyper keys {want} != pack hyper keys {have}")
        keys = jnp.stack([jax.random.PRNGKey(int(seed))])
        split = jax.vmap(jax.random.split)(keys)
        rngs, init_rngs = split[:, 0], split[:, 1]
        params, opt_state = self.program.init(init_rngs)
        member = (params, opt_state, jnp.zeros((1,), jnp.int32), rngs,
                  {name: jnp.asarray([float(hyper[name])], jnp.float32)
                   for name in hyper})
        self.state = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), self.state, member)
        self.k += 1
        self._set_program()
        self.health.admit_member()
        self.last_verdicts.append(None)
        telemetry.inc("trial_pack.backfills")
        return self.k - 1

    # -- per-trial views -----------------------------------------------------

    def trial_params(self, i: int):
        """Trial i's parameter pytree (device slices of the stacked leaves)."""
        return jax.tree.map(lambda a: a[i], self.state[0])

    def trial_state(self, i: int):
        """Trial i's full (params, opt_state, step, rng, hyper) state,
        shaped exactly like a serial TrainLoop's."""
        return jax.tree.map(lambda a: a[i], self.state)

    def stage_host_params(self, cast_f32_to_bf16: bool) -> None:
        """Dispatch ONE device-to-host copy of the stacked parameters,
        cast to what a dump stores, for a pack that has finished
        training: every member's dump then reads ``host_leaf[i]`` views
        of it (``PackedSliceLoop.host_copy``) and touches no device.
        Returns at dispatch; the copy runs under whatever the device is
        given next and is waited for where the first dump consumes it."""
        from rafiki_tpu.utils.serial import StackedHostCopy

        self.host_copy = StackedHostCopy(self.state[0], cast_f32_to_bf16)

    def slice(self, i: int) -> "PackedSliceLoop":
        return PackedSliceLoop(self, i)

    # -- epochs --------------------------------------------------------------

    def _fits_device_fast_path(self, dataset) -> bool:
        return (getattr(dataset, "mask", None) is None
                and dataset.x.nbytes + dataset.y.nbytes <= device_dataset_cap_bytes())

    def run_epoch(self, dataset, batch_size: int, epoch_seeds) -> list:
        """One epoch for every trial in the pack; ``epoch_seeds`` is the
        k per-trial shuffle seeds (serial parity: ``seed_i + epoch``).
        Returns a list of k per-trial final-step metric dicts."""
        if len(epoch_seeds) != self.k:
            raise ValueError(f"{len(epoch_seeds)} epoch seeds for pack of {self.k}")
        if dataset.size < batch_size:
            raise ValueError(
                f"Dataset has {dataset.size} examples < batch_size={batch_size}; "
                f"the epoch would run zero steps")
        # Pre-epoch stacked-state snapshot for replay capsules (sliced
        # per sick member only on trip); banked before the timer so the
        # copy never pollutes step_s. See TrainLoop.run_epoch. With
        # capsules on (the default) it is a device-to-host copy of the
        # whole stacked state, so it is a leaf phase of its own.
        with telemetry.span("train.health_snapshot", leaf=True):
            snap = self.health.snapshot_state(self.state)
        n_steps = dataset.size // batch_size
        cold = not getattr(self, "_warm", False)
        t_epoch = time.monotonic()
        # The epoch as the host waits for it: from here to the metrics on
        # the host (jax returns at enqueue, so anything read before the
        # device_get is the dispatch). A leaf phase: the hand-over
        # between two rounds ends where this span starts.
        with telemetry.span("train.packed_epoch", leaf=True, cold=cold,
                            k=self.k, steps=n_steps) as sp, _compile_seconds(sp):
            # Same in-timed-region chaos site as the serial loop: injected
            # delays here are visible to the anomaly detector.
            from rafiki_tpu import chaos as _chaos

            _chaos.hook("train.epoch", key=str(self._perf_key))
            # (n_steps, k, batch): step-major so lax.scan walks steps while
            # each trial keeps its own serial-identical permutation.
            idx = np.stack([
                np.random.default_rng(int(s)).permutation(dataset.size)
                [: n_steps * batch_size].reshape(n_steps, batch_size)
                for s in epoch_seeds], axis=1).astype(np.int32)
            poison = self._chaos_poison(n_steps)
            if self._fits_device_fast_path(dataset):
                metrics = self._epoch_on_device(dataset, idx, poison, cold)
            else:
                metrics = self._epoch_by_steps(dataset, idx, poison)
            host = {key: np.asarray(jax.device_get(v))
                    for key, v in metrics.items()}
        self._record_epoch(t_epoch)
        rows = [{key: float(v[i]) for key, v in host.items()}
                for i in range(self.k)]
        return self._health_check(rows, t_epoch, epoch_seeds, idx,
                                  poison, snap)

    def _epoch_on_device(self, dataset, idx, poison, cold: bool):
        """The whole epoch as one program over the device-resident data
        set; returns the metrics still on the device."""
        X, Y = get_device_dataset(dataset)
        if cold:
            from rafiki_tpu.obs.perf import profiler as _profiler

            _profiler.capture_cost(self._perf_key,
                                   self.program.train_epoch,
                                   self.state, X, Y, idx, poison,
                                   kind="packed", k=self.k)
        self.state, metrics = self.program.train_epoch(
            self.state, X, Y, idx, poison)
        return metrics

    def _epoch_by_steps(self, dataset, idx, poison):
        """The epoch one step program at a time, batches gathered on the
        host (data sets over the device cap); metrics on the device."""
        metrics = None
        health_steps = []
        for t in range(idx.shape[0]):
            ib = idx[t]  # (k, batch)
            batch = {"x": jnp.asarray(dataset.x[ib]),
                     "y": jnp.asarray(dataset.y[ib])}
            if poison is not None:
                batch["_health_poison"] = jnp.asarray(poison[t])
            self.state, metrics = self.program.train_step(self.state, batch)
            # (k,) device vectors appended as-is — the health series
            # syncs once, at the epoch-boundary reduction below.
            health_steps.append({k: v for k, v in metrics.items()
                                 if k.startswith(_sentinel.PREFIX)})
        out = {key: v for key, v in metrics.items()
               if not key.startswith(_sentinel.PREFIX)}
        out.update(_sentinel.reduce_epoch(
            {k: jnp.stack([h[k] for h in health_steps])
             for k in health_steps[0]}))
        return out

    def _chaos_poison(self, n_steps: int) -> np.ndarray:
        """Per-member ``train.nan`` poison plane: each live member is a
        distinct hook key (``<perf_key>@m<i>`` — ``@`` because the spec
        grammar reserves ``:``), so a chaos spec's ``match=@m2`` selects
        WHICH pack member diverges. The matrix is ALWAYS present
        (all-ones when quiet) for the same single-trace reason as the
        serial column — see :meth:`TrainLoop._chaos_poison`. Members
        whose column stays all-ones are bit-unaffected (the multiply is
        exact and the trace is uniform) — the isolation the
        nan-trial-contained scenario pins."""
        from rafiki_tpu import chaos as _chaos

        poison = np.ones((n_steps, self.k), np.float32)
        if _chaos.active() is not None:
            hit = [i for i in range(self.k)
                   if _chaos.hook("train.nan",
                                  key=f"{self._perf_key}@m{i}") is not None]
            poison[n_steps // 2, hit] = np.nan
        return poison

    def _health_check(self, rows: list, t0: float, epoch_seeds, idx,
                      poison, snapshot) -> list:
        """Epoch-boundary health gate, pack flavor: strip the sentinel
        keys from the per-member metric rows and stash one
        Optional[verdict] per live slot on ``last_verdicts``. A pack
        never raises — survivors must keep training; the pack driver
        evicts sick members (docs/health.md)."""
        health_rows = [{k: v for k, v in r.items()
                        if k.startswith(_sentinel.PREFIX)} for r in rows]
        clean = [{k: v for k, v in r.items()
                  if not k.startswith(_sentinel.PREFIX)} for r in rows]
        self.last_verdicts = self.health.observe_pack(
            health_rows, t0=t0, epoch_seeds=epoch_seeds, idx=idx,
            poison=poison, snapshot=snapshot)
        return clean

    def _record_epoch(self, t0: float) -> None:
        from rafiki_tpu.obs.ledger import ledger

        # lint: disable=RF007 — epoch wall split into ledger buckets
        dt = time.monotonic() - t0
        cold = not getattr(self, "_warm", False)
        self._warm = True
        # (The epoch's wall itself is the ``train.packed_epoch`` span,
        # tagged ``cold``; /metrics exports its aggregate.)
        # Goodput ledger: same convention as the serial loop — the cold
        # (compile-paying) epoch is overhead, warm epochs are productive.
        ledger.add("compile_s" if cold else "step_s", dt)
        from rafiki_tpu.obs.perf import profiler, slo

        profiler.note_epoch(self._perf_key, dt, cold=cold,
                            kind="packed", k=self.k,
                            packing_key=self.packing_key)
        slo.maybe_tick()

    def evaluate(self, dataset, batch_size: int) -> np.ndarray:
        """(k,) per-trial accuracies over one shared eval pass: the
        batch stream is uploaded/gathered ONCE and every trial's params
        score it inside one vmapped program."""
        total_correct = jnp.zeros((self.k,), jnp.int32)
        total = jnp.zeros((self.k,), jnp.int32)
        start = 0
        if self._fits_device_fast_path(dataset) and dataset.size >= batch_size:
            X, Y = get_device_dataset(dataset)
            n_steps = dataset.size // batch_size
            idx = np.arange(n_steps * batch_size, dtype=np.int32).reshape(
                n_steps, batch_size)
            c, n = self.program.eval_epoch(self.state[0], X, Y, idx)
            total_correct, total = total_correct + c, total + n
            start = n_steps * batch_size
        for batch in dataset.batches(batch_size, shuffle=False,
                                     drop_remainder=False, start=start):
            dev_batch = self.plan.put_batch(batch)
            c, n = self.program.eval_step(self.state[0], dev_batch)
            total_correct = total_correct + c
            total = total + n
        c = np.asarray(jax.device_get(total_correct), dtype=np.float64)
        n = np.asarray(jax.device_get(total), dtype=np.float64)
        return c / np.maximum(n, 1.0)


class PackedSliceLoop:
    """A per-trial, TrainLoop-shaped view over a PackedTrainLoop.

    Exposes exactly the surface JaxModel touches after training
    (``params``/``state``/``evaluate``/``predict_proba``), so a model
    trained inside a pack dumps, scores and serves through the same
    code paths as a serially-trained one. Mutating entry points
    (run_epoch) are deliberately absent: per-trial training continues
    only through the pack.
    """

    def __init__(self, packed: PackedTrainLoop, index: int):
        if not (0 <= index < packed.k):
            raise IndexError(f"slice {index} out of pack of {packed.k}")
        self.packed = packed
        self.index = index
        self.plan = packed.plan

    @property
    def params(self):
        return self.packed.trial_params(self.index)

    @property
    def state(self):
        return self.packed.trial_state(self.index)

    @property
    def host_copy(self):
        """The round's host copy of the pack's stacked parameters, or
        None: a dump of this member reads its ``member(index)`` (a
        serial ``TrainLoop`` has no such attribute and fetches its own)."""
        return self.packed.host_copy

    def evaluate(self, dataset, batch_size: int) -> float:
        # The packed evaluator scores all k trials in one pass; callers
        # wanting every score should use PackedTrainLoop.evaluate once
        # instead of k slice evaluates (the jit cache makes the repeat
        # calls cheap, not free).
        return float(self.packed.evaluate(dataset, batch_size)[self.index])

    def predict_proba(self, x: np.ndarray, batch_size: int,
                      extra: Optional[Batch] = None) -> np.ndarray:
        n = x.shape[0]
        outs = []
        for start in range(0, n, batch_size):
            chunk = x[start : start + batch_size]
            pad = batch_size - chunk.shape[0]
            if pad:
                chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
            batch = {"x": chunk}
            if extra:
                batch.update(extra)
            probs = np.asarray(
                self.packed.program.predict(self.packed.state[0],
                                            self.plan.put_batch(batch))[self.index])
            outs.append(probs[: batch_size - pad] if pad else probs)
        return np.concatenate(outs) if outs else np.zeros((0,))


# ---------------------------------------------------------------------------
# Standalone builders (legacy surface; Program is the primary API)
# ---------------------------------------------------------------------------


def make_train_step(loss_fn: LossFn, optimizer: optax.GradientTransformation,
                    plan: _ShardingPlan, dynamic_lr: bool = False):
    """Build a donated, jit'd SGD step.

    NOTE (contract change vs round 1): the carried state is now the
    5-tuple (params, opt_state, step, rng, hyper) — ``hyper`` may be
    an empty dict when no dynamic hyperparameters are used.
    """
    prog = Program(lambda rng: None, lambda p, b: None, loss_fn, optimizer,
                   plan, dynamic_lr=dynamic_lr)
    return prog.train_step


def make_eval_step(apply_fn, plan: _ShardingPlan):
    """Jit'd eval step returning (#correct, #valid) device scalars."""
    prog = Program(lambda rng: None, apply_fn,
                   lambda p, b, r, h: (jnp.float32(0.0), {}),
                   optax.identity(), plan, dynamic_lr=False)
    return prog.eval_step


def make_predict_fn(apply_fn, plan: _ShardingPlan):
    """Jit'd forward returning probabilities."""
    prog = Program(lambda rng: None, apply_fn,
                   lambda p, b, r, h: (jnp.float32(0.0), {}),
                   optax.identity(), plan, dynamic_lr=False)
    return prog.predict
