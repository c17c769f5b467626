"""The model contract: what a model template must implement.

Reference parity: rafiki/model/model.py (unverified path — see
SURVEY.md). The reference's ``BaseModel`` hooks are
``get_knob_config() / init(knobs) / train(dataset_uri) /
evaluate(dataset_uri) -> float / predict(queries) -> list /
dump_parameters() / load_parameters() / destroy()``; uploaded model
``.py`` files are loaded with ``load_model_class``.

We keep the same surface (so reference model templates translate
mechanically) and add a TPU-native base class, ``JaxModel``, that model
developers subclass instead of hand-writing device loops: they provide a
flax Module + knob config, and train/evaluate/predict become jit'd XLA
programs with optional within-trial data parallelism over a device mesh.
"""

from __future__ import annotations

import abc
import importlib
import importlib.util
import io
import pickle
import sys
import tempfile
import types
from typing import Any, Dict, List, Optional

import numpy as np

from rafiki_tpu import telemetry
from rafiki_tpu.model.knobs import KnobConfig, Knobs, validate_knobs
from rafiki_tpu.model.dataset import Dataset, dataset_utils


class BaseModel(abc.ABC):
    """Abstract model template (reference-compatible surface).

    Lifecycle of one trial (driven by the train worker, SURVEY.md §3.1):
      model = ModelClass(**knobs)      # reference: init(knobs)
      model.train(train_uri)
      score = model.evaluate(val_uri)
      blob = model.dump_parameters()
      ... later, for serving ...
      model = ModelClass(**knobs); model.load_parameters(blob)
      out = model.predict(queries)
    """

    def __init__(self, **knobs: Any):
        self.knobs: Knobs = validate_knobs(self.get_knob_config(), knobs)

    # -- static declarations -------------------------------------------------

    @staticmethod
    @abc.abstractmethod
    def get_knob_config() -> KnobConfig:
        """Declare the hyperparameter space."""

    # -- trial hooks ---------------------------------------------------------

    @abc.abstractmethod
    def train(self, dataset_uri: str) -> None: ...

    @abc.abstractmethod
    def evaluate(self, dataset_uri: str) -> float: ...

    @abc.abstractmethod
    def predict(self, queries: List[Any]) -> List[Any]: ...

    def dump_parameters(self) -> bytes:
        raise NotImplementedError

    def load_parameters(self, blob: bytes) -> None:
        raise NotImplementedError

    def destroy(self) -> None:
        """Release device/host resources (optional)."""

    # -- conveniences --------------------------------------------------------

    @classmethod
    def knob_config(cls) -> KnobConfig:
        return cls.get_knob_config()


class JaxModel(BaseModel):
    """TPU-native base: subclass provides a flax Module, gets jit'd hooks.

    Subclasses implement:
      * ``get_knob_config()`` — include the conventional knobs
        ``learning_rate`` / ``batch_size`` / ``epochs`` (or override
        the corresponding properties);
      * ``build_module(num_classes, input_shape) -> flax.linen.Module``
        whose ``__call__(x, train: bool)`` returns logits.

    Optional overrides: ``make_optimizer()``, ``loss()``,
    ``preprocess(x)``.

    The mesh used for within-trial data parallelism is injected by the
    scheduler via ``set_mesh`` before ``train`` (SURVEY.md §7 step 7);
    by default the model runs on the process's default device.
    """

    def __init__(self, **knobs: Any):
        super().__init__(**knobs)
        self._loop = None  # ops.train.TrainLoop, built lazily at train/load
        self._mesh = None
        self._seed = int(self.knobs.get("seed", 0))
        self._dataset_meta: Dict[str, Any] = {}
        self._ckpt_sink = None  # set by the worker for mid-trial checkpoints
        self._start_epoch = 0  # >0 after restore_checkpoint

    # -- knob conventions ----------------------------------------------------

    @property
    def batch_size(self) -> int:
        return int(self.knobs.get("batch_size", 64))

    @property
    def epochs(self) -> int:
        return int(self.knobs.get("epochs", 1))

    @property
    def learning_rate(self) -> float:
        return float(self.knobs.get("learning_rate", 1e-3))

    # -- subclass surface ----------------------------------------------------

    @abc.abstractmethod
    def build_module(self, num_classes: int, input_shape: tuple):
        """Return a flax.linen.Module mapping x -> logits."""

    def make_base_optimizer(self):
        """Lr-free optimizer core for the standard (program-shared)
        path: the train step applies ``-effective_lr(hyper, step)``
        itself, so learning rate and warmup are traced scalars and an
        lr sweep reuses ONE compiled XLA program."""
        import optax

        return optax.scale_by_adam()

    def _warmup_steps(self) -> int:
        """Linear warmup guards deep nets (GroupNorm + bf16) against
        the early-step collapse that makes high-lr trials score as
        noise — without it the advisor's lr axis has a cliff instead of
        a slope. Capped at 10% of the planned steps so short trials
        still train."""
        planned = getattr(self, "_planned_steps", None) or 1000
        return int(self.knobs.get("warmup_steps",
                                  min(100, max(1, planned // 10))))

    def make_optimizer(self):
        """Legacy override hook: return a *complete* optax optimizer
        (lr baked in). Overriding this opts the template out of
        cross-lr program sharing — same-knob trials still reuse the
        compiled program, but each distinct lr/schedule compiles its
        own. Prefer ``make_base_optimizer`` + the lr knob."""
        import optax

        sched = optax.linear_schedule(0.0, self.learning_rate, self._warmup_steps())
        return optax.adam(sched)

    def preprocess(self, x: np.ndarray) -> np.ndarray:
        """Optional input transform. MUST NOT modify ``x`` in place —
        datasets are cached and shared across trials (dataset_utils);
        return a new array (e.g. ``x / 255.0``, not ``x /= 255.0``)."""
        return x

    def loss(self, params, batch, rng, apply_fn):
        from rafiki_tpu.ops.train import cross_entropy_loss

        logits = apply_fn(params, batch, train=True, rng=rng)
        loss, acc = cross_entropy_loss(logits, batch["y"])
        return loss, {"acc": acc}

    def should_stop_early(self, epoch: int, metrics: Dict[str, float]) -> bool:
        """Per-epoch early-stop hook: return True to end training after
        ``epoch`` (metrics are that epoch's train metrics). Honoured by
        both the serial ``train()`` loop and ``train_packed`` — a packed
        member whose stop fires before its pack-mates is EVICTED from
        the stacked state mid-pack and its slot backfilled
        (docs/mesh_sweep.md), with the evicted member's params
        bit-matching the serial early-stopped run."""
        return False

    # -- internal wiring -----------------------------------------------------

    def set_mesh(self, mesh) -> None:
        self._mesh = mesh

    def _dynamic_hyper(self, takes_dropout: bool) -> Dict[str, float]:
        """Values for the traced hyper dict carried in the train state.
        Everything here changes per trial WITHOUT recompiling."""
        hyper = {"lr": float(self.learning_rate),
                 "warmup": float(self._warmup_steps())}
        if takes_dropout and "dropout" in self.knobs:
            hyper["dropout"] = float(self.knobs["dropout"])
        return hyper

    def _program_key(self, num_classes: int, input_shape: tuple,
                     takes_dropout: bool, custom_opt: bool):
        """Cache key for the compiled Program: everything that can
        reach the traced computation EXCEPT the structurally dynamic
        knobs (lr/warmup via update scaling, dropout via the hyper
        dict, epochs = python loop count, seed = init rng value).
        A custom make_optimizer may bake any knob (and the planned-step
        count, via schedules) into its trace, so only seed is excluded
        and the planned steps are keyed in."""
        from rafiki_tpu.ops.train import DYNAMIC_KNOBS

        if custom_opt:
            dyn = {"seed"}
            extra = (getattr(self, "_planned_steps", None),)
        else:
            dyn = set(DYNAMIC_KNOBS) if takes_dropout else set(DYNAMIC_KNOBS) - {"dropout"}
            extra = ()
        baked = tuple(sorted((k, repr(v)) for k, v in self.knobs.items()
                             if k not in dyn))
        return (type(self).__module__, type(self).__qualname__,
                num_classes, tuple(input_shape), baked, custom_opt) + extra

    def _loop_fns(self, num_classes: int, input_shape: tuple) -> Dict[str, Any]:
        """Everything TrainLoop/PackedTrainLoop needs, derived once:
        the module, the pure fn closures, the optimizer, this trial's
        dynamic-hyper dict, and the program cache key. Shared by the
        serial path (``_build_loop``) and the packed path
        (``train_packed``) so the two can never drift apart."""
        import functools
        import inspect

        module = self.build_module(num_classes, input_shape)
        # Modules whose __call__ accepts ``dropout_rate`` get it as a
        # traced scalar from the hyper dict (see ops.train.dropout) —
        # a dropout sweep then reuses one compiled program.
        takes_dropout = "dropout_rate" in inspect.signature(
            type(module).__call__).parameters
        custom_opt = type(self).make_optimizer is not JaxModel.make_optimizer

        def apply_train(params, batch, train=False, rng=None, hyper=None):
            kwargs = {}
            if rng is not None:
                kwargs["rngs"] = {"dropout": rng}
            if takes_dropout and hyper is not None and "dropout" in hyper:
                kwargs["dropout_rate"] = hyper["dropout"]
            return module.apply({"params": params}, batch["x"], train=train, **kwargs)

        def apply_eval(params, batch):
            return apply_train(params, batch, train=False)

        def init_fn(rng):
            dummy = np.zeros((1,) + tuple(input_shape), self._input_dtype())
            variables = module.init(rng, dummy, train=False)
            return variables["params"]

        def loss_fn(params, batch, rng, hyper):
            return self.loss(params, batch, rng,
                             functools.partial(apply_train, hyper=hyper))

        hyper = self._dynamic_hyper(takes_dropout)
        if custom_opt:
            optimizer = self.make_optimizer()
            hyper.pop("lr", None)  # lr lives inside the custom optimizer
            hyper.pop("warmup", None)
        else:
            optimizer = self.make_base_optimizer()

        return {
            "module": module,
            "init_fn": init_fn,
            "apply_eval": apply_eval,
            "loss_fn": loss_fn,
            "optimizer": optimizer,
            "hyper": hyper,
            "program_key": self._program_key(num_classes, input_shape,
                                             takes_dropout, custom_opt),
        }

    def _build_loop(self, num_classes: int, input_shape: tuple):
        from rafiki_tpu.ops.train import TrainLoop

        fns = self._loop_fns(num_classes, input_shape)
        self._module = fns["module"]
        self._loop = TrainLoop(
            fns["init_fn"], fns["apply_eval"], fns["loss_fn"], fns["optimizer"],
            mesh=self._mesh, seed=self._seed, hyper=fns["hyper"],
            program_key=fns["program_key"], eval_count=fns.get("eval_count"),
            epoch_program=self.epoch_program())
        self._arch = (num_classes, tuple(input_shape))

    def _input_dtype(self):
        return np.float32

    def _dataset_arch(self, ds: Dataset) -> tuple:
        return ds.classes, tuple(ds.x.shape[1:])

    # -- contract hooks ------------------------------------------------------

    def _prepared_dataset(self, dataset_uri: str) -> Dataset:
        """Load + preprocess. When preprocess is the identity (returns
        the same array — the default), the process-cached Dataset
        object is used AS-IS so the device-resident copy attached to it
        (ops.train.get_device_dataset) is shared across trials; a
        custom preprocess gets a fresh wrapper per call (its output may
        depend on per-trial knobs, so it cannot be shared safely)."""
        ds = dataset_utils.load(dataset_uri)
        x = self.preprocess(ds.x)
        if x is ds.x:
            return ds
        return Dataset(x, ds.y, ds.classes, ds.mask, ds.meta)

    def _health_model_identity(self) -> Dict[str, Any]:
        """Replay-capsule identity: what a fresh process needs to
        re-create this template (docs/health.md). Templates loaded from
        uploaded source embed the bytes (load_model_class stamps
        ``__rafiki_source__`` on its scratch module)."""
        mod = sys.modules.get(type(self).__module__)
        return {
            "module": type(self).__module__,
            "qualname": type(self).__qualname__,
            "source": getattr(mod, "__rafiki_source__", None),
            "knobs": dict(self.knobs),
        }

    def train(self, dataset_uri: str) -> None:
        from rafiki_tpu.model.log import logger

        ds = self._prepared_dataset(dataset_uri)
        self._dataset_meta = dict(ds.meta)
        num_classes, input_shape = self._dataset_arch(ds)
        self._planned_steps = self.epochs * max(1, ds.size // self.batch_size)
        if self._loop is None:
            self._build_loop(num_classes, input_shape)
        elif self._arch != (num_classes, input_shape):
            raise ValueError(
                f"Dataset architecture {(num_classes, input_shape)} does not match "
                f"the loaded model {self._arch}; use a fresh model instance")
        health = getattr(self._loop, "health", None)
        if health is not None:
            health.set_context(
                model=self._health_model_identity(), train_uri=dataset_uri,
                batch_size=self.batch_size, seed=self._seed,
                planned_steps=getattr(self, "_planned_steps", None))
        logger.define_plot("Training", ["loss", "acc"], x_axis="epoch")
        for epoch in range(self._start_epoch, self.epochs):
            metrics = self._loop.run_epoch(ds, self.batch_size, epoch_seed=self._seed + epoch)
            logger.log(epoch=epoch, **metrics)
            self._epochs_done = epoch
            if self._ckpt_sink is not None:
                # The sink decides whether to materialize this epoch's
                # snapshot (dump is a device fetch — not free).
                self._ckpt_sink(epoch, self.dump_checkpoint)
            if self.should_stop_early(epoch, metrics):
                break

    def evaluate(self, dataset_uri: str) -> float:
        if self._loop is None:
            raise RuntimeError("Model has no parameters: call train() or load_parameters() first")
        ds = self._prepared_dataset(dataset_uri)
        self._check_label_space(ds)
        return float(self._loop.evaluate(ds, self.batch_size))

    # -- trial packing (docs/trial_packing.md) -------------------------------

    @classmethod
    def packable(cls) -> bool:
        """Whether instances of this template may join a trial pack.
        A pack shares ONE device-resident dataset upload, so templates
        with a custom ``preprocess`` (whose output may depend on
        per-trial knobs) are excluded."""
        return cls.preprocess is JaxModel.preprocess

    @classmethod
    def epoch_program(cls) -> bool:
        """Whether an epoch over a data set that fits the device runs as
        ONE program, a scan over its steps (the default: it removes a
        dispatch and a host feed a step). A template whose step takes
        seconds gains nothing from that and returns False: its epochs
        then run step by step through one compiled step, so that trials
        whose train sets differ in length share an executable that takes
        minutes to build, and the health plane's copy of the whole train
        state to the host before the epoch (the replay capsule's, which
        the step-by-step path does not write) is not made."""
        return True

    def shard_plan(self, ds: Dataset):
        """Group-sharding plan for one trial of this template, or None
        to stay in the single-chip lanes. Families whose train state
        can outgrow one chip's HBM override this to return a
        :class:`rafiki_tpu.shard.ShardPlan`; the sweep scheduler routes
        width>1 plans to a chip group (scheduler/mesh.py GroupHandle,
        docs/sharding.md). Width-1 plans (and None) mean the serial/
        packed lanes — the default for every small template."""
        return None

    def packing_key(self, ds: Dataset):
        """Bucket key for the PackedTrialRunner: two models may train
        in one pack iff their keys are equal — same compiled program
        (module config + baked knobs), same per-epoch step geometry
        (batch size, epochs), same dynamic-hyper key set (the hyper
        dict's keys are part of the traced state structure)."""
        num_classes, input_shape = self._dataset_arch(ds)
        self._planned_steps = self.epochs * max(1, ds.size // self.batch_size)
        fns = self._loop_fns(num_classes, input_shape)
        return (fns["program_key"], self.batch_size, self.epochs,
                tuple(sorted(fns["hyper"])))

    @classmethod
    def train_packed(cls, models: List["JaxModel"], dataset_uri: str,
                     on_epoch=None, checkpoint_sink=None,
                     backfill=None, on_evict=None,
                     kill_predicate=None) -> List[List[Dict[str, float]]]:
        """Train k model instances as ONE vmapped program on one device.

        All models must share a packing_key (the caller buckets).
        Per-trial identity is preserved: model i ends with the params,
        rng chain and shuffle order a serial ``train()`` with its seed
        would produce. Returns per-model epoch histories (list of
        ``{"loss": ..., "acc": ..., "epoch": e}`` dicts) — the caller
        writes them to each trial's log. ``on_epoch(round)`` fires
        after every packed round (worker heartbeats).

        ``checkpoint_sink(round, make_blobs)``, when given, fires after
        each round BEFORE ``on_epoch``; ``make_blobs()`` materializes
        one serial-format checkpoint blob per CURRENT pack member,
        returned as ``[(model_index, epoch, blob), ...]`` — sliced out of the
        live pack (``trial_state(i)`` device views, host copies
        pipelined) without serializing the stacked state, each stamped
        with that member's OWN epoch counter. A packed trial's
        checkpoint therefore restores through the ordinary serial
        resume path (docs/trial_packing.md).

        Elastic membership (docs/mesh_sweep.md): a member whose
        ``should_stop_early`` fires (or whose epoch budget completes)
        epochs before its pack-mates is EVICTED — its state is sliced
        out of the pack into a detached serial ``TrainLoop`` (so it
        still evaluates/serves/checkpoints normally and bit-matches a
        serial run) and ``on_evict(model_index, epoch, reason)`` fires
        with reason ``"early_stop"`` or ``"finished"``. A member whose
        numerics diverge (docs/health.md) leaves the same way with
        reason ``"diverged"`` — its verdict is stashed on
        ``model._health_verdict`` and the worker marks it errored
        instead of scoring it. When
        ``backfill(n)`` is given it is called with the vacancy count
        and may return freshly-proposed models (same packing_key);
        they are appended to ``models``/the returned histories and
        admitted into the freed slots mid-pack, starting at their own
        epoch 0. When every remaining member leaves in the same round,
        the pack ends and members keep live slice views (the shared
        ``evaluate_packed`` fast path).

        ``kill_predicate(model_index, epoch, metrics)``, when given, is
        consulted at each member's epoch boundary (after the
        divergence/budget/early-stop checks decline) and a True return
        evicts the member with reason ``"killed"`` — the learning-curve
        early-kill consumer (docs/early_kill.md). The caller owns all
        bookkeeping (the worker's ``on_evict`` marks the trial errored
        and routes the advisor's consolation feedback); default None =
        behavior identical to before the parameter existed.

        Not supported in a pack (callers enforce; asserted here):
        meshes (the trial axis IS the parallelism), checkpoint-resume
        (``_start_epoch > 0`` — an interrupted pack member resumes
        SERIALLY from its slice checkpoint), masked datasets.
        """
        from rafiki_tpu.obs import health as _health
        from rafiki_tpu.ops.train import PackedTrainLoop, TrainLoop

        if not models:
            return []

        def install_detached(mi: int, state, epoch: int) -> None:
            """Evicted member keeps training-equivalent state through an
            ordinary serial loop (same cached Program — ``hyper`` must
            be passed so dynamic_lr matches the pack's trace)."""
            m = models[mi]
            m._module = fns["module"]
            m._loop = TrainLoop(
                fns["init_fn"], fns["apply_eval"], fns["loss_fn"],
                fns["optimizer"], seed=m._seed, hyper=pack_hypers[mi],
                program_key=fns["program_key"], initial_state=state)
            m._arch = arch
            m._epochs_done = epoch

        # The pack's initialisation, a leaf phase of the hand-over between
        # rounds (docs/telemetry.md): key check, data set, the vmapped
        # init and the hyper-parameters' upload, up to the first epoch.
        # ``program.build`` (a cold round's cache miss) nests inside it
        # as a plain span: this one is the leaf.
        with telemetry.span("trial_pack.init", leaf=True, k=len(models)):
            lead = models[0]
            keys = {id(m): m.packing_key(lead._prepared_dataset(dataset_uri))
                    for m in models}
            if len(set(map(repr, keys.values()))) != 1:
                raise ValueError("train_packed models do not share a packing key; "
                                 "bucket with packing_key() first")
            for m in models:
                if m._mesh is not None:
                    raise ValueError("packed trials are single-device; mesh is set")
                if m._start_epoch > 0:
                    raise ValueError("packed trials cannot resume from checkpoint")
            ds = lead._prepared_dataset(dataset_uri)
            if ds.mask is not None:
                raise ValueError("packed training does not support masked datasets")
            num_classes, input_shape = lead._dataset_arch(ds)
            epochs, batch_size = lead.epochs, lead.batch_size

            # One set of traced closures (the lead's — program_key equality
            # makes them interchangeable), k hyper dicts/seeds.
            fns = lead._loop_fns(num_classes, input_shape)
            hypers = []
            for m in models:
                m._planned_steps = epochs * max(1, ds.size // batch_size)
                m._dataset_meta = dict(ds.meta)
                mf = m._loop_fns(num_classes, input_shape)
                hypers.append(mf["hyper"])
            packed = PackedTrainLoop(
                fns["init_fn"], fns["apply_eval"], fns["loss_fn"], fns["optimizer"],
                seeds=[m._seed for m in models], hypers=hypers,
                program_key=fns["program_key"],
                packing_key=repr(keys[id(lead)]))

            histories: List[List[Dict[str, float]]] = [[] for _ in models]
            arch = (num_classes, tuple(input_shape))
            planned = epochs * max(1, ds.size // batch_size)
            portable = _portable_meta(dict(ds.meta))
            pack_hypers = {i: hypers[i] for i in range(len(models))}

            slots = list(range(len(models)))  # slot j <-> packed member j
            epochs_done = {mi: 0 for mi in slots}  # epochs COMPLETED so far
            # Replay-capsule context (docs/health.md): member_info resolves
            # a LIVE slot to its trial's knobs/seed at trip time (slots and
            # models mutate as members leave and backfills arrive).
            packed.health.set_context(
                model=lead._health_model_identity(), train_uri=dataset_uri,
                batch_size=batch_size, planned_steps=planned,
                member_info=lambda j: {
                    "model": dict(lead._health_model_identity(),
                                  knobs=dict(models[slots[j]].knobs)),
                    "seed": models[slots[j]]._seed,
                })
        rnd = 0
        while slots:
            # Serial parity: trial i's shuffle seed is seed_i + its OWN
            # epoch index, exactly what train() passes to run_epoch —
            # backfilled members count from their own epoch 0.
            mts = packed.run_epoch(
                ds, batch_size,
                [models[mi]._seed + epochs_done[mi] for mi in slots])
            for j, mi in enumerate(slots):
                histories[mi].append(dict(mts[j], epoch=epochs_done[mi]))
            if checkpoint_sink is not None:
                ents = tuple((mi, epochs_done[mi]) for mi in slots)
                checkpoint_sink(
                    rnd,
                    lambda e=ents: cls._packed_checkpoint_blobs(
                        packed, arch, e, planned, portable))
            if on_epoch is not None:
                on_epoch(rnd)
            rnd += 1

            verdicts = getattr(packed, "last_verdicts", None) or []
            leavers = []  # (slot, model_index, just-run epoch, reason)
            for j, mi in enumerate(slots):
                e = epochs_done[mi]
                verdict = verdicts[j] if j < len(verdicts) else None
                if verdict is not None:
                    # Numerics divergence (docs/health.md): the member
                    # leaves NOW regardless of budget — its verdict
                    # rides on the model for the worker's diagnosis.
                    models[mi]._health_verdict = verdict
                    leavers.append((j, mi, e, "diverged"))
                elif e + 1 >= epochs:
                    leavers.append((j, mi, e, "finished"))
                elif models[mi].should_stop_early(e, mts[j]):
                    leavers.append((j, mi, e, "early_stop"))
                elif kill_predicate is not None \
                        and kill_predicate(mi, e, mts[j]):
                    leavers.append((j, mi, e, "killed"))
            for mi in slots:
                epochs_done[mi] += 1

            if len(leavers) == len(slots):
                # Whole pack ends together: keep live slice views so
                # evaluate_packed scores everyone in ONE shared pass.
                for j, mi, e, reason in leavers:
                    m = models[mi]
                    m._module = fns["module"]
                    m._loop = packed.slice(j)
                    m._arch = arch
                    m._epochs_done = e
                    if reason == "diverged":
                        _health.note_eviction()
                    if on_evict is not None and reason in ("early_stop",
                                                           "diverged",
                                                           "killed"):
                        on_evict(mi, e, reason)
                break

            # Stragglers-in-reverse: some members are done early —
            # slice them out (descending slot so indices stay valid).
            for j, mi, e, reason in sorted(leavers, reverse=True):
                install_detached(mi, packed.evict(j), e)
                slots.pop(j)
                if reason == "diverged":
                    _health.note_eviction()
                if on_evict is not None:
                    on_evict(mi, e, reason)

            if leavers and backfill is not None:
                for m2 in (backfill(len(leavers)) or []):
                    mf2 = m2.packing_key(ds)  # sets _planned_steps
                    if repr(mf2) != repr(keys[id(lead)]):
                        raise ValueError(
                            "backfill model's packing_key differs from the "
                            "live pack's; the caller must bucket first")
                    m2._dataset_meta = dict(ds.meta)
                    hyper2 = m2._loop_fns(num_classes, input_shape)["hyper"]
                    mi2 = len(models)
                    models.append(m2)
                    histories.append([])
                    pack_hypers[mi2] = hyper2
                    packed.admit(m2._seed, hyper2)
                    slots.append(mi2)
                    epochs_done[mi2] = 0
        return histories

    @staticmethod
    def _packed_checkpoint_blobs(packed, arch, entries, planned_steps,
                                 dataset_meta) -> List[tuple]:
        """Serial-format checkpoint blobs out of a live pack, one per
        CURRENT member. ``entries`` is ``[(model_index, epoch), ...]``
        aligned with pack slots 0..k-1 (members evicted/backfilled
        mid-sweep carry their OWN epoch counters); the return is
        ``[(model_index, epoch, blob), ...]``.

        The pack is NOT serialized: each trial's state is a device-side
        slice view (``trial_state(i)`` = ``tree.map(a[i])``), and every
        slice's device→host copies are kicked off asynchronously before
        any blob is assembled, so the k transfers overlap instead of
        serializing k round-trips. Payload keys mirror
        ``dump_checkpoint`` exactly — ``restore_checkpoint`` cannot
        tell a pack-sliced snapshot from a serial one.
        """
        import jax

        from rafiki_tpu.utils.serial import dump_pytree

        states = [packed.trial_state(i) for i in range(packed.k)]
        for st in states:
            for leaf in jax.tree.leaves(st):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
        blobs = []
        for st, (mi, epoch) in zip(states, entries):
            payload = {
                "arch": arch,
                "state_packed": dump_pytree(st, cast_f32_to_bf16=False),
                "epoch": epoch,
                "planned_steps": planned_steps,
                "dataset_meta": dataset_meta,
            }
            blobs.append((mi, epoch, pickle.dumps(payload)))
        return blobs

    @classmethod
    def evaluate_packed(cls, models: List["JaxModel"], dataset_uri: str) -> List[float]:
        """Score a just-packed set of models in ONE shared eval pass:
        the batch stream is gathered once and every trial's params
        score it inside one vmapped program. Models must all be slices
        of the same live pack (i.e. straight out of train_packed)."""
        from rafiki_tpu.ops.train import PackedSliceLoop

        if not models:
            return []
        lead = models[0]
        loops = [m._loop for m in models]
        if not all(isinstance(lp, PackedSliceLoop) for lp in loops) or \
                len({id(lp.packed) for lp in loops}) != 1:
            # Mixed/serial loops (e.g. after load_parameters): fall back
            # to per-model evaluate — correctness over the shared pass.
            return [m.evaluate(dataset_uri) for m in models]
        ds = lead._prepared_dataset(dataset_uri)
        for m in models:
            m._check_label_space(ds)
        scores = loops[0].packed.evaluate(ds, lead.batch_size)
        return [float(scores[lp.index]) for lp in loops]

    def _check_label_space(self, ds: Dataset) -> None:
        """Fail loudly when an eval dataset's LABEL MEANING diverges
        from the train dataset's. Class counts alone cannot catch a
        corpus whose tag set differs but has the same cardinality: the
        loader's sorted tag ids would shift and every score would be
        silently computed against wrong labels."""
        train_tags = self._dataset_meta.get("tag_map")
        eval_tags = ds.meta.get("tag_map")
        if train_tags and eval_tags and train_tags != eval_tags:
            raise ValueError(
                f"Eval dataset tag map {eval_tags} != train tag map "
                f"{train_tags}; the datasets label different tag sets")

    def predict(self, queries: List[Any]) -> List[List[float]]:
        if self._loop is None:
            raise RuntimeError("Model has no parameters: call train() or load_parameters() first")
        x = self.preprocess(np.asarray(queries, dtype=self._input_dtype()))
        probs = self._loop.predict_proba(x, self.batch_size)
        return probs.tolist()

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        """Array-in/array-out fast path used by the ensemble predictor."""
        if self._loop is None:
            raise RuntimeError("Model has no parameters: call train() or load_parameters() first")
        return self._loop.predict_proba(self.preprocess(np.asarray(x, self._input_dtype())),
                                        self.batch_size)

    # -- params --------------------------------------------------------------

    @classmethod
    def stage_packed_dump(cls, models: List["JaxModel"]) -> None:
        """Between ``train_packed`` and the members' dumps: where the
        pack ended as a whole and its members are live slice views, ask
        it for ONE host copy of its stacked parameters, cast to what a
        dump stores. Dispatched here (the worker calls this before the
        evaluation, so the transfer runs under it), consumed by the
        first ``dump_parameters`` of the round; detached members keep
        their own fetch. A dispatch the device refuses costs the round
        its shortcut, not its trials: every member then fetches alone."""
        from rafiki_tpu.config import get_config
        from rafiki_tpu.ops.train import PackedSliceLoop

        packed = next((m._loop.packed for m in models
                       if isinstance(m._loop, PackedSliceLoop)), None)
        if packed is None:
            return
        cast = get_config().serving_params_dtype == "bfloat16"
        with telemetry.span("persist.dispatch", leaf=True, k=packed.k):
            try:
                packed.stage_host_params(cast)
            except Exception:
                import traceback

                from rafiki_tpu.utils.events import events

                telemetry.inc("persist.dispatch_errors")
                events.emit("persist_dispatch_failed", k=packed.k,
                            error=traceback.format_exc(limit=3))

    def release_train_state(self) -> None:
        """Between a serial trial's evaluation and its dump (the worker
        calls it once the score is in): where the next trial could not
        train beside this one's state, the dump is staged now
        (``TrainLoop.release_to_host``: one cast copy on its way to the
        host) and the state let go, so the saver's pending dump holds a
        bfloat16 copy and not a whole state beside the next trial's.
        Decided by what the code sees: the device's peaks so far (this
        trial's state and what its programs reserved for gradients and
        activations) plus one more state against the device's limit. Smaller models keep their
        state and the saver fetches on its own, as ever; a device that
        reports no limit (the CPU) stages nothing."""
        import jax

        from rafiki_tpu.config import get_config
        from rafiki_tpu.ops.train import TrainLoop

        loop = self._loop
        if not isinstance(loop, TrainLoop) or loop.state is None \
                or loop.plan.mesh is not None:
            return
        leaf = jax.tree.leaves(loop.state[0])[0]
        stats = next(iter(leaf.devices())).memory_stats() or {}
        limit = stats.get("bytes_limit")
        # (a program's temporaries are reserved, not "in use": both peaks)
        peak = stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
        if not limit or peak + loop.state_bytes() <= limit:
            return
        cast = get_config().serving_params_dtype == "bfloat16"
        with telemetry.span("persist.dispatch", leaf=True):
            loop.release_to_host(cast)

    def dump_parameters(self) -> bytes:
        parts = self._parameter_parts()
        with telemetry.span("persist.write", leaf=True):
            blob = b"".join(parts)
        telemetry.inc("persist.host_copy_bytes", len(blob))
        return blob

    def dump_parameter_parts(self) -> Optional[list]:
        """The blob ``dump_parameters`` returns, as the buffers whose
        join it is (the pickle's opcodes round the fetched leaves' own
        memory, not copied): what ``ParamsStore.save_parts`` hashes and
        writes as it passes. The device's side of the dump, under
        ``persist.fetch``, is over when this returns; the parts alias
        the host leaves. None for a subclass with a ``dump_parameters``
        of its own: its bytes are the blob."""
        if type(self).dump_parameters is not JaxModel.dump_parameters:
            return None
        return self._parameter_parts()

    def _parameter_parts(self) -> list:
        from rafiki_tpu.config import get_config
        from rafiki_tpu.utils.serial import (
            parts_nbytes, pickled_dict_parts, pytree_parts)

        if self._loop is None:
            raise RuntimeError("No parameters to dump: model not trained/loaded")
        # Packed single-transfer dump (utils/serial.py): persisting is
        # on the steady-state throughput path via the async saver.
        host_copy = getattr(self._loop, "host_copy", None)
        if host_copy is not None:
            # A member of a finished pack round: the device's side is the
            # round's one stacked copy, waited for by whichever member
            # is dumped first; everything else is the host's. (Or a
            # serial trial whose state was let go for its staged copy:
            # ``release_train_state``; it has no index.)
            index = getattr(self._loop, "index", None)
            telemetry.inc("persist.members_from_round_copy" if index is not None
                          else "persist.serial_from_staged_copy")
            if not host_copy.fetched:
                with telemetry.span("persist.fetch", leaf=True):
                    host_copy.fetch()
            packed = pytree_parts(host_copy.member(index), cast_f32_to_bf16=False)
        else:
            telemetry.inc("persist.members_fetched_alone")
            cast = get_config().serving_params_dtype == "bfloat16"
            # The device's side of a dump of one's own: the leaves (sliced
            # out of the pack where ``params`` is a slice view's), the cast,
            # device to host. The host's side, ``persist.write``, is the
            # caller's: the store's pass over the parts, or the join.
            with telemetry.span("persist.fetch", leaf=True):
                packed = pytree_parts(self._loop.params, cast_f32_to_bf16=cast)
        telemetry.inc("persist.blob_bytes", parts_nbytes(packed))
        return pickled_dict_parts(
            {"arch": self._arch,
             "dataset_meta": _portable_meta(self._dataset_meta)},
            "packed", packed)

    def load_parameters(self, blob: bytes) -> None:
        import jax
        import jax.numpy as jnp
        from flax import serialization

        payload = pickle.loads(blob)
        num_classes, input_shape = payload["arch"]
        self._dataset_meta = payload.get("dataset_meta", {})
        self._build_loop(num_classes, tuple(input_shape))
        template = self._loop.params
        if "packed" in payload:
            from rafiki_tpu.utils.serial import load_pytree

            state = load_pytree(payload["packed"])
            params = serialization.from_state_dict(template, state)
            # Upcast any bf16-stored leaves back to the template dtype
            # (exact: bf16 -> f32 is an injection).
            params = jax.tree.map(
                lambda t, v: jnp.asarray(v, jnp.asarray(t).dtype), template, params)
        else:  # pre-packed-format blobs (flax msgpack)
            params = serialization.from_bytes(template, payload["params"])
        self._loop.params = jax.device_put(params)

    def destroy(self) -> None:
        self._loop = None

    # -- mid-trial checkpointing --------------------------------------------

    def set_checkpoint_sink(self, sink) -> None:
        """Install a per-epoch checkpoint hook: ``sink(epoch, make_blob)``
        where ``make_blob()`` returns the full-train-state snapshot.
        The reference has no mid-trial checkpointing (SURVEY.md §5);
        the TrainWorker wires this to the params store so long trials
        survive worker crashes."""
        self._ckpt_sink = sink

    def dump_checkpoint(self) -> bytes:
        """Full resumable snapshot: params AND optimizer state AND step
        counter (``dump_parameters`` is params-only, for serving).
        Full precision (resume must be exact), packed single-transfer."""
        from rafiki_tpu.utils.serial import dump_pytree

        if self._loop is None:
            raise RuntimeError("No state to checkpoint: model not trained")
        payload = {
            "arch": self._arch,
            "state_packed": dump_pytree(self._loop.state, cast_f32_to_bf16=False),
            "epoch": getattr(self, "_epochs_done", 0),
            "planned_steps": getattr(self, "_planned_steps", None),
            "dataset_meta": _portable_meta(self._dataset_meta),
        }
        return pickle.dumps(payload)

    def restore_checkpoint(self, blob: bytes) -> int:
        """Restore a ``dump_checkpoint`` snapshot; returns the epoch to
        resume from. ``train()`` then skips the already-done epochs."""
        import jax
        from flax import serialization

        payload = pickle.loads(blob)
        num_classes, input_shape = payload["arch"]
        self._dataset_meta = payload.get("dataset_meta", {})
        if payload.get("planned_steps"):
            self._planned_steps = payload["planned_steps"]
        self._build_loop(num_classes, tuple(input_shape))
        template = self._loop.state
        if "state_packed" in payload:
            from rafiki_tpu.utils.serial import load_pytree

            raw = load_pytree(payload["state_packed"])
        else:  # pre-packed-format blobs (flax msgpack)
            raw = serialization.msgpack_restore(payload["state"])
        try:
            state = serialization.from_state_dict(template, raw)
        except Exception:
            # Checkpoints from an older state/optimizer layout: salvage
            # the trained params and step counter — the expensive part —
            # and reinitialize optimizer state / rng / hyper fresh.
            params = serialization.from_state_dict(template[0], raw["0"])
            try:
                step = serialization.from_state_dict(template[2], raw["2"])
            except Exception:
                step = template[2]
            state = (params, template[1], step, template[3], template[4])
        self._loop.state = jax.device_put(state)
        self._start_epoch = int(payload["epoch"]) + 1
        return self._start_epoch


# ---------------------------------------------------------------------------
# Model file loading (reference: load_model_class executes uploaded .py)
# ---------------------------------------------------------------------------

def _portable_meta(meta: Dict[str, Any]) -> Dict[str, Any]:
    """The dataset-meta slice worth persisting in params/checkpoint
    blobs: scalars, plus the label-space signature (``tag_map``) so a
    restored model still fails loudly on a mismatched eval dataset."""
    out = {k: v for k, v in meta.items()
           if isinstance(v, (str, int, float, bool))}
    if isinstance(meta.get("tag_map"), dict):
        out["tag_map"] = dict(meta["tag_map"])
    return out


MODEL_CODE_FILENAME = "<rafiki_model.py>"


def load_model_class(model_file_bytes: bytes, model_class: str,
                     temp_mod_name: Optional[str] = None) -> type:
    """Load a model template class from uploaded ``.py`` source bytes.

    Matches the reference behavior of exec-ing the uploaded file into a
    scratch module. The uploaded source is *trusted* (model developers
    are authenticated users — same trust model as the reference).
    """
    name = temp_mod_name or f"_rafiki_model_{abs(hash(model_file_bytes)) % (1 << 30):x}"
    mod = types.ModuleType(name)
    mod.__dict__["__file__"] = f"<{name}.py>"
    sys.modules[name] = mod
    try:
        # The code's file name is one constant, not the module's name (which
        # differs from process to process): jax writes the file names of the
        # traceback into a Pallas kernel's serialized body, that body is part
        # of the persistent compile cache's key, and a step program that
        # holds a kernel would be compiled anew by every process.
        exec(compile(model_file_bytes, MODEL_CODE_FILENAME, "exec"), mod.__dict__)
    except Exception:
        del sys.modules[name]
        raise
    # Health replay capsules (docs/health.md) embed the source so a
    # fresh process can rebuild the class without this scratch module.
    mod.__rafiki_source__ = model_file_bytes
    if not hasattr(mod, model_class):
        del sys.modules[name]
        raise ValueError(f"Model file defines no class named {model_class!r}")
    cls = getattr(mod, model_class)
    if not (isinstance(cls, type) and issubclass(cls, BaseModel)):
        del sys.modules[name]
        raise ValueError(f"{model_class} must subclass rafiki_tpu BaseModel")
    return cls


def parse_model_install_command(dependencies: Dict[str, str]) -> List[str]:
    """Validate a model's declared deps are importable (no pip in this
    environment; the reference instead generated a pip install command)."""
    missing = []
    for dep in dependencies or {}:
        pkg = {"scikit-learn": "sklearn", "Pillow": "PIL"}.get(dep, dep.replace("-", "_"))
        if importlib.util.find_spec(pkg) is None:
            missing.append(dep)
    return missing
