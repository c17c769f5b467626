"""ShardedTrainLoop: one trial's state FSDP-sharded across a chip group.

Mirrors ops/train.py's jitted/donated epoch contract — same step
closures (``_make_step_fns``), same scan body, same rng chain and
shuffle derivation, same chaos/poison column — but the train state
lives under group-wide ``NamedSharding`` from a :class:`ShardPlan`,
so a model whose params + optimizer state exceed one chip's HBM
trains by borrowing the group's aggregate capacity.

Execution model (and why it is bit-exact): each epoch is ONE
``shard_map`` over the ``("shard",)`` mesh. Every member all-gathers
the sharded leaves to full tensors, runs the *identical* per-trial
scan the serial Program runs (data movement only — gathers reorder no
arithmetic), then re-slices its own 1/width of the updated state.
Compute is intentionally replicated (ZeRO-3 with a replicated batch):
the lane exists for HBM capacity, not step-time scaling, and the
redundancy buys the property everything downstream leans on — a
width-w epoch is **bit-identical** to width-w' and to the serial loop
(pinned by tests/test_shard.py, and what lets chip-loss recovery at
reduced width match an unfaulted run exactly). A dp mesh still
composes per-member for real batch scaling; that is the documented
follow-on (docs/sharding.md).

State placement never materializes the full tree on one host: init is
jitted with sharded ``out_shardings`` (each member initializes its
slice), restores arrive pre-sharded from shard/checkpoint.py, and the
one sanctioned gather (trial completion) lives there too.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Hashable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax

from rafiki_tpu import telemetry
from rafiki_tpu.obs.health import sentinel as _sentinel
from rafiki_tpu.ops.train import (_make_step_fns, device_dataset_cap_bytes,
                                  get_program, mesh_cache_key)
from rafiki_tpu.shard.plan import ShardPlan, group_mesh, path_str


class GroupAborted(RuntimeError):
    """A group member was lost; the epoch loop stopped at the epoch
    boundary AFTER that epoch's checkpoint went durable. ``epoch`` is
    the last completed (and checkpointed) epoch — resume restores it
    and continues at ``epoch + 1``, at whatever width survives."""

    def __init__(self, epoch: int):
        super().__init__(f"sharded trial aborted after epoch {epoch}")
        self.epoch = int(epoch)


def sharded_program_key(program_key: Hashable, width: int,
                        dynamic_lr: bool) -> Hashable:
    """Cache key for a group-sharded program. The leading tag keeps the
    namespace disjoint from serial keys and ``("packed", ...)`` keys by
    construction (same pattern as ops.train.packed_program_key)."""
    return ("sharded", int(width), program_key, bool(dynamic_lr))


class _ShardedProgram:
    """The compiled, trial-independent half of a sharded loop: jit'd
    (donated) epoch/eval/init callables plus the per-leaf sharding
    tables. Cached process-wide via ops.train.get_program under a
    ``("sharded", ...)`` key, like any Program."""

    def __init__(self, init_fn, apply_fn, loss_fn,
                 optimizer: optax.GradientTransformation, mesh,
                 plan: ShardPlan, dynamic_lr: bool,
                 hyper_keys: Tuple[str, ...]):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.plan = plan
        self.optimizer = optimizer
        width = int(mesh.devices.size)
        self.width = width
        train_step, eval_step, predict, init_all = _make_step_fns(
            init_fn, apply_fn, loss_fn, optimizer, dynamic_lr)

        def make_state(init_rng, rng, hyper_dev):
            params, opt_state = init_all(init_rng)
            return (params, opt_state, jnp.zeros((), jnp.int32), rng,
                    hyper_dev)

        probe_rng = jax.random.PRNGKey(0)
        probe_hyper = {k: jnp.float32(0.0) for k in hyper_keys}
        abs_state = jax.eval_shape(make_state, probe_rng, probe_rng,
                                   probe_hyper)
        axes = plan.axes_map(abs_state)
        spec_state = plan.spec_tree(abs_state)
        self.state_sharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_state,
            is_leaf=lambda x: isinstance(x, P))
        self.replicated = NamedSharding(mesh, P())

        def gather(local):
            def g(path, x):
                a = axes.get(path_str(path))
                if a is None:
                    return x
                return jax.lax.all_gather(x, "shard", axis=a, tiled=True)

            return jax.tree_util.tree_map_with_path(g, local)

        def reslice(full):
            i = jax.lax.axis_index("shard")

            def s(path, x):
                a = axes.get(path_str(path))
                if a is None:
                    return x
                size = x.shape[a] // width
                return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis=a)

            return jax.tree_util.tree_map_with_path(s, full)

        # Per-member epoch body: gather -> the EXACT serial scan
        # (ops.train.Program.train_epoch's body) -> reslice. X/Y/idx/
        # poison are replicated (in_specs P()), so every member runs
        # the full serial computation — see the module docstring for
        # why that redundancy is the point.
        def train_epoch(state, X, Y, idx, poison):
            full = gather(state)

            def body(st, xs):
                ib, pz = xs
                batch = {"x": jnp.take(X, ib, axis=0),
                         "y": jnp.take(Y, ib, axis=0)}
                if pz is not None:
                    batch["_health_poison"] = pz
                return train_step(st, batch)

            full, ms = jax.lax.scan(body, full, (idx, poison))
            rest, health = _sentinel.split(ms)
            out = {k: v[-1] for k, v in rest.items()}
            out.update(_sentinel.reduce_epoch(health))
            return reslice(full), out

        def eval_epoch(state, X, Y, idx):
            params = gather(state)[0]

            def body(carry, ib):
                batch = {"x": jnp.take(X, ib, axis=0),
                         "y": jnp.take(Y, ib, axis=0)}
                c, n = eval_step(params, batch)
                return (carry[0] + c, carry[1] + n), None

            zero = jnp.zeros((), jnp.int32)
            (c, n), _ = jax.lax.scan(body, (zero, zero), idx)
            return c, n

        P0 = P()
        self.train_epoch = jax.jit(
            jax.shard_map(train_epoch, mesh=mesh,
                          in_specs=(spec_state, P0, P0, P0, P0),
                          out_specs=(spec_state, P0), check_vma=False),
            donate_argnums=(0,))
        self.eval_epoch = jax.jit(
            jax.shard_map(eval_epoch, mesh=mesh,
                          in_specs=(spec_state, P0, P0, P0),
                          out_specs=(P0, P0), check_vma=False))
        self.init = jax.jit(make_state, out_shardings=self.state_sharding)


class ShardedTrainLoop:
    """Drives epochs of one group-sharded trial.

    Same constructor contract as ops.train.TrainLoop where it applies;
    differences: ``devices`` (the group members, their count is the
    width) replaces ``mesh``, a :class:`ShardPlan` pins the placement,
    and ``packing_key`` (the repr of the scheduler's ``("sharded",
    family, width)`` bucket key) rides the perf records so the train
    twin can calibrate group samples separately.
    """

    def __init__(self, init_fn, apply_fn, loss_fn, optimizer=None,
                 devices=None, seed: int = 0,
                 hyper: Optional[Dict[str, float]] = None,
                 program_key: Optional[Hashable] = None,
                 plan: Optional[ShardPlan] = None,
                 packing_key: Optional[str] = None,
                 initial_state=None):
        if not devices:
            raise ValueError("ShardedTrainLoop needs the group's devices")
        self.devices = list(devices)
        self.width = len(self.devices)
        self.mesh = group_mesh(self.devices)
        self.plan = plan if plan is not None else ShardPlan(width=self.width)
        if self.plan.width != self.width:
            raise ValueError(f"plan width {self.plan.width} != group width "
                             f"{self.width}")
        self.packing_key = packing_key
        dynamic_lr = hyper is not None and "lr" in hyper
        if optimizer is None:
            optimizer = optax.scale_by_adam() if dynamic_lr else optax.adam(1e-3)
        hyper_keys = tuple(sorted(hyper or {}))

        def build() -> _ShardedProgram:
            return _ShardedProgram(init_fn, apply_fn, loss_fn, optimizer,
                                   self.mesh, self.plan, dynamic_lr,
                                   hyper_keys)

        if program_key is not None:
            self._perf_key = (sharded_program_key(program_key, self.width,
                                                  dynamic_lr),
                              mesh_cache_key(self.mesh))
            self.program = get_program(self._perf_key, build)
        else:
            self._perf_key = ("sharded", "anon", id(self))
            self.program = build()
        self.optimizer = self.program.optimizer

        if initial_state is not None:
            self.adopt(initial_state)
            return
        hyper_dev = {k: jnp.float32(v) for k, v in (hyper or {}).items()}
        rng = jax.random.PRNGKey(seed)
        rng, init_rng = jax.random.split(rng)
        self.state = self.program.init(init_rng, rng, hyper_dev)

    @property
    def params(self):
        return self.state[0]

    def adopt(self, state) -> None:
        """Adopt a full state (a reshard-restore's output, or host
        arrays) — re-placed under the group shardings if needed."""
        self.state = jax.device_put(state, self.program.state_sharding)

    def _device_dataset(self, dataset):
        """(x, y) replicated across the group, cached per mesh on the
        dataset object (same idiom as ops.train.get_device_dataset)."""
        cache = dataset.__dict__.setdefault("_shard_device_arrays", {})
        key = mesh_cache_key(self.mesh)
        if key not in cache:
            cache[key] = (
                jax.device_put(np.asarray(dataset.x), self.program.replicated),
                jax.device_put(np.asarray(dataset.y), self.program.replicated))
        return cache[key]

    def _check_dataset(self, dataset, batch_size: int) -> None:
        if dataset.size < batch_size:
            raise ValueError(
                f"Dataset has {dataset.size} examples < batch_size="
                f"{batch_size}; the epoch would run zero steps")
        if getattr(dataset, "mask", None) is not None:
            raise NotImplementedError(
                "sharded loop runs the device-resident scan path only; "
                "masked (corpus) datasets are not supported")
        if dataset.x.nbytes + dataset.y.nbytes > device_dataset_cap_bytes():
            raise NotImplementedError(
                "sharded loop requires a device-resident dataset "
                "(RAFIKI_DEVICE_DATASET_MAX_MB)")

    def run_epoch(self, dataset, batch_size: int,
                  epoch_seed: int) -> Dict[str, float]:
        """One epoch over the group. Same shuffle derivation, poison
        column and metric shape as the serial fast path — the bit-parity
        contract."""
        self._check_dataset(dataset, batch_size)
        import os as _os

        from rafiki_tpu import chaos as _chaos

        # Collective chaos site, same keying as the dp path: a kill
        # lands while the group is inside (or entering) its gathers.
        _chaos.hook("collective.step",
                    key=f"p{jax.process_index()}:"
                        f"{_os.environ.get('RAFIKI_WORKER_ID', '')}")
        t_epoch = time.monotonic()
        _chaos.hook("train.epoch", key=str(self._perf_key))
        n_steps = dataset.size // batch_size
        poison = self._chaos_poison(n_steps)
        X, Y = self._device_dataset(dataset)
        perm = np.random.default_rng(epoch_seed).permutation(dataset.size)
        idx = perm[: n_steps * batch_size].reshape(
            n_steps, batch_size).astype(np.int32)
        if not getattr(self, "_warm", False):
            from rafiki_tpu.obs.perf import profiler as _profiler

            _profiler.capture_cost(self._perf_key, self.program.train_epoch,
                                   self.state, X, Y, idx, poison,
                                   kind="sharded")
        self.state, metrics = self.program.train_epoch(
            self.state, X, Y, idx, poison)
        out = {k: float(v) for k, v in metrics.items()
               if not k.startswith(_sentinel.PREFIX)}
        self._record_epoch(t_epoch)
        return out

    def _chaos_poison(self, n_steps: int) -> np.ndarray:
        from rafiki_tpu import chaos as _chaos

        poison = np.ones(n_steps, np.float32)
        if (_chaos.active() is not None
                and _chaos.hook("train.nan",
                                key=str(self._perf_key)) is not None):
            poison[n_steps // 2] = np.nan
        return poison

    def _record_epoch(self, t0: float) -> None:
        from rafiki_tpu.obs.ledger import ledger
        from rafiki_tpu.obs.perf import profiler, slo

        # lint: disable=RF007 — epoch wall split into ledger buckets
        dt = time.monotonic() - t0
        cold = not getattr(self, "_warm", False)
        self._warm = True
        telemetry.observe("train.cold_epoch_s" if cold else "train.epoch_s",
                          dt)
        telemetry.set_gauge("shard.group_width", self.width)
        ledger.add("compile_s" if cold else "step_s", dt)
        profiler.note_epoch(self._perf_key, dt, cold=cold, kind="sharded",
                            packing_key=self.packing_key,
                            group_width=self.width)
        slo.maybe_tick()

    def evaluate(self, dataset, batch_size: int) -> float:
        """Full-batch accuracy over the group (the remainder rows are
        dropped — exact scoring goes through the detached serial loop
        installed at trial completion)."""
        self._check_dataset(dataset, batch_size)
        X, Y = self._device_dataset(dataset)
        n_steps = dataset.size // batch_size
        idx = np.arange(n_steps * batch_size, dtype=np.int32).reshape(
            n_steps, batch_size)
        c, n = self.program.eval_epoch(self.state, X, Y, idx)
        return int(c) / max(int(n), 1)


def train_sharded(model, dataset_uri: str, devices,
                  plan: Optional[ShardPlan] = None,
                  checkpoint_sink=None, abort=None,
                  resume_from=None) -> Tuple["ShardedTrainLoop",
                                             List[Dict[str, float]]]:
    """Train one JaxModel template as a group-sharded trial — the
    sharded-lane analog of ``JaxModel.train``.

    * ``checkpoint_sink(epoch, loop)`` fires after every epoch with the
      live loop; the sink decides cadence and calls
      ``shard.checkpoint.save_sharded(store, trial_id, epoch,
      loop.state, loop.width)`` itself (the sharded analog of the
      serial ``_ckpt_sink(epoch, dump_checkpoint)`` contract).
    * ``abort`` (threading.Event) is checked at each epoch boundary
      AFTER the sink ran — a set flag raises :class:`GroupAborted`
      with the last durable epoch, the group-loss ordering contract.
    * ``resume_from=(params_store, trial_id)`` restores the newest
      sharded checkpoint at THIS group's width via reshard-on-restore
      and continues after its epoch.

    On completion the model gets a detached serial TrainLoop holding
    the gathered final state, so ``evaluate``/``dump_parameters``/
    ``predict`` behave exactly as after a serial ``train()``. Returns
    ``(loop, per-epoch metrics history)``.
    """
    from rafiki_tpu.model.log import logger
    from rafiki_tpu.shard import checkpoint as shard_ckpt

    ds = model._prepared_dataset(dataset_uri)
    model._dataset_meta = dict(ds.meta)
    num_classes, input_shape = model._dataset_arch(ds)
    model._planned_steps = model.epochs * max(1, ds.size // model.batch_size)
    fns = model._loop_fns(num_classes, input_shape)
    model._module = fns["module"]
    model._arch = (num_classes, tuple(input_shape))
    if plan is None:
        plan = ShardPlan(width=len(devices), family=type(model).__name__)
    pk_repr = repr(("sharded", type(model).__name__, plan.width))
    loop = ShardedTrainLoop(
        fns["init_fn"], fns["apply_eval"], fns["loss_fn"], fns["optimizer"],
        devices=devices, seed=model._seed, hyper=fns["hyper"],
        program_key=fns["program_key"], plan=plan, packing_key=pk_repr)

    start_epoch = 0
    if resume_from is not None:
        store, trial_id = resume_from
        latest = store.latest_checkpoint(trial_id)
        if latest is not None and shard_ckpt.is_manifest(latest[1]):
            state = shard_ckpt.restore_sharded(store, latest[1], loop.state,
                                               loop.mesh, plan)
            loop.adopt(state)
            start_epoch = int(latest[0]) + 1

    history: List[Dict[str, float]] = []
    logger.define_plot("Training", ["loss", "acc"], x_axis="epoch")
    for epoch in range(start_epoch, model.epochs):
        metrics = loop.run_epoch(ds, model.batch_size,
                                 epoch_seed=model._seed + epoch)
        logger.log(epoch=epoch, **metrics)
        history.append(dict(metrics, epoch=epoch))
        model._epochs_done = epoch
        if checkpoint_sink is not None:
            checkpoint_sink(epoch, loop)
        if abort is not None and abort.is_set():
            raise GroupAborted(epoch)
    # Completion hand-off: the ONE sanctioned gather — install the
    # final state into a serial loop so scoring/serving run unchanged.
    from rafiki_tpu.ops.train import TrainLoop

    host_state = shard_ckpt.gather_state(loop.state)
    model._loop = TrainLoop(
        fns["init_fn"], fns["apply_eval"], fns["loss_fn"], fns["optimizer"],
        mesh=None, seed=model._seed, hyper=fns["hyper"],
        program_key=fns["program_key"], initial_state=host_state)
    return loop, history
