#!/usr/bin/env python3
"""The control of the ``keye_vl2`` cells' comparison: ``lfm2_control.py``'s
readings with the reference whose layers carry the indexer's loss
(``drivers/keye_sweep.py::DsaReference``), three stand-ins of its own and
``selection_gap`` beside the first step's numbers.

    python3 benchmark/keye_control.py --workload <name> --seeds 11,12 [--first-step-only]

For each seed one trial's knobs are drawn over the configuration's ranges,
and the reference is put in the program's place with ``control.STAND_INS``
(float8 products, half a batch, a state left unchanged, a score altered,
nothing altered) and with this model's own:

``topk_half``      each query selects 1,024 keys where the configuration says 2,048
``dense``          every key at or before a query attended to, the selection ignored
``no_index_loss``  the indexer's loss left out (the indexer never learns)

and compared with itself in float32 under the cell's limits.
``selection_gap`` is the stand-in's selection of the sound reference's
first-step layer inputs against the sound reference's own.
``--first-step-only`` follows the first-step trial alone (``first_step_flips``,
``first_loss_gap``, ``selection_gap``), as ``lfm2_control.py`` does and for
its reason. The benchmark's own runs never call this file;
``benchmark/tests/test_keye_cell_cpu.py`` keeps it at a tiny size.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (str(HERE), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from control import STAND_INS as _SHARED  # noqa: E402

STAND_INS = dict(_SHARED, topk_half={"fault": "topk_half"}, dense={"fault": "dense"},
                 no_index_loss={"fault": "no_index_loss"})


def selection_gap(ref, knobs: dict, quant=None, fault=None) -> float:
    """The stand-in's selection of the sound reference's first-step layer
    inputs, against the sound reference's own: the pairs in one of them
    alone over twice the sound selection's (``compare.selection``)."""
    import jax
    import jax.numpy as jnp

    import lm_check
    from drivers.keye_sweep import KIND

    ref.fault = None
    sound = ref.pieces()
    ref.fault = fault if fault in ref.mod.FAULTS else None
    stand_in = ref.pieces(quant)
    ref.fault = None
    p = ref.init_device()
    x = jnp.asarray(ref.first_set[0][:1])
    h, differ, twice = ref.mod.embed(p, x), 0, 0
    for i in range(1, len(sound.kinds) + 1):
        lp = lm_check._of_layer(p, i)
        a, b = jax.device_get(sound.run("compare.selection", sound.run("select", lp, h),
                                        stand_in.run("select", lp, h).astype(jnp.int8)))
        differ, twice = differ + int(a), twice + int(b)
        h, _loss = sound.run(f"{KIND}.fwd", lp, h)
    return differ / max(twice, 1)


def readings(cfg: dict, seed: int, limits: dict, log=lambda s: None,
             stand_ins=tuple(STAND_INS), first_step_only: bool = False) -> dict:
    """{stand-in: {"correct", "numbers": {name: value}}} for one seed."""
    import numpy as np

    import check
    import lm_check
    from drivers.keye_sweep import DsaReference

    model_seed = seed & 0x7FFFFFFF
    knobs = check.draw_knobs(cfg, np.random.default_rng(seed))
    ref = DsaReference(cfg, seed, model_seed)
    out = {"seed": seed, "knobs": knobs}
    if first_step_only:
        ref.build()
        p1, losses1 = ref.train(knobs, first_step=True)
        init, grad = ref.init_params(), ref.first_gradient
    for name in stand_ins:
        how = {k: v for k, v in STAND_INS[name].items() if k != "score_offset"}
        if first_step_only:
            q1, losses = ref.train(knobs, first_step=True, **how)
            stored = {k: check.bf16_round(v) for k, v in q1.items()}
            del q1
            numbers = {
                "first_step_flips": lm_check.first_step_flips(init, p1, stored, grad,
                                                              ref.pieces())[0],
                "first_loss_gap": abs(float(losses[0]) - float(losses1[0])) / float(losses1[0])}
            del stored
        else:
            trial, first = lm_check.stand_in_trials(ref, knobs, **STAND_INS[name])
            verdict = lm_check.compare(cfg, seed, model_seed, trial, first, limits, log, ref=ref)
            numbers = {k: n["value"] for k, n in verdict["numbers"].items()}
        numbers["selection_gap"] = selection_gap(ref, knobs, **how)
        correct = all(numbers[k] <= limits[k] for k in numbers)
        out[name] = {"correct": bool(correct), "numbers": numbers}
        log(f"seed {seed} {name}: correct={correct} {numbers}")
    return out


def main(argv=None) -> int:
    """``lfm2_control.main`` with this cell's readings."""
    from unittest import mock

    import lfm2_control

    with mock.patch.object(lfm2_control, "readings", readings), \
            mock.patch.object(lfm2_control, "STAND_INS", STAND_INS):
        return lfm2_control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
