#!/usr/bin/env python3
"""The control of the ``lfm2_moe`` cells' comparison: ``lm_control.py`` with the
reference whose table is tied (``drivers/lfm2_sweep.py::TiedReference``).

    python3 benchmark/lfm2_control.py --workload <name> --seeds 11,12 [--first-step-only]

For each seed one trial's knobs are drawn over the configuration's ranges,
and the reference is put in the program's place with ``control.STAND_INS``
(float8 products, half a batch, a state left unchanged, a score altered,
nothing altered) and compared with itself in float32 under the cell's
limits. ``--first-step-only`` follows the first-step trial alone and reads
``first_step_flips`` and ``first_loss_gap`` (what the lower precision and
half a batch are caught by): a whole trial of sixteen float32 steps, with its
host copies, is more than the chip's host holds beside them (PERF.md section
6, PR 27). The benchmark's own runs never call this file;
``benchmark/tests/test_lfm2_cell_cpu.py`` keeps it at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (str(HERE), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from control import STAND_INS  # noqa: E402


def readings(cfg: dict, seed: int, limits: dict, log=lambda s: None,
             stand_ins=tuple(STAND_INS), first_step_only: bool = False) -> dict:
    """{stand-in: {"correct", "numbers": {name: value}}} for one seed."""
    import numpy as np

    import check
    import lm_check
    from drivers import lfm2_sweep

    model_seed = seed & 0x7FFFFFFF
    knobs = check.draw_knobs(cfg, np.random.default_rng(seed))
    ref = lfm2_sweep.TiedReference(cfg, seed, model_seed)
    out = {"seed": seed, "knobs": knobs}
    if first_step_only:
        ref.build()
        # (on the host: the device has room for one float32 step's sets at a time)
        p1, losses1 = ref.train(knobs, first_step=True)
        init, grad = ref.init_params(), ref.first_gradient
    for name in stand_ins:
        if first_step_only:
            how = {k: v for k, v in STAND_INS[name].items() if k != "score_offset"}
            q1, losses = ref.train(knobs, first_step=True, **how)
            stored = {k: check.bf16_round(v) for k, v in q1.items()}
            del q1
            numbers = {
                "first_step_flips": lm_check.first_step_flips(init, p1, stored, grad,
                                                              ref.pieces())[0],
                "first_loss_gap": abs(float(losses[0]) - float(losses1[0])) / float(losses1[0])}
            correct = all(numbers[k] <= limits[k] for k in numbers)
            del stored
        else:
            trial, first = lm_check.stand_in_trials(ref, knobs, **STAND_INS[name])
            verdict = lm_check.compare(cfg, seed, model_seed, trial, first, limits, log, ref=ref)
            correct = verdict["correct"]
            numbers = {k: n["value"] for k, n in verdict["numbers"].items()}
        out[name] = {"correct": bool(correct), "numbers": numbers}
        log(f"seed {seed} {name}: correct={correct} {numbers}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--stand-ins", default=",".join(STAND_INS))
    ap.add_argument("--first-step-only", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run

    def log(text):
        print(f"[control] {text}", file=sys.stderr, flush=True)

    _manifest, cell, cfg, _traffic = run.load_cell(args.workload)
    run.place_compile_cache()
    rows = [readings(cfg, int(s), cell["limits"], log, tuple(args.stand_ins.split(",")),
                     args.first_step_only) for s in args.seeds.split(",")]
    for r in rows:
        print(json.dumps(r), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
