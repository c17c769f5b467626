#!/usr/bin/env python3
"""The control of a language-model cell's comparison (``control.py``'s kind,
with ``lm_check`` where that has ``check``): readings that the limits of
``correct`` are set from, beyond the runs' own.

    python3 benchmark/lm_control.py --workload <name> --seeds 11,12,13

For each seed one trial's knobs are drawn over the configuration's ranges,
and the reference is put in the program's place (``lm_check.stand_in_trials``)
and compared with itself in float32:

``fp8``             every matrix product's inputs in float8_e4m3, the gradient
                    that flows back in e5m2: the step below the stated bfloat16
``half_batch``      half of every batch left out, the mean over the rest
``state_unchanged`` a step that returns its state unchanged
``score_altered``   an answer altered where it is produced (+0.05)
``reference_again`` nothing altered: what the numbers read when nothing
                    differs (0 unless the chip's float32 is not reproducible)

Each must come out not correct on every seed but the last, which must come
out correct. The benchmark's own runs never call this file;
``benchmark/tests/test_lm_cells_cpu.py`` keeps it at a tiny size.
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
             stand_ins=tuple(STAND_INS)) -> dict:
    """{stand-in: {"correct", "numbers": {name: value}}} for one seed."""
    import numpy as np

    import check
    import lm_check

    model_seed = seed & 0x7FFFFFFF
    knobs = check.draw_knobs(cfg, np.random.default_rng(seed))
    ref = lm_check.Reference(cfg, seed, model_seed)
    out = {"seed": seed, "knobs": knobs}
    for name in stand_ins:
        trial, first = lm_check.stand_in_trials(ref, knobs, **STAND_INS[name])
        verdict = lm_check.compare(cfg, seed, model_seed, trial, first, limits,
                                   log, ref=ref)
        out[name] = {"correct": verdict["correct"],
                     "numbers": {k: n["value"] for k, n in verdict["numbers"].items()}}
        log(f"seed {seed} {name}: correct={verdict['correct']} {out[name]['numbers']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--stand-ins", default=",".join(STAND_INS))
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run

    def log(text):
        print(f"[control] {text}", file=sys.stderr, flush=True)

    _manifest, cell, cfg, _traffic = run.load_cell(args.workload)
    run.place_compile_cache()
    rows = [readings(cfg, int(s), cell["limits"], log, tuple(args.stand_ins.split(",")))
            for s in args.seeds.split(",")]
    for r in rows:
        print(json.dumps(r), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
