#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, beyond the runs'
own: the control and the planted faults, on the chip at a cell's own size,
each judged by ``check.compare`` as a run's round is.

    python3 benchmark/control.py --workload <name> --seeds 11,12,13
    python3 benchmark/control.py --workload <name> --program-seeds 21,22,23

``--seeds``: for each seed a round of ``--members`` trials is drawn over
the configuration's ranges, and the reference is put in the program's place
(``check.stand_in_rounds``) and compared with itself in float32:

``fp8``             every matmul input in float8_e4m3: the control, the
                    step below the stated bfloat16
``half_batch``      half of every batch left out, the mean over the rest
``state_unchanged`` a step that returns its state unchanged
``score_altered``   an answer altered where it is produced (+0.05)
``reference_again`` nothing altered: what the numbers read when nothing
                    differs (0 unless the chip's float32 is not reproducible)

``--program-seeds``: the program's own readings on many seeds in one
process (set-up is most of a run): ``run.py``'s whole path, driver and
comparison, with a window of one round (a budget of 8 s) and the warm-up
round only before the first. The benchmark's own runs never call this file;
``tests/test_run_cpu.py`` keeps it at a tiny size.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (str(HERE), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

STAND_INS = {
    "fp8": {"quant": "fp8"},
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
    "score_altered": {"score_offset": 0.05},
    "reference_again": {},
}


def readings(cfg: dict, seed: int, limits: dict, members: int = 2,
             log=lambda s: None) -> dict:
    """{stand-in: {"correct", "numbers": {name: value}}} for one seed."""
    import numpy as np

    import check

    model_seed = seed & 0x7FFFFFFF
    rng = np.random.default_rng(seed)
    knobs = [check.draw_knobs(cfg, rng) for _ in range(members)]
    follow = check.pick_followed(knobs, rng)
    ref = check.Reference(cfg, seed, model_seed)
    out = {"seed": seed, "knobs": knobs, "followed": follow}
    for name, how in STAND_INS.items():
        members, first = check.stand_in_rounds(ref, knobs, **how)
        verdict = check.compare(cfg, seed, model_seed, members, first, follow,
                                limits, log, ref=ref)
        out[name] = {"correct": verdict["correct"],
                     "numbers": {k: n["value"] for k, n in verdict["numbers"].items()}}
        log(f"seed {seed} {name}: correct={verdict['correct']} {out[name]['numbers']}")
    return out


def program_readings(workload: str, seeds: list, log, platform: str = "tpu",
                     overrides: dict = None) -> list:
    """The program through ``run.main`` on each seed, in this process
    (``platform`` and ``overrides``: for benchmark/tests, as in run.py)."""
    import run

    rows = []
    traffic = (overrides or {}).get("traffic", lambda t: t)
    for i, seed in enumerate(seeds):
        buf = io.StringIO()
        no_warmup = lambda t, i=i: dict(traffic(t), **({"warmup_rounds": 0} if i else {}))
        # (a budget of 8 s: the round it starts is the window's only one)
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       "8", "--trace", "0"], platform=platform,
                      overrides=dict(overrides or {}, out=buf, traffic=no_warmup))
        line = json.loads(buf.getvalue().strip().splitlines()[-1]) if rc == 0 else {}
        rows.append({"seed": seed, "rc": rc, "correct": line.get("correct"),
                     "compared": line.get("compared"),
                     "problems": line.get("problems")})
        log(json.dumps(rows[-1]))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--members", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run

    def log(text):
        print(f"[control] {text}", file=sys.stderr, flush=True)

    rows = []
    if args.program_seeds:
        rows += program_readings(args.workload,
                                 [int(s) for s in args.program_seeds.split(",")], log)
    if args.seeds:
        _manifest, cell, cfg, _traffic = run.load_cell(args.workload)
        run.place_compile_cache()
        for s in args.seeds.split(","):
            rows.append(readings(cfg, int(s), cell["limits"], args.members, log))
    for r in rows:
        print(json.dumps(r), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
