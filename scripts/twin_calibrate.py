#!/usr/bin/env python
"""Extract a versioned twin calibration bundle from a journal dir.

    python scripts/twin_calibrate.py /path/to/journals -o twin_cal.json

Reads the merged ``journal-*.jsonl`` rings under the directory and
distills the three ingredients the simulator needs — hop-segment
sample distributions (``serving/hops``), the live gateway knobs
(``gateway/config``) and XLA cost rows (``perf/cost``) — into one
``calibration_version``-stamped JSON the twin CLI and tests load
byte-reproducibly.

With ``--train`` the TRAIN twin's bundle is extracted instead:
per-(packing_key, k) epoch samples (``perf/step``), the captured pack
placement (``mesh/pack_formed``) and sweep shape, fitted epoch
overhead, and cost rows (docs/twin.md). The usual fix for a missing-
kinds failure there is a mesh sweep run with ``RAFIKI_LOG_DIR`` set.

Fails LOUDLY (exit 2) listing every missing record kind rather than
defaulting anything: a twin calibrated on air predicts air. The usual
fix is re-running the workload with ``RAFIKI_LOG_DIR`` pointed at a
fresh directory.

Exit codes: 0 bundle written, 2 calibration impossible (missing
kinds / unreadable dir), plus a summary line on stdout either way.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rafiki_tpu.obs.twin.calibration import Calibration, CalibrationError


def main(argv: Optional[List[str]] = None) -> int:
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()  # a CPU request lands before the first backend use
    p = argparse.ArgumentParser(
        prog="scripts/twin_calibrate.py",
        description="journal dir -> versioned twin calibration bundle")
    p.add_argument("log_dir", help="journal directory (RAFIKI_LOG_DIR "
                                   "of a captured serving run)")
    p.add_argument("-o", "--out", default="twin_cal.json",
                   help="bundle path (default twin_cal.json)")
    p.add_argument("--json", action="store_true",
                   help="emit the summary as JSON instead of prose")
    p.add_argument("--train", action="store_true",
                   help="extract the TRAIN twin's bundle (perf/step + "
                        "mesh/pack_formed) instead of the serving one")
    args = p.parse_args(argv)

    if args.train:
        return _main_train(args)

    try:
        cal = Calibration.from_journal_dir(args.log_dir)
    except CalibrationError as e:
        if args.json:
            print(json.dumps({"error": str(e), "missing": e.missing,
                              "source": e.source}))
        else:
            print(f"twin_calibrate: {e}", file=sys.stderr)
        return 2
    except OSError as e:
        print(f"twin_calibrate: cannot read {args.log_dir}: {e}",
              file=sys.stderr)
        return 2

    cal.save(args.out)
    summary = {
        "out": args.out,
        "calibration_version": cal.version,
        "source": cal.source,
        "workers": cal.workers,
        "segments": {s: len(xs) for s, xs in sorted(cal.segments.items())},
        "cost_rows": len(cal.cost),
        "gateway_knobs": len(cal.gateway),
    }
    if args.json:
        print(json.dumps(summary))
    else:
        segs = ", ".join(f"{s}:{n}" for s, n in summary["segments"].items())
        print(f"wrote {args.out}: v{cal.version} bundle from "
              f"{cal.source} — {cal.workers} worker(s), "
              f"{summary['cost_rows']} cost row(s), samples [{segs}]")
    return 0


def _main_train(args) -> int:
    from rafiki_tpu.obs.twin.train.calibration import (TrainCalibration,
                                                       TrainCalibrationError)
    try:
        cal = TrainCalibration.from_journal_dir(args.log_dir)
    except TrainCalibrationError as e:
        if args.json:
            print(json.dumps({"error": str(e), "missing": e.missing,
                              "source": e.source}))
        else:
            print(f"twin_calibrate: {e}", file=sys.stderr)
        return 2
    except OSError as e:
        print(f"twin_calibrate: cannot read {args.log_dir}: {e}",
              file=sys.stderr)
        return 2

    cal.save(args.out)
    summary = {
        "out": args.out,
        "train_calibration_version": cal.version,
        "source": cal.source,
        "packing_keys": len(cal.packing_keys()),
        "packs": len(cal.packs),
        "sweep": cal.sweep,
        "epoch_overhead_s": round(cal.epoch_overhead_s, 6),
        "cost_rows": len(cal.cost),
    }
    if args.json:
        print(json.dumps(summary))
    else:
        print(f"wrote {args.out}: v{cal.version} train bundle from "
              f"{cal.source} — {summary['packing_keys']} packing key(s), "
              f"{summary['packs']} pack(s), "
              f"overhead {summary['epoch_overhead_s']}s/epoch, "
              f"{summary['cost_rows']} cost row(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
