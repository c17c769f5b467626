#!/usr/bin/env python3
"""The control of the ``ouro`` cells' comparison: ``lm_control.py`` with the
reference that knows the loop (``drivers/ouro_sweep.py::LoopedReference``) and
two stand-ins of its own.

    python3 benchmark/ouro_control.py --workload <name> --seeds 11,12 [--first-step-only]

For each seed one trial's knobs are drawn over the configuration's ranges,
and the reference is put in the program's place with ``control.STAND_INS``
(float8 products, half a batch, a state left unchanged, a score altered,
nothing altered) and with this model's own:

``three_passes``  the stack run three times where the configuration says four
``uniform_exit``  the exit weights held at 1 / R, so that no gate moves them

and compared with itself in float32 under the cell's limits.
``--first-step-only`` follows the first-step trial alone and reads
``first_step_flips`` and ``first_loss_gap`` (what each of them but the altered
score is caught by), as ``lfm2_control.py`` does and for its reason. The
benchmark's own runs never call this file;
``benchmark/tests/test_ouro_cell_cpu.py`` keeps it at a tiny size.
"""

from __future__ import annotations

import contextlib
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (str(HERE), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import lfm2_control  # noqa: E402
from control import STAND_INS as _SHARED  # noqa: E402

STAND_INS = dict(_SHARED, three_passes={"fault": "three_passes"},
                 uniform_exit={"fault": "uniform_exit"})


def _as_this_cells():
    """``lfm2_control``'s ``readings`` and ``main`` build their reference from
    ``lfm2_sweep.TiedReference`` and take their stand-ins from that module's
    ``STAND_INS`` when they are called (accepted files, not this PR's to give
    parameters): for the time of a call both hold this cell's."""
    from unittest import mock

    from drivers import lfm2_sweep, ouro_sweep

    stack = contextlib.ExitStack()
    stack.enter_context(mock.patch.object(lfm2_sweep, "TiedReference",
                                          ouro_sweep.LoopedReference))
    stack.enter_context(mock.patch.object(lfm2_control, "STAND_INS", STAND_INS))
    return stack


def readings(cfg: dict, seed: int, limits: dict, log=lambda s: None,
             stand_ins=tuple(STAND_INS), first_step_only: bool = False) -> dict:
    """{stand-in: {"correct", "numbers": {name: value}}} for one seed."""
    with _as_this_cells():
        return lfm2_control.readings(cfg, seed, limits, log, stand_ins, first_step_only)


def main(argv=None) -> int:
    with _as_this_cells():
        return lfm2_control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
