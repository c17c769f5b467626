"""Sweep-supervisor process entrypoint:
``python -m rafiki_tpu.scheduler.sweep_proc run|resume ...``.

The mesh sweep normally runs in the caller's process. Crash-safety
testing needs it in a process OF ITS OWN, so a chaos fault (the
``supervisor.tick`` kill site, a whole-host loss) can SIGKILL the
supervisor without taking the test harness down with it — and so
``resume_sweep`` can then prove a genuinely fresh process (no shared
memory, only the MetaStore + sweep WAL + journals) adopts the job.
The chaos scenarios (chaos/scenarios.py) and tests/test_recovery.py
drive sweeps through this module; it is equally usable as a manual
supervisor launcher.

Modes::

    run     --db X --params Y --job J [--chips N] [--trials-per-chip K]
            [--advisor KIND] [--advisor-kwargs JSON]
    resume  --db X --params Y --job J [--chips N] [--trials-per-chip K]
            [--stale-after-s S]

Chaos/observability propagation is by environment, same contract as
every other subprocess in the tree: ``RAFIKI_CHAOS`` self-installs at
import, ``RAFIKI_LOG_DIR`` points the journal, ``RAFIKI_EVENTS_DIR``
the event sink. Exit codes: 0 = job COMPLETED, 2 = any other terminal
status, 1 = crash (including a WAL reconcile refusal on resume).

The final line on stdout is a JSON summary (status, trial count, and
for resume the adopt/salvage accounting) — drivers parse that instead
of scraping the store again.
"""

from __future__ import annotations

import json
import os
import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    import argparse

    ap = argparse.ArgumentParser(prog="rafiki_tpu.scheduler.sweep_proc")
    ap.add_argument("mode", choices=("run", "resume"))
    ap.add_argument("--db", required=True)
    ap.add_argument("--params", required=True)
    ap.add_argument("--job", required=True)
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--trials-per-chip", type=int, default=None)
    ap.add_argument("--advisor", default="gp")
    ap.add_argument("--advisor-kwargs", default=None,
                    help="JSON dict of engine kwargs, e.g. "
                         '\'{"n_initial": 4}\'')
    ap.add_argument("--stale-after-s", type=float, default=None)
    args = ap.parse_args(argv)

    # Platform pinning must precede any jax import (analysis RF001);
    # a CPU run needs enough virtual devices BEFORE the backend
    # initializes, or a --chips 2 sweep silently degrades to one chip.
    from rafiki_tpu.utils.backend import ensure_host_device_count, honor_env_platform

    ensure_host_device_count(max(8, int(args.chips or 0)))
    honor_env_platform()

    from rafiki_tpu.obs import journal as journal_mod
    from rafiki_tpu.store import MetaStore, ParamsStore
    from rafiki_tpu.utils.events import configure_from_env as _events_env

    journal_mod.configure_from_env(role=f"sweep-{args.mode}")
    _events_env()
    store = MetaStore(args.db)
    params = ParamsStore(args.params)

    if args.mode == "run":
        from rafiki_tpu.scheduler.mesh import MeshSweepScheduler

        kwargs = json.loads(args.advisor_kwargs) if args.advisor_kwargs \
            else None
        sched = MeshSweepScheduler(store, params)
        result = sched.run_sweep(
            args.job, chips=args.chips,
            trials_per_chip=int(args.trials_per_chip or 2),
            advisor_kind=args.advisor, advisor_kwargs=kwargs)
        out = {"mode": "run", "job_id": args.job, "status": result.status,
               "n_trials": len(result.trials),
               "errors": result.errors}
        print(json.dumps(out))
        return 0 if result.status == "COMPLETED" else 2

    from rafiki_tpu.scheduler.recovery import resume_sweep

    summary = resume_sweep(
        store, params, args.job, chips=args.chips,
        trials_per_chip=args.trials_per_chip,
        stale_after_s=args.stale_after_s)
    job = store.get_train_job(args.job)
    summary["status"] = None if job is None else job["status"]
    print(json.dumps({"mode": "resume", **summary}, default=str))
    return 0 if summary["status"] == "COMPLETED" else 2


if __name__ == "__main__":
    sys.exit(main())
