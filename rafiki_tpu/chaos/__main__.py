"""CLI: ``python -m rafiki_tpu.chaos run <scenario>|all`` / ``list``.

Runs recovery scenarios against an in-proc cluster and exits nonzero
on any failed invariant — the entrypoint operators use to replay a
fault schedule deterministically (tests/test_chaos.py runs the
scenarios through the runner's Python API).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    # Before ANYTHING imports jax (analysis rule RF001): scenario
    # clusters run on whatever platform the env pins — CPU in CI.
    from rafiki_tpu.utils.backend import ensure_host_device_count, honor_env_platform

    honor_env_platform()
    # Mesh scenarios (docs/mesh_sweep.md) need a multi-chip pod; on the
    # CPU fake this is 8 virtual devices, same as the test suite.
    ensure_host_device_count(8)

    from rafiki_tpu.chaos.runner import (
        SCENARIOS, format_report, run_scenarios)

    parser = argparse.ArgumentParser(
        prog="python -m rafiki_tpu.chaos",
        description="Deterministic fault-injection scenario runner")
    sub = parser.add_subparsers(dest="command", required=True)
    sub.add_parser("list", help="list scenarios")
    runp = sub.add_parser("run", help="run scenarios")
    runp.add_argument("scenarios", nargs="+",
                      help="scenario names, or 'all'")
    runp.add_argument("--json", action="store_true",
                      help="machine-readable reports on stdout")
    args = parser.parse_args(argv)

    if args.command == "list":
        for name in sorted(SCENARIOS):
            print(f"{name}\n    {SCENARIOS[name].description}")
        return 0

    names = (sorted(SCENARIOS) if args.scenarios == ["all"]
             else args.scenarios)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        print(f"unknown scenarios: {unknown}; "
              f"known: {sorted(SCENARIOS)}", file=sys.stderr)
        return 2
    reports = run_scenarios(names)
    if args.json:
        print(json.dumps([r.to_dict() for r in reports], indent=2))
    else:
        for r in reports:
            print(format_report(r))
    failed = [r.name for r in reports if not r.passed]
    if failed:
        print(f"\nFAILED: {failed}", file=sys.stderr)
        return 1
    print(f"\nall {len(reports)} scenario(s) passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
