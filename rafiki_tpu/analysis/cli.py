"""``python -m rafiki_tpu.analysis [paths] [--format json|text]
[--select RF001,RF002] [--show-suppressed]``.

Exit code 0 when every finding is suppressed (with justification), 1
when unsuppressed findings remain, 2 on usage/parse errors —
scripts/check_lint.sh turns that into the tier-1 gate.

``--contracts`` switches to contract-extraction mode: instead of
findings it emits the whole-program contracts manifest (journal
writer/reader joins, env-knob registry, telemetry names) as
byte-deterministic JSON; with ``--docs`` it emits the generated
docs/knobs.md instead. check_lint.sh diffs both against the committed
copies (tests/data/contracts_manifest.json, docs/knobs.md), so
contract drift fails the gate as a reviewable diff.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Sequence

from rafiki_tpu.analysis.core import (
    REGISTRY, AnalysisResult, analyze_paths, load_builtin_checkers)

DEFAULT_PATHS = ["rafiki_tpu", "scripts"]


def _format_text(result: AnalysisResult, show_suppressed: bool) -> List[str]:
    out = []
    for f in result.findings:
        if f.suppressed and not show_suppressed:
            continue
        tag = " (suppressed: %s)" % f.justification if f.suppressed else ""
        out.append(f"{f.path}:{f.line}:{f.col}: {f.checker_id} "
                   f"[{f.severity}] {f.message}{tag}")
    n = len(result.unsuppressed)
    n_sup = len(result.findings) - n
    out.append(f"{result.files_analyzed} files analyzed: {n} finding(s), "
               f"{n_sup} suppressed")
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m rafiki_tpu.analysis",
        description="rafiki-tpu repo-specific static analysis")
    parser.add_argument("paths", nargs="*", default=None,
                        help=f"files/dirs to analyze (default: "
                             f"{' '.join(DEFAULT_PATHS)})")
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--select", default=None,
                        help="comma-separated checker ids to run")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="include suppressed findings in text output")
    parser.add_argument("--list-checkers", action="store_true")
    parser.add_argument("--contracts", action="store_true",
                        help="emit the whole-program contracts manifest "
                             "(deterministic JSON) instead of findings")
    parser.add_argument("--docs", action="store_true",
                        help="with --contracts: emit the generated "
                             "docs/knobs.md instead of the manifest")
    args = parser.parse_args(argv)

    if args.docs and not args.contracts:
        print("--docs requires --contracts", file=sys.stderr)
        return 2
    if args.contracts:
        from rafiki_tpu.analysis.contracts import generate_knobs_md
        from rafiki_tpu.analysis.contracts.envknobs import extract_env
        from rafiki_tpu.analysis.contracts.manifest import (
            _load_modules, dump_manifest, manifest_for_paths)

        paths = args.paths or DEFAULT_PATHS
        if args.docs:
            import os
            env = extract_env(_load_modules(paths, root=os.getcwd()))
            sys.stdout.write(generate_knobs_md(env))
        else:
            sys.stdout.write(dump_manifest(manifest_for_paths(paths)))
        return 0

    load_builtin_checkers()
    if args.list_checkers:
        for cid in sorted(REGISTRY):
            cls = REGISTRY[cid]
            print(f"{cid} {cls.name} [{cls.severity}] — {cls.rationale}")
        return 0

    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    if select:
        unknown = [s for s in select if s not in REGISTRY]
        if unknown:
            print(f"unknown checker id(s): {', '.join(unknown)}",
                  file=sys.stderr)
            return 2
    result = analyze_paths(args.paths or DEFAULT_PATHS, select=select)

    if args.format == "json":
        print(json.dumps({
            "files_analyzed": result.files_analyzed,
            "parse_errors": result.parse_errors,
            "findings": [f.to_dict() for f in result.findings],
            "unsuppressed": len(result.unsuppressed),
        }, indent=2))
    else:
        for line in _format_text(result, args.show_suppressed):
            print(line)
        for err in result.parse_errors:
            print(f"parse error: {err}", file=sys.stderr)
    if result.parse_errors:
        return 2
    return 1 if result.unsuppressed else 0


if __name__ == "__main__":
    sys.exit(main())
