#!/usr/bin/env bash
# Static-analysis gate: zero unsuppressed findings over the canonical
# path set (see docs/static_analysis.md). Same checkers, same paths as
# tests/test_lint_clean.py — this is the shell-visible form. JSON
# output so a failing run leaves a machine-readable artifact on stdout.
#
# The contracts pass (docs/static_analysis.md, "Contracts") then diffs
# the freshly extracted contracts manifest and the generated knob docs
# against their committed copies: any journal-kind / env-knob /
# telemetry-name drift fails the gate as a reviewable diff.
#
#   --contracts-only   skip the checker pass; run only the contracts
#                      extraction + golden/docs diffs (fast path for
#                      regenerate-and-recheck loops)
set -o pipefail
cd "$(dirname "$0")/.."

PATHS="rafiki_tpu scripts"
GOLDEN=tests/data/contracts_manifest.json
KNOBS=docs/knobs.md

if [ "${1:-}" != "--contracts-only" ]; then
  env JAX_PLATFORMS=cpu python -m rafiki_tpu.analysis $PATHS --format json
  rc=$?
  if [ $rc -ne 0 ]; then
    echo "check_lint: unsuppressed findings (or parse errors) — run" >&2
    echo "  python -m rafiki_tpu.analysis $PATHS" >&2
    echo "and fix or justify-suppress each (docs/static_analysis.md)." >&2
    exit $rc
  fi
fi

# -- contracts pass ----------------------------------------------------------

tmp="$(mktemp -d)"
trap 'rm -rf "$tmp"' EXIT

env JAX_PLATFORMS=cpu python -m rafiki_tpu.analysis --contracts $PATHS \
  > "$tmp/manifest.json" || exit 2
if ! diff -u "$GOLDEN" "$tmp/manifest.json"; then
  echo "check_lint: contracts manifest drifted from $GOLDEN —" >&2
  echo "review the diff above (a renamed journal kind, env knob, or" >&2
  echo "metric changes a cross-process contract), then regenerate:" >&2
  echo "  python -m rafiki_tpu.analysis --contracts > $GOLDEN" >&2
  exit 1
fi

env JAX_PLATFORMS=cpu python -m rafiki_tpu.analysis --contracts --docs \
  $PATHS > "$tmp/knobs.md" || exit 2
if ! diff -u "$KNOBS" "$tmp/knobs.md"; then
  echo "check_lint: $KNOBS is stale — it is generated, not" >&2
  echo "hand-edited. Regenerate:" >&2
  echo "  python -m rafiki_tpu.analysis --contracts --docs > $KNOBS" >&2
  exit 1
fi
if grep -q "undocumented" "$tmp/knobs.md"; then
  echo "check_lint: undocumented env knob(s) — add a one-line" >&2
  echo "description to rafiki_tpu/analysis/contracts/knobdocs.py" >&2
  echo "and regenerate $KNOBS." >&2
  grep "undocumented" "$tmp/knobs.md" | head -5 >&2
  exit 1
fi

echo "check_lint: contracts manifest and knob docs match the tree"
exit 0
