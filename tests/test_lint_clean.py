"""Tier-1 enforcement of the static-analysis pass: the repo must
analyze CLEAN — zero unsuppressed findings over the same path set the
CLI and scripts/check_lint.sh use. A new violation of any encoded
failure class (docs/static_analysis.md) fails the suite exactly like a
broken test."""

import os

from rafiki_tpu.analysis import analyze_paths, load_builtin_checkers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_PATHS = [os.path.join(REPO, "rafiki_tpu"),
              os.path.join(REPO, "scripts")]

load_builtin_checkers()


def test_repo_analyzes_clean():
    result = analyze_paths(LINT_PATHS)
    assert result.parse_errors == []
    assert result.files_analyzed > 50  # the walk actually saw the tree
    pretty = [f"{f.location()} {f.checker_id}: {f.message}"
              for f in result.unsuppressed]
    assert pretty == [], "\n".join(pretty)


def test_every_suppression_is_justified():
    result = analyze_paths(LINT_PATHS)
    for f in result.findings:
        if f.suppressed:
            assert f.justification, f"{f.location()} suppressed without why"


def test_repo_clean_under_contract_checkers():
    """RF014–RF016 specifically: every journal kind written is read (or
    justify-suppressed), every read field is written, every knob agrees
    on its default and reaches its spawned children."""
    result = analyze_paths(LINT_PATHS, select=["RF014", "RF015", "RF016"])
    pretty = [f"{f.location()} {f.checker_id}: {f.message}"
              for f in result.unsuppressed]
    assert pretty == [], "\n".join(pretty)


def test_repo_clean_under_full_gather_checker():
    """RF019 specifically (docs/sharding.md): group-sharded train
    state is materialized on a host ONLY through shard/checkpoint.py's
    manifest path (save_sharded / gather_state)."""
    result = analyze_paths(LINT_PATHS, select=["RF019"])
    pretty = [f"{f.location()} {f.checker_id}: {f.message}"
              for f in result.unsuppressed]
    assert pretty == [], "\n".join(pretty)


def test_contracts_manifest_golden_matches_tree():
    """The committed manifest is byte-identical to a fresh extraction —
    the in-process form of check_lint.sh's contracts diff. On drift:
    python -m rafiki_tpu.analysis --contracts > tests/data/contracts_manifest.json
    """
    from rafiki_tpu.analysis.contracts.manifest import (
        dump_manifest, manifest_for_paths)
    fresh = dump_manifest(manifest_for_paths(LINT_PATHS, root=REPO))
    golden = open(os.path.join(
        REPO, "tests/data/contracts_manifest.json")).read()
    assert fresh == golden


def test_knob_docs_golden_matches_tree():
    """docs/knobs.md is generated; regenerate on drift:
    python -m rafiki_tpu.analysis --contracts --docs > docs/knobs.md
    """
    from rafiki_tpu.analysis.contracts.envknobs import extract_env
    from rafiki_tpu.analysis.contracts.knobdocs import generate_knobs_md
    from rafiki_tpu.analysis.contracts.manifest import _load_modules
    fresh = generate_knobs_md(extract_env(_load_modules(LINT_PATHS,
                                                        root=REPO)))
    golden = open(os.path.join(REPO, "docs/knobs.md")).read()
    assert fresh == golden
    assert "undocumented" not in fresh
