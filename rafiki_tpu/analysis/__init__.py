"""Repo-specific static analysis (``python -m rafiki_tpu.analysis``).

Each checker encodes a failure class this repo actually shipped; see
docs/static_analysis.md for the catalog. Import surface:

    from rafiki_tpu.analysis import analyze_paths, load_builtin_checkers
    load_builtin_checkers()
    result = analyze_paths(["rafiki_tpu"])

NOTE: this package must stay importable without jax — it runs in CI
paths that have no accelerator stack and must not initialise a backend.
"""

from rafiki_tpu.analysis.core import (  # noqa: F401
    REGISTRY, AnalysisResult, Checker, Finding, ModuleContext,
    ProjectContext, analyze_paths, load_builtin_checkers, register)
