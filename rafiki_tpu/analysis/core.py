"""AST static-analysis core: checker registry, project context, runner.

Why in-repo instead of flake8 plugins: every checker here encodes a
failure class this codebase has actually shipped (see
docs/static_analysis.md for the catalog and the historical bug behind
each id). The framework is deliberately small:

  * a :class:`Checker` subclass registers itself via :func:`register`
    and receives one :class:`ModuleContext` per analyzed file;
  * project-wide facts (the jax import-taint set) are computed once in
    :class:`ProjectContext` before any checker runs, so checkers can
    ask "does importing this module pull in jax?" without re-walking
    the tree;
  * findings are suppressed inline with ``# lint: disable=RF00x — why``
    on the offending line (or an immediately preceding comment line).
    A suppression WITHOUT a justification does not suppress — the rule
    "every suppression carries its one-line why" is enforced here, not
    by review vigilance.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple, Type

SEVERITIES = ("error", "warning")

# ``# lint: disable=RF001`` or ``# lint: disable=RF001,RF003 — reason``.
# The justification separator is any of ``—``, ``--``, ``-`` or ``:``
# followed by non-empty text.
_SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*disable=([A-Z]{2,}\d+(?:\s*,\s*[A-Z]{2,}\d+)*)"
    r"\s*(?:(?:—|--|-|:)\s*(\S.*))?")


@dataclass
class Finding:
    checker_id: str
    path: str
    line: int
    col: int
    severity: str
    message: str
    suppressed: bool = False
    justification: str = ""

    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_dict(self) -> dict:
        return {
            "checker": self.checker_id,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "severity": self.severity,
            "message": self.message,
            "suppressed": self.suppressed,
            "justification": self.justification,
        }


@dataclass
class ModuleContext:
    """Everything a checker may want to know about one analyzed file."""

    path: str                 # as given on the command line (relative ok)
    module_name: str          # dotted, e.g. "rafiki_tpu.bus.queues"
    tree: ast.Module
    source: str
    lines: List[str]
    project: "ProjectContext"

    # (line -> (set of ids | None for all, justification)) built lazily
    _suppressions: Optional[Dict[int, Tuple[Set[str], str]]] = None

    def suppression_at(self, line: int) -> Optional[Tuple[Set[str], str]]:
        """The suppression covering ``line``: same line or an
        immediately preceding comment-only line."""
        if self._suppressions is None:
            sup: Dict[int, Tuple[Set[str], str]] = {}
            for i, text in enumerate(self.lines, start=1):
                m = _SUPPRESS_RE.search(text)
                if not m:
                    continue
                ids = {s.strip() for s in m.group(1).split(",")}
                just = (m.group(2) or "").strip()
                sup[i] = (ids, just)
                # a comment-only line covers the next code line
                if text.lstrip().startswith("#"):
                    sup.setdefault(i + 1, (ids, just))
            self._suppressions = sup
        return self._suppressions.get(line)


class ProjectContext:
    """Cross-file facts shared by all checkers for one analysis run."""

    def __init__(self, modules: Dict[str, ModuleContext]):
        self.modules = modules            # module_name -> ctx
        self._facts: Dict[str, object] = {}
        self.jax_tainted: Set[str] = self._compute_jax_taint()

    def fact(self, key: str, compute):
        """Memoized whole-program fact shared across checkers — the
        contracts extraction (RF014–RF016) walks every tree once per
        run through this, not once per (checker, module) pair."""
        if key not in self._facts:
            self._facts[key] = compute(self)
        return self._facts[key]

    # -- jax import taint ----------------------------------------------------

    @staticmethod
    def _imported_module_names(tree: ast.AST) -> Set[str]:
        """Every module name this tree may import (module- or
        function-level): for ``from M import a, b`` both ``M`` and
        ``M.a``/``M.b`` are candidates (a may itself be a submodule)."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    names.add(alias.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                names.add(node.module)
                for alias in node.names:
                    names.add(f"{node.module}.{alias.name}")
        return names

    def _compute_jax_taint(self) -> Set[str]:
        """Fixpoint: a module is jax-tainted if it imports jax, or
        imports an analyzed module that is. Bounded to the analyzed
        file set — callers who need whole-project taint analyze the
        whole project."""
        imports = {name: self._imported_module_names(ctx.tree)
                   for name, ctx in self.modules.items()}
        tainted = {name for name, imps in imports.items()
                   if any(i == "jax" or i.startswith("jax.") for i in imps)}
        changed = True
        while changed:
            changed = False
            for name, imps in imports.items():
                if name in tainted:
                    continue
                if any(i in tainted for i in imps):
                    tainted.add(name)
                    changed = True
        return tainted

    def is_jax_tainted(self, module_name: str) -> bool:
        return module_name in self.jax_tainted


class Checker:
    """Base class. Subclasses set ``id``/``name``/``severity`` and
    implement :meth:`check_module`; :func:`register` puts them in the
    registry the CLI and tests discover checkers from."""

    id: str = ""
    name: str = ""
    severity: str = "warning"
    rationale: str = ""  # one-liner surfaced by ``--explain``

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str,
                severity: Optional[str] = None) -> Finding:
        return Finding(
            checker_id=self.id, path=ctx.path,
            line=getattr(node, "lineno", 0), col=getattr(node, "col_offset", 0),
            severity=severity or self.severity, message=message)


REGISTRY: Dict[str, Type[Checker]] = {}


def register(cls: Type[Checker]) -> Type[Checker]:
    if not cls.id:
        raise ValueError(f"{cls.__name__} has no checker id")
    if cls.id in REGISTRY and REGISTRY[cls.id] is not cls:
        raise ValueError(f"duplicate checker id {cls.id}")
    REGISTRY[cls.id] = cls
    return cls


def load_builtin_checkers() -> None:
    """Plugin discovery: import every module in the checkers package;
    each registers itself on import."""
    import importlib
    import pkgutil

    from rafiki_tpu.analysis import checkers as pkg

    for mod in pkgutil.iter_modules(pkg.__path__):
        importlib.import_module(f"{pkg.__name__}.{mod.name}")


# ---------------------------------------------------------------------------
# File collection and module naming
# ---------------------------------------------------------------------------


def _collect_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    # de-dup, stable order
    seen: Set[str] = set()
    uniq = []
    for f in out:
        key = os.path.abspath(f)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


def module_name_for(path: str) -> str:
    """Dotted module name: walk up while __init__.py exists, so
    rafiki_tpu/bus/queues.py -> rafiki_tpu.bus.queues; a top-level
    script (chip_smoke.py) is just its stem."""
    path = os.path.abspath(path)
    parts = [os.path.splitext(os.path.basename(path))[0]]
    d = os.path.dirname(path)
    while os.path.exists(os.path.join(d, "__init__.py")):
        parts.append(os.path.basename(d))
        d = os.path.dirname(d)
    name = ".".join(reversed(parts))
    return name[: -len(".__init__")] if name.endswith(".__init__") else name


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


@dataclass
class AnalysisResult:
    findings: List[Finding] = field(default_factory=list)
    files_analyzed: int = 0
    parse_errors: List[str] = field(default_factory=list)

    @property
    def unsuppressed(self) -> List[Finding]:
        return [f for f in self.findings if not f.suppressed]


def analyze_paths(paths: Sequence[str],
                  select: Optional[Sequence[str]] = None) -> AnalysisResult:
    """Parse every .py under ``paths``, build project context, run the
    registered checkers (all, or only ``select`` ids), apply inline
    suppressions. Checkers must already be loaded/registered."""
    result = AnalysisResult()
    modules: Dict[str, ModuleContext] = {}
    for path in _collect_py_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=path)
        except (OSError, SyntaxError) as e:
            result.parse_errors.append(f"{path}: {e}")
            continue
        ctx = ModuleContext(path=path, module_name=module_name_for(path),
                            tree=tree, source=source,
                            lines=source.splitlines(), project=None)  # type: ignore[arg-type]
        modules[ctx.module_name] = ctx
    project = ProjectContext(modules)
    for ctx in modules.values():
        ctx.project = project

    ids = sorted(REGISTRY) if select is None else [i for i in sorted(REGISTRY)
                                                  if i in set(select)]
    checkers = [REGISTRY[i]() for i in ids]
    for ctx in modules.values():
        result.files_analyzed += 1
        for checker in checkers:
            for f in checker.check_module(ctx):
                sup = ctx.suppression_at(f.line)
                if sup is not None and f.checker_id in sup[0]:
                    if sup[1]:
                        f.suppressed = True
                        f.justification = sup[1]
                    else:
                        f.message += (" [suppression present but has no "
                                      "justification — add one after the id]")
                result.findings.append(f)
    result.findings.sort(key=lambda f: (f.path, f.line, f.checker_id))
    return result
