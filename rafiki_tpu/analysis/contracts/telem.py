"""Telemetry-name registry extraction.

Write sites are ``telemetry.inc/set_gauge/add_gauge/observe/span/record_span``
calls with a constant first argument; an f-string name records a
*dynamic site* with its constant prefix (``gateway.shed_{reason}`` →
``gateway.shed_``). Collector registrations
(``register_collector("goodput", ...)``) are extracted too — they
explain whole prom-family prefixes the static name set can't.

Joins (pure functions over file contents, so the extractor itself
stays I/O-free):

* :func:`documented_names` parses the docs/telemetry.md table —
  backticked tokens, ``{a,b}`` brace groups expanded, ``<...>``
  placeholders to wildcards, and the ``/ `_suffix``` shorthand resolved
  against the preceding full name;
* :func:`join_prom_golden` maps ``# TYPE rafiki_<name> <type>``
  families back onto the static registry and reports the families
  nothing explains — the drift a renamed metric leaves behind.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from rafiki_tpu.analysis.checkers._ast_util import dotted_name

_APIS = {"inc": "counter", "set_gauge": "gauge", "add_gauge": "gauge",
         "observe": "histogram", "span": "span", "record_span": "span"}
_SAN_RE = re.compile(r"[^a-zA-Z0-9_]")
_TYPE_LINE = re.compile(r"^# TYPE rafiki_(\w+) (counter|gauge|summary)$")
_BACKTICK = re.compile(r"`([^`]+)`")
_BRACE = re.compile(r"\{([^{}]+)\}")


@dataclass
class MetricSite:
    path: str
    line: int
    name: str
    api: str                     # counter | gauge | histogram | span


@dataclass
class DynamicMetricSite:
    path: str
    line: int
    prefix: str                  # constant f-string head ("" if none)
    api: str


@dataclass
class TelemetryContracts:
    sites: List[MetricSite] = field(default_factory=list)
    dynamic_sites: List[DynamicMetricSite] = field(default_factory=list)
    collectors: List[MetricSite] = field(default_factory=list)

    def names(self) -> Dict[str, List[MetricSite]]:
        out: Dict[str, List[MetricSite]] = {}
        for s in self.sites:
            out.setdefault(s.name, []).append(s)
        return out


def _telemetry_call(call: ast.Call) -> Optional[str]:
    parts = dotted_name(call.func).split(".")
    if len(parts) >= 2 and parts[-1] in _APIS and (
            parts[-2] == "telemetry" or parts[-2].endswith("telemetry")):
        return _APIS[parts[-1]]
    return None


def extract_telemetry(modules) -> TelemetryContracts:
    out = TelemetryContracts()
    for m in sorted(modules, key=lambda m: m.path):
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            parts = dotted_name(node.func).split(".")
            if (parts[-1] == "register_collector" and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                out.collectors.append(MetricSite(
                    m.path, node.lineno, node.args[0].value, "collector"))
                continue
            api = _telemetry_call(node)
            if api is None or not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.sites.append(MetricSite(m.path, node.lineno,
                                            arg.value, api))
            elif isinstance(arg, ast.IfExp):  # "a" if cold else "b"
                for side in (arg.body, arg.orelse):
                    if (isinstance(side, ast.Constant)
                            and isinstance(side.value, str)):
                        out.sites.append(MetricSite(
                            m.path, node.lineno, side.value, api))
            elif isinstance(arg, ast.JoinedStr):
                head = ""
                if (arg.values and isinstance(arg.values[0], ast.Constant)
                        and isinstance(arg.values[0].value, str)):
                    head = arg.values[0].value
                out.dynamic_sites.append(DynamicMetricSite(
                    m.path, node.lineno, head, api))
            else:
                out.dynamic_sites.append(DynamicMetricSite(
                    m.path, node.lineno, "", api))
    out.sites.sort(key=lambda s: (s.name, s.path, s.line))
    out.dynamic_sites.sort(key=lambda s: (s.path, s.line))
    out.collectors.sort(key=lambda s: (s.name, s.path, s.line))
    return out


# ---------------------------------------------------------------------------
# docs/telemetry.md join
# ---------------------------------------------------------------------------


def documented_names(docs_text: str) -> Tuple[Set[str], Set[str]]:
    """(exact names, wildcard patterns) from the instrumentation table.
    Only table rows count (lines starting ``|``) so prose backticks
    don't leak in."""
    exact: Set[str] = set()
    wild: Set[str] = set()
    for line in docs_text.splitlines():
        if not line.startswith("|"):
            continue
        first_col = line.split("|")[1] if line.count("|") >= 2 else ""
        prev = ""
        for tok in _BACKTICK.findall(first_col):
            tok = tok.strip()
            m = _BRACE.search(tok)
            toks = ([tok[:m.start()] + alt.strip() + tok[m.end():]
                     for alt in m.group(1).split(",")] if m else [tok])
            for t in toks:
                short = t.startswith((".", "_"))
                if short and prev:
                    # `a.b_c` / `_d` means a.b_d: resolve against the
                    # row's first FULL name, not a prior expansion
                    sep = t[0]
                    cut = prev.rfind(sep)
                    t = (prev[:cut] if cut > 0 else prev) + t
                if "<" in t:
                    wild.add(re.sub(r"<[^<>]*>", "*", t))
                else:
                    exact.add(t)
                    if not short:
                        prev = t
    return exact, wild


def is_documented(name: str, exact: Set[str], wild: Set[str]) -> bool:
    return name in exact or any(fnmatch.fnmatchcase(name, w) for w in wild)


# ---------------------------------------------------------------------------
# prom golden join
# ---------------------------------------------------------------------------


def _san(name: str) -> str:
    out = _SAN_RE.sub("_", name)
    return out if not out[:1].isdigit() else "_" + out


def join_prom_golden(golden_text: str, contracts: TelemetryContracts
                     ) -> Dict[str, List[str]]:
    """Classify every golden family: ``matched`` (a static write site
    sanitizes to it), ``explained`` (span machinery, a registered
    collector's flattened prefix, or a dynamic-site prefix), or
    ``unexplained`` — the reviewable drift bucket."""
    static = {_san(s.name) for s in contracts.sites}
    collector_prefixes = [_san(c.name) + "_" for c in contracts.collectors]
    collector_names = {_san(c.name) for c in contracts.collectors}
    dynamic_prefixes = [_san(d.prefix) for d in contracts.dynamic_sites
                        if d.prefix]
    matched: List[str] = []
    explained: List[str] = []
    unexplained: List[str] = []
    for line in golden_text.splitlines():
        m = _TYPE_LINE.match(line.strip())
        if not m:
            continue
        fam = m.group(1)
        if fam in static:
            matched.append(fam)
        elif (fam.startswith("span_")
              or fam in collector_names
              or any(fam.startswith(p) for p in collector_prefixes)
              or any(fam.startswith(p) for p in dynamic_prefixes if p)):
            explained.append(fam)
        else:
            unexplained.append(fam)
    return {"matched": sorted(matched), "explained": sorted(explained),
            "unexplained": sorted(unexplained)}
