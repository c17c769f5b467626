"""RF008 metric-name drift.

Perf-sentinel finding (docs/perf.md): SLO specs, the prom golden file
and dashboard queries all address telemetry series *by name string*.
A metric name built at the call site — an f-string, a ``"a" + b``
concatenation, a lowercase variable — can silently fork one logical
series into many (per-id cardinality explosions) or rename it out from
under every consumer; nothing fails, the SLO just stops seeing data.

The rule: the name argument to ``telemetry.inc`` / ``observe`` /
``set_gauge`` / ``add_gauge`` / ``span`` / ``record_span`` must be
*statically known* —
a string literal, an UPPER_CASE registry constant (bare or dotted),
or a conditional between such values (the train loop's
``"train.cold_epoch_s" if cold else "train.epoch_s"`` split names two
literal series, not a dynamic one).

Genuinely bounded dynamic refinements (the gateway's per-reason shed
counters, the chaos plane's site×mode injection counters) stay legal
via justify-suppression — the justification is where "bounded" gets
argued. ``rafiki_tpu/telemetry/`` and ``rafiki_tpu/obs/`` are exempt:
they implement the registry this rule protects.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set

from rafiki_tpu.analysis.core import Checker, Finding, ModuleContext, register
from rafiki_tpu.analysis.checkers._ast_util import dotted_name

_EXEMPT_PREFIXES = ("rafiki_tpu.telemetry", "rafiki_tpu.obs")

#: Telemetry entry points whose first argument is a series name.
_METHODS = ("inc", "observe", "set_gauge", "add_gauge", "span",
            "record_span")


def _metric_call_names(tree: ast.Module) -> Set[str]:
    """Dotted names that resolve to a telemetry name-taking entry point
    in this module — ``<alias>.<method>`` for module aliases, plus bare
    aliases from ``from rafiki_tpu.telemetry import inc [as x]``."""
    names: Set[str] = set()
    module_aliases: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "rafiki_tpu.telemetry":
                for a in node.names:
                    if a.name in _METHODS:
                        names.add(a.asname or a.name)
            elif node.module == "rafiki_tpu":
                for a in node.names:
                    if a.name == "telemetry":
                        module_aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "rafiki_tpu.telemetry":
                    module_aliases.add(a.asname or a.name)
    for alias in module_aliases:
        for m in _METHODS:
            names.add(f"{alias}.{m}")
    return names


def _is_static_name(node: ast.AST) -> bool:
    """A statically-known series name: literal, UPPER_CASE constant
    (bare or as the final attribute of a dotted path), or an IfExp /
    BoolOp choosing between such values."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, str)
    if isinstance(node, ast.Name):
        return node.id.isupper()
    if isinstance(node, ast.Attribute):
        return node.attr.isupper()
    if isinstance(node, ast.IfExp):
        return _is_static_name(node.body) and _is_static_name(node.orelse)
    if isinstance(node, ast.BoolOp):
        return all(_is_static_name(v) for v in node.values)
    return False


def _describe(node: ast.AST) -> str:
    if isinstance(node, ast.JoinedStr):
        return "an f-string"
    if isinstance(node, ast.BinOp):
        return "a concatenation/expression"
    if isinstance(node, ast.Name):
        return f"the variable {node.id!r}"
    if isinstance(node, ast.Call):
        return "a call result"
    return "a dynamic expression"


@register
class MetricNameDrift(Checker):
    id = "RF008"
    name = "metric-name-drift"
    severity = "error"
    rationale = ("metric/span names built at the call site silently "
                 "fork or rename series out from under prom exposition, "
                 "the golden file and SLO specs — names must be string "
                 "literals or UPPER_CASE registry constants")

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        if ctx.module_name.startswith(_EXEMPT_PREFIXES):
            return []
        findings: List[Finding] = []
        call_names = _metric_call_names(ctx.tree)
        if not call_names:
            return []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func)
            if fn not in call_names or not node.args:
                continue
            name_arg = node.args[0]
            if _is_static_name(name_arg):
                continue
            method = fn.rsplit(".", 1)[-1]
            findings.append(self.finding(
                ctx, name_arg,
                f"telemetry.{method} name is {_describe(name_arg)}: "
                "dynamic series names drift away from prom exposition "
                "and SLO specs — use a string literal or an UPPER_CASE "
                "constant, or justify-suppress a bounded refinement"))
        return findings
