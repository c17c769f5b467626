"""RF002 platform-literal-gate.

Historical bug (round 5, the one-chip bench script, since retired): its
MFU fields were gated on ``platform == "tpu"`` while the backend of the day registered
the chip under another platform name — every run silently reported
``mfu: null``.

Rule: never equality-compare a platform string against the literal
``"tpu"`` without saying why. Hardware facts (peaks, HBM) are keyed on
``device_kind`` (utils.backend.peak_bf16_flops); a place that really
means the platform name — the process scheduler's worker env, a
measurement path that refuses to run without the chip — carries a
justified suppression.
"""

from __future__ import annotations

import ast
from typing import Iterable

from rafiki_tpu.analysis.core import Checker, Finding, ModuleContext, register


@register
class PlatformLiteralGate(Checker):
    id = "RF002"
    name = "platform-literal-gate"
    severity = "error"
    rationale = ('a platform-name literal is not a hardware fact — key '
                 'peaks on device_kind, and justify-suppress the places '
                 'that really mean the platform name')

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left] + list(node.comparators)
            has_tpu_literal = any(
                # lint: disable=RF002 — the checker must name the literal it hunts
                isinstance(s, ast.Constant) and s.value == "tpu"
                for s in sides)
            if not has_tpu_literal:
                continue
            if not any(isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
                continue
            findings.append(self.finding(
                ctx, node,
                'platform compared against the literal "tpu": key '
                'hardware facts on device_kind, or justify-suppress a '
                'place that really means the platform name'))
        return findings
