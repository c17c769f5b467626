"""RF001 entrypoint-platform-pin.

Historical bug (round 5): ``run_inference_worker_process`` was the one
jax-touching spawn entrypoint that never called
``honor_env_platform()``, so the spawned child ignored the CPU request
its parent ran under. An explicit CPU request is applied before the
first backend use, in every process.

Rule: a *process entrypoint* (module-level ``main``/``serve``,
``run_*_process`` spawn targets, or an ``if __name__ == "__main__"``
block) in a module whose import closure reaches jax must call
``honor_env_platform()`` or ``force_cpu_backend()`` — directly, or via
another function in the same module (a ``main`` that pins through its own
``_init_backend``) — and the pin must lexically precede the first
direct ``jax.*`` use in that scope. A bare ``import jax`` before the
pin is fine: the platform is chosen at backend *init*, which
``jax.config`` updates still preempt post-import.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Optional, Set, Tuple

from rafiki_tpu.analysis.core import Checker, Finding, ModuleContext, register
from rafiki_tpu.analysis.checkers._ast_util import (
    dotted_name, dunder_main_block, module_functions)

PIN_CALLS = {"honor_env_platform", "force_cpu_backend"}
ENTRYPOINT_NAME = re.compile(r"^(main|serve|run_\w*_process)$")


def _calls_in(nodes: Iterable[ast.AST]) -> List[ast.Call]:
    out: List[ast.Call] = []
    for n in nodes:
        out.extend(c for c in ast.walk(n) if isinstance(c, ast.Call))
    return out


def _pinning_functions(tree: ast.Module) -> Set[str]:
    """Module functions that (transitively, within this module) call a
    pin — covers a main -> _init_backend -> honor chain."""
    fns = {f.name: f for f in module_functions(tree)}
    pinning: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for name, fn in fns.items():
            if name in pinning:
                continue
            for call in _calls_in(fn.body):
                target = dotted_name(call.func)
                leaf = target.rsplit(".", 1)[-1]
                if leaf in PIN_CALLS or target in pinning:
                    pinning.add(name)
                    changed = True
                    break
    return pinning


def _first_pin_line(body: List[ast.stmt], pinning: Set[str]) -> Optional[int]:
    lines = [call.lineno for call in _calls_in(body)
             if (lambda t: t.rsplit(".", 1)[-1] in PIN_CALLS or t in pinning)(
                 dotted_name(call.func))]
    return min(lines) if lines else None


def _first_jax_touch(body: List[ast.stmt]) -> Optional[Tuple[int, str]]:
    """First direct ``jax.<...>`` attribute use (``jax.devices()``,
    ``jax.distributed.initialize`` ...). Imports of jax don't count."""
    best: Optional[Tuple[int, str]] = None
    for n in body:
        for node in ast.walk(n):
            if isinstance(node, ast.Attribute):
                name = dotted_name(node)
                if name == "jax" or name.startswith("jax."):
                    if best is None or node.lineno < best[0]:
                        best = (node.lineno, name)
    return best


@register
class EntrypointPlatformPin(Checker):
    id = "RF001"
    name = "entrypoint-platform-pin"
    severity = "error"
    rationale = ("jax-touching process entrypoints must pin the backend "
                 "(honor_env_platform) before first jax use — an explicit "
                 "CPU request is applied before the first backend use")

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        if not ctx.project.is_jax_tainted(ctx.module_name):
            return []
        pinning = _pinning_functions(ctx.tree)
        scopes: List[Tuple[str, List[ast.stmt], ast.AST]] = []
        for fn in module_functions(ctx.tree):
            if ENTRYPOINT_NAME.match(fn.name):
                scopes.append((fn.name, fn.body, fn))
        main_block = dunder_main_block(ctx.tree)
        if main_block is not None:
            scopes.append(('__main__ block', main_block.body, main_block))

        findings = []
        for label, body, node in scopes:
            pin_line = _first_pin_line(body, pinning)
            touch = _first_jax_touch(body)
            if pin_line is None:
                findings.append(self.finding(
                    ctx, node,
                    f"entrypoint `{label}` of jax-importing module "
                    f"{ctx.module_name} never pins the platform: call "
                    f"honor_env_platform() (utils.backend) before any jax "
                    f"touch, so an explicit CPU request is applied before "
                    f"the first backend use"))
            elif touch is not None and touch[0] < pin_line:
                findings.append(self.finding(
                    ctx, node,
                    f"entrypoint `{label}` touches `{touch[1]}` at line "
                    f"{touch[0]} before the platform pin at line {pin_line} "
                    f"— move honor_env_platform() ahead of the first jax "
                    f"use"))
        return findings
