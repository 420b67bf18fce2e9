"""exception-swallow (OSL501): broad handlers that hide failures.

A ``except Exception`` (or bare ``except``) whose body neither re-raises
nor logs leaves no trace of the failure — the simulator then reports a
result computed from partial state, which is worse than crashing. The rule
accepts any of:

- a ``raise`` anywhere in the handler body (re-raise or translation);
- a structured log: a call to ``logging``/``warnings`` machinery or to a
  logger method (``.warning()``, ``.error()``, ``.exception()``, ...);

Narrowed handlers (``except ValueError: pass``) are not flagged — naming
the exception is the other sanctioned fix.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .core import FileContext, Finding, Rule, dotted_name, register

_BROAD = {"Exception", "BaseException"}
_LOG_METHODS = {
    "warning",
    "warn",
    "error",
    "exception",
    "critical",
    "info",
    "debug",
    "log",
}
_LOG_PREFIXES = ("logging.", "warnings.", "log.", "logger.", "trace.")


def _is_broad(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    return dotted_name(handler.type) in _BROAD


def _handled(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name.startswith(_LOG_PREFIXES):
                return True
            if isinstance(node.func, ast.Attribute) and node.func.attr in _LOG_METHODS:
                return True
    return False


@register
class ExceptionSwallowRule(Rule):
    name = "exception-swallow"
    code = "OSL501"
    description = "broad except without re-raise or structured log"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ExceptHandler) and _is_broad(node) and not _handled(node):
                caught = "bare except" if node.type is None else f"except {dotted_name(node.type)}"
                yield self.finding(
                    ctx,
                    node,
                    f"`{caught}` swallows the failure (no raise, no log); "
                    "narrow the exception or log the failure",
                )
