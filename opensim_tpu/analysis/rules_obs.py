"""deadline-span (OSL701): Deadline phase boundaries without trace spans.

The resilience layer and the observability layer are two views of the SAME
phase structure: everywhere a function enforces the request deadline
(``check_deadline("phase")``) or installs a deadline scope
(``deadline_scope(...)``), the tracer must be able to say how long that
phase took and whether it failed — otherwise a 504's ``phase`` field names
a boundary the flight recorder has no span for, and the latency histograms
go dark exactly where requests die.

The rule flags any function that calls a Deadline API but opens no span in
the same function body (``obs.span`` / ``event`` /
``start_trace`` / ``trace_scope``). Nested ``def``/``lambda`` bodies are
not credited to the outer function — a span opened inside a callback does
not cover the enclosing boundary.

Fix by wrapping the phase in ``with obs.span("phase"):``; see
docs/observability.md.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .core import FileContext, Finding, Rule, dotted_name, register

_DEADLINE_CALLS = {"check_deadline", "deadline_scope"}
_SPAN_CALLS = {
    "span",
    "event",
    "start_trace",
    "trace_scope",
    "child_from_seconds",
}


def _leaf(node: ast.Call) -> str:
    name = dotted_name(node.func)
    if name:
        return name.rsplit(".", 1)[-1]
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return ""


def _own_body_walk(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function's body without descending into nested function/class
    definitions (their deadline calls are judged on their own)."""
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


@register
class DeadlineSpanRule(Rule):
    name = "deadline-span"
    code = "OSL701"
    description = "Deadline phase boundary without a matching trace span"
    # the modules DEFINING the two layers are exempt: deadline.py's own
    # helpers necessarily name the Deadline APIs, obs is the span layer
    exclude_paths = ("resilience/deadline.py", "opensim_tpu/obs/", "tests/")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            first_deadline = None
            has_span = False
            for node in _own_body_walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                leaf = _leaf(node)
                if leaf in _DEADLINE_CALLS and first_deadline is None:
                    first_deadline = node
                elif leaf in _SPAN_CALLS:
                    has_span = True
            if first_deadline is not None and not has_span:
                yield self.finding(
                    ctx,
                    first_deadline,
                    f"function {fn.name!r} opens a Deadline phase boundary "
                    "but records no trace span; wrap the phase in "
                    "`with obs.span(...)` so the "
                    "flight recorder and latency histograms cover it",
                )
