"""OSL16xx — the interprocedural dataflow rule pack.

Built on :mod:`analysis.dataflow` (per-function CFGs, effect inference,
taint lattice, jit-region tracking). Three rules:

- **OSL1601 jit-impurity** — OSL101 generalized from one syntactic file
  to call-graph depth: any function transitively reachable from a
  jit-traced region (``@jax.jit``-family decorators, function refs passed
  to ``lax.scan``/``vmap``/``pallas_call``, ``# opensim-lint: jit-region``
  markers) with an inferred side effect — I/O, clock/RNG reads,
  host-device syncs, module/instance state writes. All of these execute
  once at trace time and go silently stale in the compiled program.

- **OSL1602 tracer-leak** — a traced value (function parameter or
  ``jnp.``/``lax.``-family result) stored into state that outlives the
  trace (``self.attr``, module globals, nonlocals): the tracer escapes
  and either raises ``UnexpectedTracerError`` much later or bakes stale
  data into host state.

- **OSL1603 untrusted-input-taint** — HTTP query/body params, CLI args,
  YAML documents, and stdin flowing into ``open()``/path joins/
  ``subprocess`` without passing a **registered validator** (a function
  carrying a ``@sanitizer`` decorator — see ``utils/validate.py``).
  Flow-sensitive per function, interprocedural through call-graph
  summaries.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

from .core import Finding, ProjectContext, Rule, register
from .dataflow import get_engine, get_taint_hits, get_tracer_leaks


@dataclass
class _Site:
    lineno: int
    col_offset: int


_EFFECT_WHY = {
    "io": "I/O executes once at trace time and never again in the compiled program",
    "clock": "the clock is read once at trace time (stale constant baked in)",
    "rng": "host RNG draws once at trace time (same 'random' value every step)",
    "host-sync": "forces a host-device sync / fails outright on tracers",
    "state-write": "host state mutates at trace time only, then silently never again",
}


@register
class JitImpurityRule(Rule):
    name = "jit-impurity"
    code = "OSL1601"
    description = (
        "side effect in a function transitively reachable from a jit-traced region"
    )
    project_rule = True
    exclude_paths = ("tests/",)

    def project_check(self, project: ProjectContext) -> Iterable[Finding]:
        df = get_engine(project)
        reach = df.jit_reachable()
        roots = df.jit_roots()
        for qual in sorted(reach):
            unit = df.units[qual]
            root, chain = reach[qual]
            for eff in df.direct_effects(qual):
                via = " -> ".join(q.rsplit(".", 1)[-1] for q in chain + (qual,))
                how = roots.get(root, "jit root")
                short_root = root.rsplit(".", 1)[-1]
                where = (
                    f"jit-traced `{short_root}` ({how})"
                    if qual == root
                    else f"reachable from jit-traced `{short_root}` ({how}) via {via}"
                )
                yield self.finding(
                    unit.ctx.path,
                    _Site(eff.line, eff.col),
                    f"`{eff.desc}` ({eff.kind}) in `{qual.rsplit('.', 1)[-1]}`, "
                    f"{where}: {_EFFECT_WHY[eff.kind]}",
                )


@register
class TracerLeakRule(Rule):
    name = "tracer-leak"
    code = "OSL1602"
    description = "traced value stored into state that outlives the trace"
    project_rule = True
    exclude_paths = ("tests/",)

    def project_check(self, project: ProjectContext) -> Iterable[Finding]:
        df = get_engine(project)
        for hit in get_tracer_leaks(project):
            unit = df.units[hit.unit]
            yield self.finding(
                unit.ctx.path,
                _Site(hit.line, hit.col),
                f"{hit.desc} stored into {hit.sink} inside jit-reachable "
                f"`{hit.unit.rsplit('.', 1)[-1]}`: the tracer outlives the "
                "trace (UnexpectedTracerError later, or silently stale host state)",
            )


@register
class InputTaintRule(Rule):
    name = "input-taint"
    code = "OSL1603"
    description = (
        "untrusted input reaches a filesystem/subprocess sink without a "
        "registered validator"
    )
    project_rule = True
    exclude_paths = ("tests/",)

    def project_check(self, project: ProjectContext) -> Iterable[Finding]:
        df = get_engine(project)
        for hit in get_taint_hits(project):
            unit = df.units[hit.unit]
            yield self.finding(
                unit.ctx.path,
                _Site(hit.line, hit.col),
                f"untrusted input reaches {hit.sink} in "
                f"`{hit.unit.rsplit('.', 1)[-1]}` ({hit.desc}); route it "
                "through a registered validator (@sanitizer, utils/validate.py)",
            )
