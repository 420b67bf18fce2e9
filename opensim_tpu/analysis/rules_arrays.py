"""OSL18xx — the array-contract rule pack.

Built on :mod:`analysis.arrays` (abstract interpretation of numpy/jax
values over the dataflow CFGs, checked against the contract registry in
``encoding/dtypes.py``). Four rules:

- **OSL1801 array-off-policy** — an array created without (or with a
  non-policy) dtype reaches an ``EncodedCluster``/``ScanState``/
  ``NodeArenas`` field or a kernel-entry argument whose contract declares
  a different width. The finding anchors at the creation site (the
  ``np.zeros``/``np.asarray``/literal without a ``dtype=`` from
  ``encoding/dtypes.py``), interprocedurally when the array crosses a
  function boundary before binding.

- **OSL1802 silent-upcast** — a dtype promotion (mixed-width binop,
  ``np.where``, int true-division, float ufunc on ints, numpy's i64
  ``sum`` accumulator) on a path that reaches an arena write or kernel
  boundary whose contract is narrower. Anchors at the promotion site: the
  exact expression where float32 silently became float64.

- **OSL1803 shape-contract** — rank or named-axis-order mismatch against
  the declared ``(dtype, axes)`` contract at a binding site; axis names
  are the symbolic shape vocabulary from ``encoding/state.py`` with the
  builder-local aliases in ``AXIS_ALIASES``. Unknown axes (``?``) never
  fire — only a known-vs-known mismatch does.

- **OSL1804 contract-abi-parity** — the registry's own sync: the contract
  registry in ``encoding/dtypes.py`` vs the policy constants it names vs
  the ``EncodedCluster``/``ScanState`` field sets, naming the exact
  field.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Iterable, Optional

from .arrays import Contracts, _parse_dtypes_module, get_array_findings
from .core import FileContext, Finding, ProjectContext, Rule, register


@dataclass
class _Site:
    lineno: int
    col_offset: int


_SCOPE_PATHS = ("encoding/", "engine/", "parallel/", "ops/")


class _ArrayRuleBase(Rule):
    project_rule = True
    paths = _SCOPE_PATHS
    exclude_paths = ("tests/",)

    def project_check(self, project: ProjectContext) -> Iterable[Finding]:
        for f in get_array_findings(project):
            if f.code == self.code:
                yield self.finding(f.path, _Site(f.line, f.col), f.message)


@register
class OffPolicyArrayRule(_ArrayRuleBase):
    name = "array-off-policy"
    code = "OSL1801"
    description = (
        "array built without a policy dtype (encoding/dtypes.py) reaches a "
        "contracted arena field or kernel boundary of a different width"
    )


@register
class SilentUpcastRule(_ArrayRuleBase):
    name = "silent-upcast"
    code = "OSL1802"
    description = (
        "dtype promotion on a path reaching an arena write or kernel "
        "boundary whose contract is narrower (interprocedural)"
    )


@register
class ShapeContractRule(_ArrayRuleBase):
    name = "shape-contract"
    code = "OSL1803"
    description = (
        "rank/axis-order mismatch against the declared (dtype, axes) "
        "contract at an arena or kernel binding"
    )


@register
class ContractAbiParityRule(Rule):
    name = "contract-abi-parity"
    code = "OSL1804"
    description = (
        "contract registry, dtypes policy and EncodedCluster/ScanState "
        "fields drifted out of sync"
    )
    project_rule = True

    def project_check(self, project: ProjectContext) -> Iterable[Finding]:
        dtypes_ctx: Optional[FileContext] = None
        state_ctx: Optional[FileContext] = None
        for ctx in project.contexts:
            p = ctx.path.replace(os.sep, "/")
            if p.endswith("encoding/dtypes.py"):
                dtypes_ctx = ctx
            elif p.endswith("encoding/state.py"):
                state_ctx = ctx
        if dtypes_ctx is None:
            return
        con = _parse_dtypes_module(dtypes_ctx.tree, dtypes_ctx.path)
        if not con.arena and not con.state:
            return  # a dtypes.py predating the registry: nothing to gate

        def anchor(fname: str) -> _Site:
            return _Site(con.entry_lines.get(fname, 1), 0)

        for msg in con.problems:
            yield self.finding(dtypes_ctx.path, _Site(1, 0),
                               f"contract registry parse problem: {msg}")

        # 1. every contract names a policy constant that resolves
        for table_name, table in (("ARENA_CONTRACTS", con.arena),
                                  ("STATE_CONTRACTS", con.state)):
            for fname, (policy, _axes) in table.items():
                if policy not in con.policies:
                    yield self.finding(
                        dtypes_ctx.path, anchor(fname),
                        f"{table_name}[{fname!r}] names `{policy}`, which is "
                        "not a *_DTYPE policy constant in encoding/dtypes.py",
                    )
        for fn, params in con.kernel_args.items():
            for pname, (policy, _axes) in params.items():
                if policy not in con.policies:
                    yield self.finding(
                        dtypes_ctx.path, _Site(1, 0),
                        f"KERNEL_ARG_CONTRACTS[{fn!r}][{pname!r}] names "
                        f"`{policy}`, which is not a *_DTYPE policy constant",
                    )

        # 2. registry key sets == the NamedTuple field sets
        if state_ctx is not None:
            yield from self._check_fields(dtypes_ctx, state_ctx, con, anchor)

    # -- registry keys vs encoding/state.py -----------------------------------

    def _check_fields(self, dtypes_ctx, state_ctx, con: Contracts, anchor):
        import ast

        for cls_name, table, table_name in (
            ("EncodedCluster", con.arena, "ARENA_CONTRACTS"),
            ("ScanState", con.state, "STATE_CONTRACTS"),
        ):
            fields = None
            for node in ast.walk(state_ctx.tree):
                if isinstance(node, ast.ClassDef) and node.name == cls_name:
                    fields = [
                        item.target.id
                        for item in node.body
                        if isinstance(item, ast.AnnAssign)
                        and isinstance(item.target, ast.Name)
                    ]
                    break
            if fields is None:
                continue
            for fname in fields:
                if fname not in table:
                    yield self.finding(
                        dtypes_ctx.path, _Site(1, 0),
                        f"{cls_name} field `{fname}` (encoding/state.py) has "
                        f"no {table_name} entry — every arena field must "
                        "declare its (policy dtype, axes) contract",
                    )
            for fname in table:
                if fname not in fields:
                    yield self.finding(
                        dtypes_ctx.path, anchor(fname),
                        f"{table_name} entry `{fname}` names no {cls_name} "
                        "field (stale contract after a field removal/rename?)",
                    )
