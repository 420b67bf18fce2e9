"""Array-contract abstract interpretation (the OSL18xx engine).

An abstract interpreter over :mod:`analysis.dataflow`'s per-function CFGs
computing a **(dtype, rank, symbolic-axis)** lattice for numpy/jax values,
checked against the contract registry in ``encoding/dtypes.py``
(``ARENA_CONTRACTS``/``STATE_CONTRACTS``/``KERNEL_ARG_CONTRACTS``).

Abstract value
    ``ArrayVal(dtype, axes, creations, widenings)``. ``dtype`` is one of
    the width tags (``bool/u8/i32/i64/f32/f64``) or ``None`` =
    unknown; ``axes`` is a tuple of canonical axis names (``"?"`` =
    unknown axis) or ``None`` = unknown rank. ``creations`` records
    array-creation sites (``np.zeros`` without a policy dtype, explicit
    non-policy dtypes); ``widenings`` records promotion events (a binop /
    ``np.where`` / int-division producing a wider dtype than an operand).
    Both event sets are capped at :data:`_EVENT_CAP` entries, keeping the
    lattice finite.

Lattice / termination
    Join is pointwise: dtypes and axes join to themselves when equal and
    to unknown otherwise (a two-level lattice over a finite tag set);
    event sets join by capped union over the finite universe of source
    sites in one function. Every chain therefore stabilizes and the
    generic ``forward_analyze`` worklist terminates. Interprocedural
    summaries (joined return value + parameter-to-boundary flows) are
    iterated to a fixpoint exactly like ``TaintEngine`` — a bounded
    number of rounds, then one collect pass that emits findings.

Promotion rules
    NumPy NEP-50 semantics by default: python scalars are weak (an int
    scalar never widens an array; a float scalar widens integer arrays to
    f64), ``i32 × f32 → f64``, int true-division → f64, integer
    ``sum``/``prod`` accumulate at i64. Files that import ``jax.numpy``
    use JAX's lattice instead (int × float → the float's width, no
    value-free f64 jumps) so jit kernels are not flagged with numpy-only
    promotions. The tables are verified against ``np.result_type`` /
    ``jnp.promote_types`` by tests/test_analysis_arrays.py.

Checked boundaries
    ``EncodedCluster(...)``/``ScanState(...)``/``NodeArenas(...)``
    constructor bindings (keyword, positional, and ``**dict``),
    ``._replace(...)`` on struct-typed values, and calls into the kernel
    entries declared in ``KERNEL_ARG_CONTRACTS`` (trailing-axis match, so
    batched/vmapped leading axes are allowed). Findings:

    - **OSL1801** off-policy creation: an array created without (or with
      a non-policy) dtype reaches a contract boundary of a different
      width — anchored at the creation site.
    - **OSL1802** silent upcast: a promotion event on a path reaching a
      boundary whose contract is narrower than the promoted dtype —
      anchored at the promotion site, interprocedural.
    - **OSL1803** shape-contract violation: rank or named-axis-order
      mismatch against the declared contract — anchored at the binding.

The checker only acts on *known* facts — unknown dtypes/axes never fire —
so precision is favored over recall (zero-suppression sweep).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field, replace
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple, Union

from .core import FileContext, ProjectContext
from .dataflow import Atom, DataflowEngine, FnUnit, forward_analyze, get_engine

# width tags, narrowest-first within each kind
_INT_LADDER = ("bool", "u8", "i32", "i64")
_FLOAT_LADDER = ("f32", "f64")
TAGS = _INT_LADDER + _FLOAT_LADDER

_EVENT_CAP = 4
_MAX_ROUNDS = 4

#: modules analyzed / reported on — the arena pipeline
_SCOPE = ("encoding/", "engine/", "parallel/", "ops/")

_NP_NAME_TO_TAG = {
    "bool": "bool", "bool_": "bool", "uint8": "u8", "int32": "i32",
    "int64": "i64", "float32": "f32", "float64": "f64", "double": "f64",
    # non-policy widths that a mutation / drift may introduce: keep them
    # distinguishable so the mismatch message names the real width
    "int8": "i8", "int16": "i16", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "float16": "f16", "bfloat16": "bf16",
}

_CREATORS = {
    "zeros", "ones", "empty", "full", "arange", "array", "asarray",
    "ascontiguousarray", "frombuffer", "fromiter", "linspace",
}
_LIKE_CREATORS = {"zeros_like", "ones_like", "empty_like", "full_like"}
_ARRAY_BASES = {"np", "numpy", "jnp"}
_BIN_FUNCS = {"maximum", "minimum", "fmax", "fmin", "add", "subtract",
              "multiply", "divide", "true_divide", "power", "hypot"}
_FLOAT_UFUNCS = {"log", "log2", "log10", "log1p", "exp", "expm1", "sqrt",
                 "sin", "cos", "tan", "tanh", "arctan", "arcsin", "arccos"}
_INT_ACCUM_REDUCERS = {"sum", "prod", "cumsum", "cumprod"}
_KEEP_REDUCERS = {"max", "min", "amax", "amin"}
_PASSTHROUGH_CALLS = {"copy", "device_put", "to_device", "block_until_ready",
                      "broadcast_to"}
_STRUCT_NAMES = ("EncodedCluster", "ScanState", "NodeArenas")


def npname_to_tag(name: str) -> Optional[str]:
    if name in _NP_NAME_TO_TAG:
        return _NP_NAME_TO_TAG[name]
    short = (name.replace("float", "f").replace("uint", "u")
             .replace("int", "i"))
    return short if short != name or name.startswith(("f", "u", "i")) else None


def _is_float(tag: str) -> bool:
    return tag in ("f16", "bf16", "f32", "f64")


def _rank_of(tag: str, ladder: Sequence[str]) -> int:
    try:
        return ladder.index(tag)
    except ValueError:
        return len(ladder)  # unknown exotic width: treat as widest


def promote(a: str, b: str, jax_sem: bool) -> str:
    """Promotion of two known *array* dtype tags."""
    if a == b:
        return a
    fa, fb = _is_float(a), _is_float(b)
    if fa and fb:
        return a if _rank_of(a, _FLOAT_LADDER) >= _rank_of(b, _FLOAT_LADDER) else b
    if not fa and not fb:
        return a if _rank_of(a, _INT_LADDER) >= _rank_of(b, _INT_LADDER) else b
    flt, other = (a, b) if fa else (b, a)
    if jax_sem:
        return flt  # JAX: int x float -> the float's width
    # NumPy: i32/i64 x f32 -> f64; bool/u8 x f32 -> f32
    if flt == "f32" and other in ("i32", "i64", "u32", "u64", "i16", "u16"):
        return "f64"
    return flt


def promote_weak(tag: str, scalar_kind: str, jax_sem: bool) -> str:
    """Array tag x python scalar (NEP-50 weak promotion)."""
    if scalar_kind == "float" and not _is_float(tag):
        return "f32" if jax_sem else "f64"
    return tag


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------

Event = Tuple[str, int, int, str]  # (path, line, col, description)


def _cap(events: Iterable[Event]) -> Tuple[Event, ...]:
    return tuple(sorted(set(events))[:_EVENT_CAP])


@dataclass(frozen=True)
class ArrayVal:
    """One abstract numpy/jax value."""

    dtype: Optional[str] = None
    axes: Optional[Tuple[str, ...]] = None
    creations: Tuple[Event, ...] = ()
    widenings: Tuple[Event, ...] = ()
    param_src: int = -1  # parameter index when the raw param, else -1


@dataclass(frozen=True)
class StructVal:
    """A value known to be one of the contract-carrying NamedTuples."""

    struct: str  # EncodedCluster | ScanState | NodeArenas


@dataclass(frozen=True)
class DictVal:
    """A dict literal with constant string keys and array-ish values."""

    items: Tuple[Tuple[str, ArrayVal], ...]


@dataclass(frozen=True)
class Scalar:
    """A weak python scalar ('int' | 'float' | 'bool')."""

    kind: str


Val = Union[ArrayVal, StructVal, DictVal, Scalar]


def join_vals(a: Optional[Val], b: Optional[Val]) -> Optional[Val]:
    if a == b:
        return a
    if isinstance(a, ArrayVal) and isinstance(b, ArrayVal):
        return ArrayVal(
            dtype=a.dtype if a.dtype == b.dtype else None,
            axes=a.axes if a.axes == b.axes else None,
            creations=_cap(a.creations + b.creations),
            widenings=_cap(a.widenings + b.widenings),
            param_src=a.param_src if a.param_src == b.param_src else -1,
        )
    if isinstance(a, DictVal) and isinstance(b, DictVal):
        da, db = dict(a.items), dict(b.items)
        keys = sorted(set(da) & set(db))
        joined = []
        for k in keys:
            j = join_vals(da[k], db[k])
            if isinstance(j, ArrayVal):
                joined.append((k, j))
        return DictVal(tuple(joined))
    return None


State = Dict[str, Val]


def _join_states(a: State, b: State) -> State:
    if a == b:
        return a
    out: State = {}
    for k in set(a) | set(b):
        j = join_vals(a.get(k), b.get(k)) if (k in a and k in b) else None
        if j is not None:
            out[k] = j
    return out


# ---------------------------------------------------------------------------
# contract source
# ---------------------------------------------------------------------------

_CONTRACT_BLOCKS = ("ARENA_CONTRACTS", "STATE_CONTRACTS")


@dataclass
class Contracts:
    """The registry from ``encoding/dtypes.py`` — parsed from the linted
    source when the file is in the project (so corpus fixtures and policy
    edits are honored), imported live otherwise."""

    policies: Dict[str, str] = field(default_factory=dict)  # name -> tag
    arena: Dict[str, Tuple[str, Tuple[str, ...]]] = field(default_factory=dict)
    state: Dict[str, Tuple[str, Tuple[str, ...]]] = field(default_factory=dict)
    kernel_args: Dict[str, Dict[str, Tuple[str, Tuple[str, ...]]]] = field(
        default_factory=dict
    )
    struct_params: Dict[str, str] = field(default_factory=dict)
    axis_aliases: Dict[str, str] = field(default_factory=dict)
    entry_lines: Dict[str, int] = field(default_factory=dict)  # field -> line
    source_path: Optional[str] = None
    problems: List[str] = field(default_factory=list)

    def __post_init__(self) -> None:
        self._vocab: Dict[str, str] = {}

    def _build_vocab(self) -> None:
        for table in (self.arena, self.state, *self.kernel_args.values()):
            for _tag, axes in table.values():
                for ax in axes:
                    self._vocab[ax.lower()] = ax
        for alias, canon in self.axis_aliases.items():
            self._vocab[alias.lower()] = self._vocab.get(canon.lower(), canon)

    def norm_axis(self, name: str) -> str:
        """Canonical axis for a rendered shape symbol, '?' when unknown."""
        return self._vocab.get(name.lower(), "?")

    def struct_fields(self, struct: str) -> Dict[str, Tuple[str, Tuple[str, ...]]]:
        if struct == "EncodedCluster":
            return self.arena
        if struct == "ScanState":
            return self.state
        if struct == "NodeArenas":
            # raw arenas share names (and contracts) with the assembled
            # cluster; plus the host-side gpu device-count column
            sub = {k: v for k, v in self.arena.items() if k.startswith("node_")
                   or k in ("alloc", "unschedulable", "taint_key", "taint_val",
                            "taint_effect", "label_val", "label_num")}
            sub["node_gpu_count"] = ("INT_DTYPE", ("N",))
            return sub
        return {}

    def resolve(self, entry: Tuple[str, Tuple[str, ...]]) -> Tuple[Optional[str], Tuple[str, ...], str]:
        """(tag, axes, policy-name); tag None when the policy is unknown."""
        policy, axes = entry
        return self.policies.get(policy), axes, policy


def _parse_dtypes_module(tree: ast.Module, path: str) -> Contracts:
    out = Contracts(source_path=path)
    for node in tree.body:
        target: Optional[str] = None
        value: Optional[ast.expr] = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(
            node.targets[0], ast.Name
        ):
            target, value = node.targets[0].id, node.value
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
            target, value = node.target.id, node.value
        if target is None or value is None:
            continue
        if target.endswith("_DTYPE"):
            leaf = value.attr if isinstance(value, ast.Attribute) else (
                value.id if isinstance(value, ast.Name) else None
            )
            tag = npname_to_tag(leaf) if leaf else None
            if tag is None:
                out.problems.append(
                    f"policy constant {target} does not resolve to a numpy dtype"
                )
            else:
                out.policies[target] = tag
            continue
        if target in _CONTRACT_BLOCKS + (
            "KERNEL_ARG_CONTRACTS", "AXIS_ALIASES", "STRUCT_PARAM_NAMES",
        ):
            try:
                lit = ast.literal_eval(value)
            except (ValueError, SyntaxError):
                out.problems.append(f"{target} is not a literal dict")
                continue
            if target == "ARENA_CONTRACTS":
                out.arena = lit
            elif target == "STATE_CONTRACTS":
                out.state = lit
            elif target == "KERNEL_ARG_CONTRACTS":
                out.kernel_args = lit
            elif target == "AXIS_ALIASES":
                out.axis_aliases = lit
            else:
                out.struct_params = lit
            if target in _CONTRACT_BLOCKS and isinstance(value, ast.Dict):
                for key in value.keys:
                    if isinstance(key, ast.Constant) and isinstance(key.value, str):
                        out.entry_lines[key.value] = key.lineno
    out._build_vocab()
    return out


def _live_contracts() -> Contracts:
    out = Contracts()
    try:
        from ..encoding import dtypes as D
    except ImportError as e:  # numpy-free environment: no contracts, no findings
        out.problems.append(f"cannot import encoding.dtypes: {e}")
        return out
    import numpy as np

    for name in dir(D):
        if name.endswith("_DTYPE"):
            out.policies[name] = npname_to_tag(np.dtype(getattr(D, name)).name) or "?"
    out.arena = dict(D.ARENA_CONTRACTS)
    out.state = dict(D.STATE_CONTRACTS)
    out.kernel_args = {k: dict(v) for k, v in D.KERNEL_ARG_CONTRACTS.items()}
    out.axis_aliases = dict(D.AXIS_ALIASES)
    out.struct_params = dict(D.STRUCT_PARAM_NAMES)
    out._build_vocab()
    return out


def load_contracts(project: ProjectContext) -> Contracts:
    for ctx in project.contexts:
        p = "/" + ctx.path.replace(os.sep, "/")
        if p.endswith("/encoding/dtypes.py"):
            return _parse_dtypes_module(ctx.tree, ctx.path)
    return _live_contracts()


# ---------------------------------------------------------------------------
# the interprocedural engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArrayFinding:
    code: str  # OSL1801 | OSL1802 | OSL1803
    path: str
    line: int
    col: int
    message: str


@dataclass
class ArraySummary:
    ret: Optional[ArrayVal] = None
    # (param index, struct name, field) boundaries the raw param reaches
    param_checks: Tuple[Tuple[int, str, str], ...] = ()

    def key(self) -> Tuple:
        return (self.ret, self.param_checks)


def _in_scope(path: str) -> bool:
    p = "/" + path.replace(os.sep, "/")
    return any(f"/{frag}" in p for frag in _SCOPE) and "/tests/" not in p


class ArrayEngine:
    """Summary-fixpoint driver over every in-scope function unit."""

    def __init__(self, project: ProjectContext) -> None:
        self.df: DataflowEngine = get_engine(project)
        self.contracts = load_contracts(project)
        self.summaries: Dict[str, ArraySummary] = {}
        self.quals = [
            q for q, u in self.df.units.items() if _in_scope(u.ctx.path)
        ]

    def run(self) -> List[ArrayFinding]:
        if not self.contracts.arena and not self.contracts.state:
            return []  # no registry in sight: nothing to check against
        for _round in range(_MAX_ROUNDS):
            changed = False
            for qual in self.quals:
                new = self._analyze(qual, collect=False)
                old = self.summaries.get(qual)
                if old is None or old.key() != new.key():
                    self.summaries[qual] = new
                    changed = True
            if not changed:
                break
        seen: Set[Tuple] = set()
        findings: List[ArrayFinding] = []
        for qual in self.quals:
            self._analyze(qual, collect=True, findings=findings, seen=seen)
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.code, f.message))
        return findings

    def _analyze(
        self,
        qual: str,
        collect: bool,
        findings: Optional[List[ArrayFinding]] = None,
        seen: Optional[Set[Tuple]] = None,
    ) -> ArraySummary:
        unit = self.df.units[qual]
        cfg = self.df.cfg(qual)
        summary = ArraySummary()
        pass_ = _ArrayPass(self, unit, summary, collect, findings, seen)
        forward_analyze(cfg, pass_.init_state(), pass_.transfer, _join_states)
        return summary


class _ArrayPass:
    def __init__(
        self,
        engine: ArrayEngine,
        unit: FnUnit,
        summary: ArraySummary,
        collect: bool,
        findings: Optional[List[ArrayFinding]],
        seen: Optional[Set[Tuple]],
    ) -> None:
        self.eng = engine
        self.df = engine.df
        self.con = engine.contracts
        self.unit = unit
        self.summary = summary
        self.collect = collect
        self.findings = findings
        self.seen = seen
        self.jax_sem = "jax.numpy" in unit.ctx.source or "jax import numpy" in unit.ctx.source
        self._param_checks: Set[Tuple[int, str, str]] = set(summary.param_checks)

    # -- init ----------------------------------------------------------------

    def _annotations(self) -> Dict[str, Optional[str]]:
        node = self.unit.node
        out: Dict[str, Optional[str]] = {}
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return out
        args = node.args
        for a in args.posonlyargs + args.args + args.kwonlyargs:
            ann = a.annotation
            leaf = None
            if isinstance(ann, ast.Name):
                leaf = ann.id
            elif isinstance(ann, ast.Attribute):
                leaf = ann.attr
            elif isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                leaf = ann.value.rsplit(".", 1)[-1]
            out[a.arg] = leaf
        return out

    def init_state(self) -> State:
        state: State = {}
        ann = self._annotations()
        leaf = self.unit.qual.rsplit(".", 1)[-1]
        karg = self.con.kernel_args.get(leaf, {})
        for i, p in enumerate(self.unit.params):
            a = ann.get(p)
            if a in _STRUCT_NAMES:
                state[p] = StructVal(a)
            elif p in karg:
                tag, axes, _name = self.con.resolve(karg[p])
                state[p] = ArrayVal(dtype=tag, axes=axes or None, param_src=i)
            elif a is None and p in self.con.struct_params:
                state[p] = StructVal(self.con.struct_params[p])
            else:
                state[p] = ArrayVal(param_src=i)
        return state

    # -- transfer ------------------------------------------------------------

    def transfer(self, atom: Atom, state: State) -> State:
        node = atom.node
        new = state
        if atom.role == "test":
            self.eval(node.test if hasattr(node, "test") else node, state)
            return new
        if atom.role == "iter" and isinstance(node, (ast.For, ast.AsyncFor)):
            self.eval(node.iter, state)
            return self._bind(node.target, None, new)
        if atom.role == "withitem" and isinstance(node, ast.withitem):
            self.eval(node.context_expr, state)
            if node.optional_vars is not None:
                return self._bind(node.optional_vars, None, new)
            return new
        if atom.role in ("except",):
            return new
        if atom.role == "return" and isinstance(node, ast.Return):
            if node.value is not None:
                val = self.eval(node.value, state)
                if isinstance(val, ArrayVal):
                    joined = join_vals(self.summary.ret, val) if self.summary.ret else val
                    if isinstance(joined, ArrayVal):
                        self.summary.ret = joined
            return new
        if isinstance(node, ast.Assign):
            val = self.eval(node.value, state)
            for t in node.targets:
                new = self._bind(t, val, new)
            return new
        if isinstance(node, ast.AnnAssign) and node.value is not None:
            return self._bind(node.target, self.eval(node.value, state), new)
        if isinstance(node, ast.AugAssign):
            val = self._binop(node.target, node.op, node.value, state, node)
            if isinstance(node.target, ast.Name):
                new = dict(new)
                if val is None:
                    new.pop(node.target.id, None)
                else:
                    new[node.target.id] = val
            return new
        if isinstance(node, ast.Expr):
            self.eval(node.value, state)
            return new
        if isinstance(node, (ast.Assert, ast.Raise, ast.Delete)):
            for child in ast.walk(node):
                if isinstance(child, ast.Call):
                    self.eval(child, state)
            return new
        return new

    def _bind(self, target: ast.AST, val: Optional[Val], state: State) -> State:
        if isinstance(target, ast.Name):
            state = dict(state)
            if val is None:
                state.pop(target.id, None)
            else:
                # plain aliasing keeps the raw-parameter identity: a param
                # renamed and then bound to a contract field is still the
                # caller's value (interprocedural param_checks)
                state[target.id] = val
            return state
        if isinstance(target, (ast.Tuple, ast.List)):
            out = state
            for elt in target.elts:
                out = self._bind(elt, None, out)
            return out
        return state

    # -- eval ----------------------------------------------------------------

    def eval(self, expr: ast.AST, state: State) -> Optional[Val]:
        if isinstance(expr, ast.Constant):
            v = expr.value
            if isinstance(v, bool):
                return Scalar("bool")
            if isinstance(v, int):
                return Scalar("int")
            if isinstance(v, float):
                return Scalar("float")
            return None
        if isinstance(expr, ast.Name):
            return state.get(expr.id)
        if isinstance(expr, ast.Attribute):
            return self._eval_attr(expr, state)
        if isinstance(expr, ast.Subscript):
            return self._eval_subscript(expr, state)
        if isinstance(expr, ast.BinOp):
            return self._binop(expr.left, expr.op, expr.right, state, expr)
        if isinstance(expr, ast.UnaryOp):
            inner = self.eval(expr.operand, state)
            if isinstance(expr.op, ast.Not):
                return Scalar("bool") if isinstance(inner, Scalar) else (
                    replace(inner, dtype="bool", param_src=-1)
                    if isinstance(inner, ArrayVal) else None
                )
            return inner
        if isinstance(expr, ast.BoolOp):
            vals = [self.eval(v, state) for v in expr.values]
            out = vals[0]
            for v in vals[1:]:
                out = join_vals(out, v)
            return out
        if isinstance(expr, ast.IfExp):
            self.eval(expr.test, state)
            return join_vals(self.eval(expr.body, state), self.eval(expr.orelse, state))
        if isinstance(expr, ast.Compare):
            operands = [self.eval(o, state) for o in [expr.left] + expr.comparators]
            arrays = [o for o in operands if isinstance(o, ArrayVal)]
            if arrays:
                best = max(arrays, key=lambda a: len(a.axes) if a.axes else -1)
                return ArrayVal(dtype="bool", axes=best.axes)
            return Scalar("bool")
        if isinstance(expr, ast.Call):
            return self._eval_call(expr, state)
        if isinstance(expr, ast.Dict):
            items = []
            for k, v in zip(expr.keys, expr.values):
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    av = self.eval(v, state)
                    if isinstance(av, ArrayVal):
                        items.append((k.value, av))
                else:
                    self.eval(v, state) if v is not None else None
            return DictVal(tuple(items))
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            for elt in expr.elts:
                self.eval(elt, state)
            return None
        if isinstance(expr, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)):
            return None
        if isinstance(expr, ast.Starred):
            return self.eval(expr.value, state)
        return None

    def _eval_attr(self, expr: ast.Attribute, state: State) -> Optional[Val]:
        base = self.eval(expr.value, state)
        if isinstance(base, StructVal):
            fields = self.con.struct_fields(base.struct)
            entry = fields.get(expr.attr)
            if entry is not None:
                tag, axes, _name = self.con.resolve(entry)
                return ArrayVal(dtype=tag, axes=self._norm_axes(axes))
            return None
        if isinstance(base, ArrayVal):
            if expr.attr == "T":
                return replace(
                    base,
                    axes=tuple(reversed(base.axes)) if base.axes else None,
                    param_src=-1,
                )
            if expr.attr in ("real", "imag"):
                return base
            return None
        return None

    def _eval_subscript(self, expr: ast.Subscript, state: State) -> Optional[Val]:
        base = self.eval(expr.value, state)
        if isinstance(base, DictVal):
            sl = expr.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                return dict(base.items).get(sl.value)
            return None
        if not isinstance(base, ArrayVal):
            return None
        elts = expr.slice.elts if isinstance(expr.slice, ast.Tuple) else [expr.slice]
        axes = base.axes
        if axes is not None:
            new_axes: Optional[List[str]] = []
            pos = 0
            for elt in elts:
                if isinstance(elt, ast.Slice):
                    if pos < len(axes):
                        new_axes.append("?")  # sliced extent: name no longer exact
                        pos += 1
                    else:
                        new_axes = None
                        break
                elif isinstance(elt, ast.Constant) and elt.value is None:
                    new_axes = None  # newaxis
                    break
                elif isinstance(elt, ast.Constant) and elt.value is Ellipsis:
                    new_axes = None
                    break
                else:
                    idx = self.eval(elt, state)
                    if isinstance(idx, ArrayVal):
                        new_axes = None  # fancy/mask indexing
                        break
                    if pos < len(axes):
                        pos += 1  # integer index drops the axis
                    else:
                        new_axes = None
                        break
            if new_axes is not None:
                new_axes.extend(axes[pos:])
            return replace(
                base, axes=tuple(new_axes) if new_axes is not None else None,
                param_src=-1,
            )
        return replace(base, axes=None, param_src=-1)

    # -- arithmetic ----------------------------------------------------------

    def _binop(
        self, left: ast.AST, op: ast.operator, right: ast.AST,
        state: State, site: ast.AST,
    ) -> Optional[Val]:
        l = self.eval(left, state)
        r = self.eval(right, state)
        if isinstance(l, Scalar) and isinstance(r, Scalar):
            if isinstance(op, ast.Div):
                return Scalar("float")
            kinds = {l.kind, r.kind}
            return Scalar("float" if "float" in kinds else "int")
        lav = l if isinstance(l, ArrayVal) else None
        rav = r if isinstance(r, ArrayVal) else None
        if lav is None and rav is None:
            return None
        axes = self._broadcast_axes(lav, rav)
        creations = (lav.creations if lav else ()) + (rav.creations if rav else ())
        widenings = (lav.widenings if lav else ()) + (rav.widenings if rav else ())
        dtype: Optional[str] = None
        if lav is not None and rav is not None:
            if lav.dtype and rav.dtype:
                dtype = promote(lav.dtype, rav.dtype, self.jax_sem)
                if isinstance(op, ast.Div) and not _is_float(dtype):
                    dtype = "f32" if self.jax_sem else "f64"
                if dtype not in (lav.dtype, rav.dtype) or (
                    isinstance(op, ast.Div) and dtype not in (lav.dtype, rav.dtype)
                ):
                    widenings += (self._event(site, f"{lav.dtype} x {rav.dtype} -> {dtype}"),)
        else:
            av = lav or rav
            other = r if lav is not None else l
            if isinstance(other, Scalar) and av.dtype:
                dtype = promote_weak(av.dtype, other.kind, self.jax_sem)
                if isinstance(op, ast.Div) and not _is_float(dtype):
                    dtype = "f32" if self.jax_sem else "f64"
                if dtype != av.dtype:
                    widenings += (
                        self._event(site, f"{av.dtype} x py-{other.kind} -> {dtype}"),
                    )
            # unknown operand: dtype unknown, keep the known side's axes
        return ArrayVal(
            dtype=dtype, axes=axes, creations=_cap(creations),
            widenings=_cap(widenings),
        )

    def _broadcast_axes(
        self, l: Optional[ArrayVal], r: Optional[ArrayVal]
    ) -> Optional[Tuple[str, ...]]:
        la = l.axes if l is not None else None
        ra = r.axes if r is not None else None
        if la is None:
            return ra
        if ra is None:
            return la
        if la == ra:
            return la
        if len(la) != len(ra):
            return la if len(la) > len(ra) else ra
        return None

    # -- calls ---------------------------------------------------------------

    @staticmethod
    def _dotted(expr: ast.AST) -> Optional[str]:
        parts: List[str] = []
        cur = expr
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if isinstance(cur, ast.Name):
            parts.append(cur.id)
            return ".".join(reversed(parts))
        return None

    def _event(self, node: ast.AST, desc: str) -> Event:
        return (
            self.unit.ctx.path,
            getattr(node, "lineno", 0),
            getattr(node, "col_offset", 0),
            desc,
        )

    def _resolve_dtype_arg(self, expr: ast.AST) -> Tuple[Optional[str], bool]:
        """(tag, is_policy_or_known). tag None + True = explicit-but-opaque
        (e.g. ``x.dtype``): no default-creation event, nothing to check."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return npname_to_tag(expr.value), True
        if isinstance(expr, ast.Name):
            if expr.id == "bool":
                return "bool", True
            if expr.id in self.con.policies:
                return self.con.policies[expr.id], True
            tag = npname_to_tag(expr.id)
            if tag and expr.id in _NP_NAME_TO_TAG:
                return tag, True
            return None, True
        if isinstance(expr, ast.Attribute):
            leaf = expr.attr
            if leaf in self.con.policies:
                return self.con.policies[leaf], True
            if leaf in _NP_NAME_TO_TAG:
                return _NP_NAME_TO_TAG[leaf], True
            return None, True  # x.dtype and friends: opaque
        if isinstance(expr, ast.Call):
            # np.dtype(np.float32)
            inner = expr.args[0] if expr.args else None
            if inner is not None:
                return self._resolve_dtype_arg(inner)
        return None, True

    def _axes_from_shape(self, expr: ast.AST) -> Optional[Tuple[str, ...]]:
        elts = expr.elts if isinstance(expr, (ast.Tuple, ast.List)) else [expr]
        axes = []
        for e in elts:
            axes.append(self._render_axis(e))
        return tuple(axes)

    def _render_axis(self, e: ast.AST) -> str:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            return self.con.norm_axis(str(e.value)) if self.con.norm_axis(
                str(e.value)) != "?" else str(e.value)
        name: Optional[str] = None
        if isinstance(e, ast.Name):
            name = e.id
        elif isinstance(e, ast.Attribute):
            name = e.attr
        elif (
            isinstance(e, ast.BinOp)
            and isinstance(e.op, ast.Add)
            and isinstance(e.right, ast.Constant)
            and isinstance(e.right.value, int)
        ):
            base = self._render_axis(e.left)
            if base != "?":
                name = f"{base}+{e.right.value}"
        if name is None:
            return "?"
        return self.con.norm_axis(name)

    def _norm_axes(self, axes: Tuple[str, ...]) -> Tuple[str, ...]:
        return tuple(self.con.norm_axis(a) if self.con.norm_axis(a) != "?" else a
                     for a in axes)

    def _scalar_tag(self, val: Optional[Val], jaxish: bool) -> Optional[str]:
        if isinstance(val, Scalar):
            if val.kind == "float":
                return "f32" if jaxish else "f64"
            if val.kind == "int":
                return "i32" if jaxish else "i64"
            return "bool"
        if isinstance(val, ArrayVal):
            return val.dtype
        return None

    def _eval_call(self, call: ast.Call, state: State) -> Optional[Val]:
        dotted = self._dotted(call.func)
        leaf = dotted.rsplit(".", 1)[-1] if dotted else None
        base = dotted.rsplit(".", 2)[-2] if dotted and "." in dotted else None
        if leaf is None and isinstance(call.func, ast.Attribute):
            # method chained on a call/subscript receiver, e.g.
            # np.frombuffer(b).reshape(s): _dotted can't root it at a Name,
            # but _eval_method only needs the attr + an evaluable receiver
            leaf = call.func.attr

        # struct constructors / _replace are contract boundaries
        if leaf in _STRUCT_NAMES:
            self._check_constructor(leaf, call, state)
            return StructVal(leaf)
        if leaf == "_replace" and isinstance(call.func, ast.Attribute):
            recv = self.eval(call.func.value, state)
            if isinstance(recv, StructVal):
                self._check_kwargs(recv.struct, call, state)
                return recv
            for kw in call.keywords:
                if kw.value is not None:
                    self.eval(kw.value, state)
            return None

    # kernel entry boundaries
        if leaf in self.con.kernel_args:
            self._check_kernel_call(leaf, call, state)

        # numpy/jax creators & transforms
        if base in _ARRAY_BASES and leaf is not None:
            out = self._eval_np_call(base, leaf, call, state)
            if out is not None or leaf in _CREATORS or leaf in _LIKE_CREATORS:
                return out
        if leaf is not None and isinstance(call.func, ast.Attribute):
            out = self._eval_method(leaf, call, state)
            if out is not None:
                return out

        # known helpers
        if leaf == "_grown" and len(call.args) >= 2:
            src = self.eval(call.args[0], state)
            axes = self._axes_from_shape(call.args[1])
            if isinstance(src, ArrayVal):
                return ArrayVal(dtype=src.dtype, axes=axes,
                                creations=src.creations, widenings=src.widenings)
            return ArrayVal(axes=axes)
        if leaf in _PASSTHROUGH_CALLS and call.args:
            inner = self.eval(call.args[0], state)
            if isinstance(inner, ArrayVal):
                return replace(inner, param_src=-1)
            for a in call.args[1:]:
                self.eval(a, state)
            return None

        # interprocedural: resolved project call -> summary
        target = self.df.resolve_call(self.unit, call)
        for a in call.args:
            self.eval(a, state)
        for kw in call.keywords:
            if kw.value is not None:
                self.eval(kw.value, state)
        if target is not None:
            summ = self.eng.summaries.get(target)
            if summ is not None:
                self._apply_param_checks(target, summ, call, state)
                return summ.ret
        return None

    def _eval_np_call(
        self, module_base: str, leaf: str, call: ast.Call, state: State
    ) -> Optional[Val]:
        jaxish = module_base == "jnp" or (self.jax_sem and module_base != "np")
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        if leaf in _CREATORS:
            return self._eval_creator(jaxish, leaf, call, kw, state)
        if leaf in _LIKE_CREATORS:
            src = self.eval(call.args[0], state) if call.args else None
            axes = src.axes if isinstance(src, ArrayVal) else None
            if "dtype" in kw:
                tag, _known = self._resolve_dtype_arg(kw["dtype"])
                return ArrayVal(dtype=tag, axes=axes)
            if isinstance(src, ArrayVal):
                return ArrayVal(dtype=src.dtype, axes=axes)
            return ArrayVal()
        if leaf == "where" and len(call.args) == 3:
            self.eval(call.args[0], state)
            a = self.eval(call.args[1], state)
            b = self.eval(call.args[2], state)
            return self._promote_vals(a, b, call, jaxish)
        if leaf in ("concatenate", "stack", "vstack", "hstack", "column_stack"):
            parts: List[Optional[Val]] = []
            if call.args and isinstance(call.args[0], (ast.Tuple, ast.List)):
                parts = [self.eval(e, state) for e in call.args[0].elts]
            out: Optional[Val] = parts[0] if parts else None
            for p in parts[1:]:
                out = self._promote_vals(out, p, call, jaxish)
            if isinstance(out, ArrayVal):
                return replace(out, axes=None, param_src=-1)
            return ArrayVal()
        if leaf in _BIN_FUNCS and len(call.args) >= 2:
            a = self.eval(call.args[0], state)
            b = self.eval(call.args[1], state)
            return self._promote_vals(a, b, call, jaxish)
        if leaf == "clip" and call.args:
            out = self.eval(call.args[0], state)
            for bound in call.args[1:3]:
                out = self._promote_vals(out, self.eval(bound, state), call, jaxish)
            return out if isinstance(out, ArrayVal) else None
        if leaf in _FLOAT_UFUNCS and call.args:
            src = self.eval(call.args[0], state)
            if isinstance(src, ArrayVal):
                if src.dtype and not _is_float(src.dtype):
                    dtype = "f32" if jaxish else "f64"
                    wid = src.widenings + (
                        self._event(call, f"{leaf}({src.dtype}) -> {dtype}"),
                    )
                    return replace(src, dtype=dtype, widenings=_cap(wid), param_src=-1)
                return replace(src, param_src=-1)
            return None
        if leaf in _INT_ACCUM_REDUCERS and call.args:
            return self._reduce(self.eval(call.args[0], state), leaf, call, jaxish)
        if leaf in _KEEP_REDUCERS and call.args:
            src = self.eval(call.args[0], state)
            if isinstance(src, ArrayVal):
                return replace(src, axes=None, param_src=-1)
            return None
        if leaf == "transpose" and call.args:
            src = self.eval(call.args[0], state)
            if isinstance(src, ArrayVal):
                axes = tuple(reversed(src.axes)) if src.axes and len(call.args) == 1 else None
                return replace(src, axes=axes, param_src=-1)
            return None
        if leaf in _NP_NAME_TO_TAG:  # np.float64(x) style strong scalar
            for a in call.args:
                self.eval(a, state)
            return ArrayVal(dtype=_NP_NAME_TO_TAG[leaf], axes=())
        return None

    def _eval_creator(
        self, jaxish: bool, leaf: str, call: ast.Call,
        kw: Dict[str, ast.expr], state: State,
    ) -> ArrayVal:
        fname = ("jnp." if jaxish else "np.") + leaf
        axes: Optional[Tuple[str, ...]] = None
        if leaf in ("zeros", "ones", "empty", "full") and call.args:
            axes = self._axes_from_shape(call.args[0])
        elif leaf == "arange" and call.args:
            axes = (self._render_axis(call.args[0]),) if len(call.args) == 1 else ("?",)
        elif leaf == "linspace":
            axes = ("?",)
        dtype_expr = kw.get("dtype")
        if dtype_expr is None:
            for pos, name in self._dtype_positions(leaf, call):
                dtype_expr = pos
                break
        if dtype_expr is not None:
            tag, _known = self._resolve_dtype_arg(dtype_expr)
            return ArrayVal(dtype=tag, axes=axes)
        # no dtype: default-width creation
        default: Optional[str] = None
        event_needed = True
        if leaf in ("zeros", "ones", "empty", "linspace"):
            default = None if jaxish else "f64"
        elif leaf == "frombuffer":
            default = None if jaxish else "f64"
        elif leaf == "full" and len(call.args) >= 2:
            default = self._scalar_tag(self.eval(call.args[1], state), jaxish)
        elif leaf == "arange" and call.args:
            kinds = [self.eval(a, state) for a in call.args]
            if any(isinstance(k, Scalar) and k.kind == "float" for k in kinds):
                default = "f32" if jaxish else "f64"
            else:
                # extents are ints in practice: numpy defaults to i64,
                # jax to i32 (which IS the policy width — stays clean)
                default = "i32" if jaxish else "i64"
        elif leaf in ("array", "asarray", "ascontiguousarray", "fromiter"):
            src = self.eval(call.args[0], state) if call.args else None
            if isinstance(src, ArrayVal):
                # dtype-preserving view/copy: not a creation
                return replace(src, param_src=-1)
            if isinstance(src, Scalar):
                default = self._scalar_tag(src, jaxish)
            elif call.args and isinstance(call.args[0], (ast.Tuple, ast.List)):
                default = self._literal_seq_tag(call.args[0], jaxish)
            else:
                event_needed = False  # unknown payload: don't guess
        ev: Tuple[Event, ...] = ()
        if event_needed:
            ev = (self._event(call, f"{fname} (dtype {default or 'default'})"),)
        return ArrayVal(dtype=default, axes=axes, creations=ev)

    @staticmethod
    def _dtype_positions(leaf: str, call: ast.Call):
        # positional dtype args: zeros/ones/empty(shape, dtype),
        # full(shape, fill, dtype), arange(..., dtype) is kw-only in practice
        if leaf in ("zeros", "ones", "empty") and len(call.args) >= 2:
            yield call.args[1], "dtype"
        if leaf == "full" and len(call.args) >= 3:
            yield call.args[2], "dtype"
        if leaf in ("array", "asarray", "ascontiguousarray", "frombuffer") and len(call.args) >= 2:
            yield call.args[1], "dtype"

    def _literal_seq_tag(self, seq: ast.expr, jaxish: bool) -> Optional[str]:
        has_float = False
        all_scalar = True
        for node in ast.walk(seq):
            if isinstance(node, ast.Constant):
                if isinstance(node.value, float):
                    has_float = True
                elif not isinstance(node.value, (int, bool)):
                    all_scalar = False
            elif not isinstance(node, (ast.Tuple, ast.List, ast.UnaryOp,
                                       ast.USub, ast.UAdd, ast.Load)):
                all_scalar = False
        if not all_scalar:
            return None
        if has_float:
            return "f32" if jaxish else "f64"
        return "i32" if jaxish else "i64"

    def _eval_method(self, leaf: str, call: ast.Call, state: State) -> Optional[Val]:
        assert isinstance(call.func, ast.Attribute)
        recv = self.eval(call.func.value, state)
        if not isinstance(recv, ArrayVal):
            return None
        jaxish = self.jax_sem
        if leaf == "astype" and call.args:
            tag, _known = self._resolve_dtype_arg(call.args[0])
            # an explicit cast sanctions the value: prior events cleared
            return ArrayVal(dtype=tag, axes=recv.axes)
        if leaf == "copy":
            return replace(recv, param_src=-1)
        if leaf == "reshape":
            args = call.args
            if len(args) == 1 and isinstance(args[0], (ast.Tuple, ast.List)):
                axes = self._axes_from_shape(args[0])
            elif args:
                axes = tuple(self._render_axis(a) for a in args)
            else:
                axes = None
            if axes and any(
                isinstance(a, ast.Constant) and a.value == -1
                for a in (args[0].elts if len(args) == 1 and isinstance(args[0], (ast.Tuple, ast.List)) else args)
            ):
                axes = None
            return replace(recv, axes=axes, param_src=-1)
        if leaf in ("ravel", "flatten"):
            return replace(recv, axes=None, param_src=-1)
        if leaf == "transpose":
            axes = tuple(reversed(recv.axes)) if recv.axes and not call.args else None
            return replace(recv, axes=axes, param_src=-1)
        if leaf in _INT_ACCUM_REDUCERS:
            return self._reduce(recv, leaf, call, jaxish)
        if leaf in _KEEP_REDUCERS or leaf == "mean":
            if leaf == "mean" and recv.dtype and not _is_float(recv.dtype):
                dtype = "f32" if jaxish else "f64"
                return ArrayVal(dtype=dtype, axes=None,
                                creations=recv.creations,
                                widenings=_cap(recv.widenings + (
                                    self._event(call, f"mean({recv.dtype}) -> {dtype}"),)))
            return replace(recv, axes=None, param_src=-1)
        if leaf in _PASSTHROUGH_CALLS:
            return replace(recv, param_src=-1)
        return None

    def _reduce(
        self, src: Optional[Val], leaf: str, call: ast.Call, jaxish: bool
    ) -> Optional[Val]:
        if not isinstance(src, ArrayVal):
            return None
        if src.dtype and not _is_float(src.dtype) and not jaxish and src.dtype != "i64":
            wid = src.widenings + (
                self._event(call, f"{leaf}({src.dtype}) -> i64"),
            )
            return ArrayVal(dtype="i64", axes=None, creations=src.creations,
                            widenings=_cap(wid))
        return replace(src, axes=None, param_src=-1)

    def _promote_vals(
        self, a: Optional[Val], b: Optional[Val], site: ast.AST, jaxish: bool
    ) -> Optional[Val]:
        aav = a if isinstance(a, ArrayVal) else None
        bav = b if isinstance(b, ArrayVal) else None
        if aav is None and bav is None:
            return None
        axes = self._broadcast_axes(aav, bav)
        creations = (aav.creations if aav else ()) + (bav.creations if bav else ())
        widenings = (aav.widenings if aav else ()) + (bav.widenings if bav else ())
        dtype: Optional[str] = None
        if aav is not None and bav is not None and aav.dtype and bav.dtype:
            dtype = promote(aav.dtype, bav.dtype, jaxish)
            if dtype not in (aav.dtype, bav.dtype):
                widenings += (self._event(site, f"{aav.dtype} x {bav.dtype} -> {dtype}"),)
        elif (aav is None) != (bav is None):
            av = aav or bav
            other = b if aav is not None else a
            if isinstance(other, Scalar) and av.dtype:
                dtype = promote_weak(av.dtype, other.kind, jaxish)
                if dtype != av.dtype:
                    widenings += (
                        self._event(site, f"{av.dtype} x py-{other.kind} -> {dtype}"),
                    )
        return ArrayVal(dtype=dtype, axes=axes, creations=_cap(creations),
                        widenings=_cap(widenings))

    # -- boundaries ----------------------------------------------------------

    def _check_constructor(self, struct: str, call: ast.Call, state: State) -> None:
        fields = self.con.struct_fields(struct)
        order = list(fields)
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                self.eval(arg.value, state)
                return  # positional mapping lost after *args
            if i < len(order):
                self._check_bind(struct, order[i], self.eval(arg, state), arg)
        self._check_kwargs(struct, call, state)

    def _check_kwargs(self, struct: str, call: ast.Call, state: State) -> None:
        fields = self.con.struct_fields(struct)
        for kw in call.keywords:
            if kw.arg is None:  # **mapping
                mapping = self.eval(kw.value, state)
                if isinstance(mapping, DictVal):
                    for name, av in mapping.items:
                        if name in fields:
                            self._check_bind(struct, name, av, kw.value)
                continue
            val = self.eval(kw.value, state)
            if kw.arg in fields:
                self._check_bind(struct, kw.arg, val, kw.value)

    def _check_kernel_call(self, leaf: str, call: ast.Call, state: State) -> None:
        contracts = self.con.kernel_args.get(leaf, {})
        target = self.df.resolve_call(self.unit, call)
        params: List[str] = []
        offset = 0
        if target is not None:
            callee = self.df.units[target]
            params = callee.params
            if params and params[0] in ("self", "cls") and isinstance(
                call.func, ast.Attribute
            ):
                offset = 1
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                return
            idx = i + offset
            if idx < len(params) and params[idx] in contracts:
                self._check_kernel_bind(leaf, params[idx], contracts[params[idx]],
                                        self.eval(arg, state), arg)
        for kw in call.keywords:
            if kw.arg in contracts:
                self._check_kernel_bind(leaf, kw.arg, contracts[kw.arg],
                                        self.eval(kw.value, state), kw.value)

    def _check_kernel_bind(
        self, fn: str, param: str, entry: Tuple[str, Tuple[str, ...]],
        val: Optional[Val], site: ast.AST,
    ) -> None:
        tag, axes, policy = self.con.resolve(entry)
        self._check_value(
            f"{fn}(…, {param}=…)", tag, self._norm_axes(axes), policy, val, site,
            trailing_axes=True,
        )

    def _check_bind(
        self, struct: str, fname: str, val: Optional[Val], site: ast.AST
    ) -> None:
        entry = self.con.struct_fields(struct).get(fname)
        if entry is None:
            return
        tag, axes, policy = self.con.resolve(entry)
        self._check_value(
            f"{struct}.{fname}", tag, self._norm_axes(axes), policy, val, site,
            trailing_axes=False,
        )
        if isinstance(val, ArrayVal) and val.param_src >= 0 and not self.collect:
            self._param_checks.add((val.param_src, struct, fname))
            self.summary.param_checks = tuple(sorted(self._param_checks))

    def _check_value(
        self, what: str, tag: Optional[str], want_axes: Tuple[str, ...],
        policy: str, val: Optional[Val], site: ast.AST, trailing_axes: bool,
    ) -> None:
        if not isinstance(val, ArrayVal):
            return
        if tag is not None and val.dtype is not None and val.dtype != tag:
            if val.widenings:
                for path, line, col, desc in val.widenings:
                    self._emit(
                        "OSL1802", path, line, col,
                        f"silent upcast ({desc}) reaches `{what}` "
                        f"(contract {policy}={tag}, value is {val.dtype})",
                    )
            elif val.creations:
                for path, line, col, desc in val.creations:
                    self._emit(
                        "OSL1801", path, line, col,
                        f"off-policy array creation ({desc}) reaches `{what}` "
                        f"(contract {policy}={tag}, value is {val.dtype})",
                    )
            else:
                self._emit(
                    "OSL1801", self.unit.ctx.path,
                    getattr(site, "lineno", 0), getattr(site, "col_offset", 0),
                    f"`{what}` receives a {val.dtype} value "
                    f"(contract {policy}={tag}) built without a policy dtype",
                )
        if want_axes and val.axes is not None:
            got = val.axes
            want = want_axes
            if trailing_axes and len(got) > len(want):
                got = got[len(got) - len(want):]
            if len(got) != len(want):
                self._emit(
                    "OSL1803", self.unit.ctx.path,
                    getattr(site, "lineno", 0), getattr(site, "col_offset", 0),
                    f"shape contract violation: `{what}` expects rank "
                    f"{len(want)} axes [{', '.join(want)}], got rank "
                    f"{len(val.axes)}",
                )
            elif any(
                g != "?" and w != "?" and g.lower() != w.lower()
                for g, w in zip(got, want)
            ):
                self._emit(
                    "OSL1803", self.unit.ctx.path,
                    getattr(site, "lineno", 0), getattr(site, "col_offset", 0),
                    f"shape contract violation: `{what}` expects axes "
                    f"[{', '.join(want)}], got [{', '.join(got)}]",
                )

    def _apply_param_checks(
        self, target: str, summ: ArraySummary, call: ast.Call, state: State
    ) -> None:
        if not summ.param_checks:
            return
        callee = self.df.units[target]
        offset = 0
        if callee.params and callee.params[0] in ("self", "cls") and isinstance(
            call.func, ast.Attribute
        ):
            offset = 1
        by_index = {i: a for i, a in enumerate(call.args)
                    if not isinstance(a, ast.Starred)}
        by_name = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        for pidx, struct, fname in summ.param_checks:
            arg: Optional[ast.expr] = None
            pos = pidx - offset
            if pos in by_index:
                arg = by_index[pos]
            elif pidx < len(callee.params) and callee.params[pidx] in by_name:
                arg = by_name[callee.params[pidx]]
            if arg is not None:
                self._check_bind(struct, fname, self.eval(arg, state), arg)

    def _emit(self, code: str, path: str, line: int, col: int, message: str) -> None:
        if not self.collect or self.findings is None or self.seen is None:
            return
        key = (code, path, line, col, message)
        if key in self.seen:
            return
        self.seen.add(key)
        self.findings.append(ArrayFinding(code, path, line, col, message))


def get_array_findings(project: ProjectContext) -> List[ArrayFinding]:
    cached = getattr(project, "_array_findings", None)
    if cached is None:
        cached = ArrayEngine(project).run()
        project._array_findings = cached
    return cached
