"""opensim-lint engine: rule registry, whole-program context, suppression.

The analyzer is the Python/JAX analogue of the `go vet` + race-detector
gate the reference's vendored kube-scheduler ships under: a small set of
repo-specific rules for the bug classes the tier-1 tests cannot see until
they bite on TPU or under load — host work leaking into jit-traced code,
dtype drift off the Go int64/float32 parity contract, iteration-order
nondeterminism in encoder/fingerprint streams, in-place mutation of
fingerprinted objects, swallowed exceptions, and (the OSL12xx family)
cross-module lock-discipline violations in the threaded serving core.

Two analysis tiers share one parse:

- **per-file rules** see a :class:`FileContext` (one ``ast.parse`` per
  file per run, shared by every rule — the engine never re-parses);
- **whole-program rules** additionally consult the
  :class:`ProjectContext` built once over ALL linted files: a symbol
  table (classes, their attributes, module globals, imports), a call
  graph (calls resolved through ``self``, typed locals/params, and
  module-level singletons), every ``threading.Lock/RLock/Condition``
  attribute as a named **lock node**, every ``with <lock>:`` body as a
  **critical section**, and the static **lock-acquisition graph**
  (lock A held while lock B is acquired, attributed through direct
  calls). Rules that set ``project_rule = True`` run once per project
  via :meth:`Rule.project_check` instead of once per file.

Suppression syntax (pylint-style, checked on the finding's line and on a
standalone comment line directly above it):

    do_risky_thing()  # opensim-lint: disable=jit-boundary
    # opensim-lint: disable=determinism,cache-mutation
    next_line_is_exempt()

File-level (anywhere in the first 10 lines):

    # opensim-lint: disable-file=dtype-drift

``disable=all`` suppresses every rule. Rules are addressed by short name
(``jit-boundary``) or code (``OSL101``).
"""

from __future__ import annotations

import ast
import json
import os
import re
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "Finding",
    "FileContext",
    "ProjectContext",
    "Rule",
    "RULES",
    "register",
    "lint_source",
    "lint_paths",
    "render_human",
    "render_json",
    "render_sarif",
]


@dataclass(frozen=True)
class Finding:
    """One diagnostic: rule identity + location + message."""

    rule: str  # short name, e.g. "jit-boundary"
    code: str  # stable id, e.g. "OSL101"
    path: str
    line: int
    col: int
    message: str

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "code": self.code,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
        }


@dataclass
class FileContext:
    """Parsed source handed to each rule (one parse per file per run).

    ``module`` is the dotted-module guess derived from the path (used by
    import resolution); ``project`` is the whole-program context shared by
    every file in the run (present even for a single-string lint — the
    project is just that one file then)."""

    path: str  # display path (as given / repo-relative)
    source: str
    tree: ast.Module
    lines: List[str]
    module: str = ""
    project: Optional["ProjectContext"] = None
    suppress_line: Dict[int, set] = field(default_factory=dict)
    suppress_file: set = field(default_factory=set)


class Rule:
    """Base class: subclasses set ``name``/``code`` and implement ``check``
    (per-file) or set ``project_rule = True`` and implement
    ``project_check`` (once per run, over the whole program).

    ``paths`` restricts the rule to files whose normalized path contains one
    of the fragments (empty = every file); ``exclude_paths`` wins over
    ``paths``. Per-file rules that consult ``ctx.project`` must set
    ``needs_project = True`` — the whole-program pass is only built when a
    selected rule asks for it, so ``--rules`` runs of plain AST rules skip
    the symbol-table/call-graph cost entirely."""

    name: str = ""
    code: str = ""
    description: str = ""
    paths: Tuple[str, ...] = ()
    exclude_paths: Tuple[str, ...] = ()
    project_rule: bool = False
    needs_project: bool = False

    def applies_to(self, path: str) -> bool:
        p = path.replace(os.sep, "/")
        if any(frag in p for frag in self.exclude_paths):
            return False
        return not self.paths or any(frag in p for frag in self.paths)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        raise NotImplementedError

    def project_check(self, project: "ProjectContext") -> Iterable[Finding]:
        return ()

    def finding(self, ctx_or_path, node: ast.AST, message: str) -> Finding:
        path = ctx_or_path.path if isinstance(ctx_or_path, FileContext) else str(ctx_or_path)
        return Finding(
            rule=self.name,
            code=self.code,
            path=path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
        )


RULES: Dict[str, Rule] = {}


def register(cls):
    """Class decorator adding a rule (by short name) to the registry."""
    rule = cls()
    if not rule.name or not rule.code:
        raise ValueError(f"rule {cls.__name__} needs name and code")
    RULES[rule.name] = rule
    return cls


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(r"#\s*opensim-lint:\s*(disable(?:-file)?)\s*=\s*([A-Za-z0-9_,\- ]+)")


def _suppressions(lines: List[str]) -> Tuple[Dict[int, set], set]:
    """(per-line rule sets keyed by 1-based line, file-level rule set).

    A standalone suppression comment (nothing but the comment on its line)
    also covers the next line, so fixes can keep long lines intact."""
    per_line: Dict[int, set] = {}
    file_level: set = set()
    for i, text in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        names = {n.strip().lower() for n in m.group(2).split(",") if n.strip()}
        if m.group(1) == "disable-file":
            if i <= 10:
                file_level |= names
            continue
        per_line.setdefault(i, set()).update(names)
        if text.lstrip().startswith("#"):
            per_line.setdefault(i + 1, set()).update(names)
    return per_line, file_level


def _suppressed(f: Finding, per_line: Dict[int, set], file_level: set) -> bool:
    for names in (file_level, per_line.get(f.line, ())):
        if not names:
            continue
        lowered = {f.rule.lower(), f.code.lower()}
        if "all" in names or (lowered & set(names)):
            return True
    return False


# ---------------------------------------------------------------------------
# shared AST helpers for the rule modules
# ---------------------------------------------------------------------------


def dotted_name(node: ast.AST) -> str:
    """'jax.lax.scan' for nested Attribute/Name chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


# ---------------------------------------------------------------------------
# whole-program context: symbols, call graph, locks, critical sections
# ---------------------------------------------------------------------------

#: constructors whose result is a lock object (leaf name; the root, when
#: present, must look like the threading module)
_LOCK_CTORS = {"Lock", "RLock", "Condition", "BoundedSemaphore", "Semaphore"}
_LOCK_ROOTS = {"threading", "_threading", ""}

#: with-expression names that *look* like locks when resolution fails —
#: the same heuristic OSL1001 ships (a name ending in lock/cond[ition])
_LOCKISH_SUFFIX = ("lock", "cond", "condition", "mutex")

_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z0-9_.]+)")

#: method names that mutate their receiver in place (list/dict/set/deque)
MUTATOR_METHODS = {
    "append", "appendleft", "extend", "extendleft", "insert", "remove",
    "pop", "popleft", "popitem", "clear", "update", "add", "discard",
    "setdefault", "sort", "reverse", "rotate", "move_to_end",
}


def _is_lock_ctor(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func)
    leaf = name.rsplit(".", 1)[-1] if name else ""
    root = name.split(".", 1)[0] if "." in name else ""
    return leaf in _LOCK_CTORS and root in _LOCK_ROOTS


def _contains_lock_ctor(node: ast.AST) -> bool:
    return any(_is_lock_ctor(n) for n in ast.walk(node))


@dataclass
class AttrInfo:
    """One ``self.X = ...`` instance attribute discovered in a class."""

    name: str
    lineno: int
    kind: str = "other"  # "lock" | "instance" | "other"
    rhs: Optional[ast.AST] = None
    instance_of: Optional[Tuple[str, str]] = None  # (module, Class)
    guarded_by: Optional[str] = None  # raw `# guarded-by:` token
    ann_class: Optional[str] = None  # class name from a param/attr annotation


@dataclass
class ClassInfo:
    module: str
    name: str
    node: ast.ClassDef
    bases: List[str] = field(default_factory=list)
    attrs: Dict[str, AttrInfo] = field(default_factory=dict)
    methods: Dict[str, "FunctionInfo"] = field(default_factory=dict)


@dataclass
class FunctionInfo:
    module: str
    qualname: str  # module.Class.meth or module.func
    name: str
    cls: Optional[str]
    node: ast.AST  # FunctionDef | AsyncFunctionDef


@dataclass
class ModuleInfo:
    path: str
    name: str  # dotted
    ctx: FileContext
    imports: Dict[str, Tuple[str, Optional[str]]] = field(default_factory=dict)
    globals_instance: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    globals_lock: Dict[str, str] = field(default_factory=dict)  # name -> lock id
    classes: Dict[str, ClassInfo] = field(default_factory=dict)
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)  # by bare name


@dataclass
class CriticalSection:
    lock: str  # canonical lock id (or heuristic local id)
    names: Set[str]  # raw names in the with-expression (wait exemption)
    path: str
    func: str  # enclosing function qualname
    node: ast.With


@dataclass
class CallSite:
    caller: str
    callee: Optional[str]  # resolved qualname or None
    target: str  # dotted source text of the callee expression
    path: str
    node: ast.Call
    held: Tuple[Tuple[str, frozenset], ...]  # (lock id, raw names) stack


@dataclass
class AttrAccess:
    """One resolved ``<instance-of-C>.attr`` use outside/inside locks."""

    owner: Tuple[str, str]  # (module, Class) the attribute belongs to
    attr: str
    kind: str  # "load" | "store" | "mutate"
    path: str
    func: str
    node: ast.AST
    held: Tuple[str, ...]  # lock ids held lexically at the access
    in_init: bool  # inside the owning class's __init__/__post_init__


@dataclass
class LockEdge:
    src: str
    dst: str
    path: str
    node: ast.AST
    via: str  # "" for a directly nested `with`, else the call chain text


def _module_name(path: str) -> str:
    p = path.replace(os.sep, "/")
    if p.endswith(".py"):
        p = p[:-3]
    parts = [x for x in p.split("/") if x and x not in (".", "..")]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) or "<module>"


def _annotation_class(node: Optional[ast.AST]) -> Optional[str]:
    """Extract a bare class name from a return/param annotation:
    ``X``, ``"X"``, ``Optional[X]``, ``X | None``."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value.strip().strip("'\"")
        return name.split("[")[-1].rstrip("]") if "[" in name else name
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Subscript):  # Optional[X] / List[X] -> X
        head = dotted_name(node.value).rsplit(".", 1)[-1]
        if head in ("Optional", "Union"):
            inner = node.slice
            if isinstance(inner, ast.Tuple):
                for el in inner.elts:
                    got = _annotation_class(el)
                    if got:
                        return got
            return _annotation_class(inner)
        return None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):  # X | None
        return _annotation_class(node.left) or _annotation_class(node.right)
    return None


class ProjectContext:
    """Symbol table + call graph + lock graph over every linted file."""

    def __init__(self, contexts: Sequence[FileContext]) -> None:
        self.contexts = list(contexts)
        self.by_path: Dict[str, FileContext] = {c.path: c for c in self.contexts}
        self.modules: Dict[str, ModuleInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}  # by qualname
        self.classes: Dict[Tuple[str, str], ClassInfo] = {}
        self.critical_sections: List[CriticalSection] = []
        self.call_sites: Dict[str, List[CallSite]] = {}  # callee -> sites
        self.calls_from: Dict[str, List[CallSite]] = {}  # caller -> sites
        self.accesses: List[AttrAccess] = []
        self.lock_edges: Dict[Tuple[str, str], LockEdge] = {}
        self.spawn_sites: List[Tuple[FileContext, ast.Call, str, Optional[str]]] = []
        # (ctx, call node, kind "thread"|"submit", entry qualname or None)
        self._canon_memo: Dict[Tuple[str, str, str], Optional[str]] = {}
        self._ret_memo: Dict[str, Optional[Tuple[str, str]]] = {}
        self._attr_memo: Dict[str, Set[str]] = {}  # lock -> attributed quals
        self._find_memo: Dict[str, Optional[str]] = {}
        for ctx in self.contexts:
            self._collect_symbols(ctx)
        for ctx in self.contexts:
            self._scan_functions(ctx)
        # per-path indices so per-file rules don't rescan the whole project
        # for every file (O(files x accesses) otherwise)
        self.accesses_by_path: Dict[str, List[AttrAccess]] = {}
        for acc in self.accesses:
            self.accesses_by_path.setdefault(acc.path, []).append(acc)
        self.held_sites_by_path: Dict[str, List[CallSite]] = {}
        for sites in self.calls_from.values():
            for site in sites:
                if site.held:
                    self.held_sites_by_path.setdefault(site.path, []).append(site)
        self.spawns_by_path: Dict[
            str, List[Tuple[FileContext, ast.Call, str, Optional[str]]]
        ] = {}
        for spawn in self.spawn_sites:
            self.spawns_by_path.setdefault(spawn[0].path, []).append(spawn)

    # -- naming helpers ------------------------------------------------------

    @staticmethod
    def short(lock_id: str) -> str:
        """Human-sized tail of a canonical id (messages/docs)."""
        return ".".join(lock_id.split(".")[-3:])

    def _find_module(self, target: str) -> Optional[str]:
        if target in self._find_memo:
            return self._find_memo[target]
        got: Optional[str] = None
        if target in self.modules:
            got = target
        else:
            tail = "." + target
            hits = [m for m in self.modules if m.endswith(tail)]
            if len(hits) == 1:
                got = hits[0]
        self._find_memo[target] = got
        return got

    # -- phase 1: per-module symbols ----------------------------------------

    def _collect_symbols(self, ctx: FileContext) -> None:
        mi = ModuleInfo(path=ctx.path, name=ctx.module, ctx=ctx)
        self.modules[ctx.module] = mi
        body = list(ctx.tree.body)
        top_level = set(map(id, body))
        for node in ast.walk(ctx.tree):
            # imports bind names wherever they appear — `if TYPE_CHECKING:`
            # blocks bind for annotations, and function-level imports (the
            # deferred-import idiom breaking module cycles, e.g. watch.py's
            # `from ..engine import prepcache`) must resolve for call-graph
            # attribution to see through them. Collisions with a top-level
            # name are possible in principle; in practice the idiom imports
            # the same module either way.
            if isinstance(node, (ast.Import, ast.ImportFrom)) and id(node) not in top_level:
                body.append(node)
        for node in body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    mi.imports[alias.asname or alias.name.split(".")[0]] = (
                        alias.name, None,
                    )
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    # in a package __init__ the module name already IS the
                    # package, so `from .` resolves one level higher than in
                    # a plain module
                    drop = node.level - 1 if ctx.path.endswith("__init__.py") else node.level
                    parts = ctx.module.split(".")
                    parts = parts[: max(0, len(parts) - drop)]
                    base = ".".join(parts + ([node.module] if node.module else []))
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    mi.imports[alias.asname or alias.name] = (base, alias.name)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    if _contains_lock_ctor(node.value):
                        mi.globals_lock[t.id] = f"{ctx.module}.{t.id}"
                    elif isinstance(node.value, ast.Call):
                        cname = dotted_name(node.value.func)
                        mi.globals_instance[t.id] = ("", cname)  # resolved lazily
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = FunctionInfo(
                    module=ctx.module,
                    qualname=f"{ctx.module}.{node.name}",
                    name=node.name, cls=None, node=node,
                )
                mi.functions[node.name] = fi
                self.functions[fi.qualname] = fi
            elif isinstance(node, ast.ClassDef):
                self._collect_class(ctx, mi, node)

    def _collect_class(self, ctx: FileContext, mi: ModuleInfo, node: ast.ClassDef) -> None:
        ci = ClassInfo(module=ctx.module, name=node.name, node=node)
        ci.bases = [dotted_name(b) for b in node.bases if dotted_name(b)]
        mi.classes[node.name] = ci
        self.classes[(ctx.module, node.name)] = ci
        for item in node.body:
            if isinstance(item, ast.Assign) and len(item.targets) == 1 and isinstance(
                item.targets[0], ast.Name
            ):
                # class-level attr (e.g. `_touch_lock = _threading.Lock()`)
                name = item.targets[0].id
                info = AttrInfo(name=name, lineno=item.lineno, rhs=item.value)
                if _contains_lock_ctor(item.value):
                    info.kind = "lock"
                info.guarded_by = self._guard_token(ctx, item.lineno)
                ci.attrs.setdefault(name, info)
            if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            fi = FunctionInfo(
                module=ctx.module,
                qualname=f"{ctx.module}.{node.name}.{item.name}",
                name=item.name, cls=node.name, node=item,
            )
            ci.methods[item.name] = fi
            self.functions[fi.qualname] = fi
            for sub in ast.walk(item):
                tgt = None
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    tgt, rhs = sub.targets[0], sub.value
                elif isinstance(sub, ast.AnnAssign) and sub.value is not None:
                    tgt, rhs = sub.target, sub.value
                else:
                    continue
                if not (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    continue
                info = ci.attrs.get(tgt.attr)
                is_lock = _contains_lock_ctor(rhs)
                if info is None or (is_lock and info.kind != "lock"):
                    info = AttrInfo(name=tgt.attr, lineno=sub.lineno, rhs=rhs)
                    if is_lock:
                        info.kind = "lock"
                    ci.attrs[tgt.attr] = info
                guard = self._guard_token(ctx, sub.lineno)
                if guard and not info.guarded_by:
                    info.guarded_by = guard
        # `self.X = param` in __init__ inherits the param's annotation
        init = ci.methods.get("__init__")
        if init is not None:
            ann = {
                a.arg: _annotation_class(a.annotation)
                for a in list(init.node.args.args) + list(init.node.args.kwonlyargs)
                if a.annotation is not None
            }
            for info in ci.attrs.values():
                if info.kind != "other" or info.ann_class is not None:
                    continue
                rhs = info.rhs
                # unwrap `x if x is not False else None`-style publication
                cands = [rhs]
                if isinstance(rhs, ast.IfExp):
                    cands = [rhs.body, rhs.orelse]
                for cand in cands:
                    if isinstance(cand, ast.Name) and ann.get(cand.id):
                        info.ann_class = ann[cand.id]
                        break

    @staticmethod
    def _guard_token(ctx: FileContext, lineno: int) -> Optional[str]:
        if 1 <= lineno <= len(ctx.lines):
            m = _GUARDED_BY_RE.search(ctx.lines[lineno - 1])
            if m:
                return m.group(1)
        return None

    # -- resolution ----------------------------------------------------------

    def canonical_lock(self, module: str, cls: str, attr: str) -> Optional[str]:
        """Lock id for a class attribute, following one alias level
        (``self.lock = RECORDER.lock``)."""
        key = (module, cls, attr)
        if key in self._canon_memo:
            return self._canon_memo[key]
        self._canon_memo[key] = None  # cycle guard
        ci = self.classes.get((module, cls))
        got: Optional[str] = None
        if ci is not None:
            info = ci.attrs.get(attr)
            if info is not None:
                if info.kind == "lock":
                    got = f"{module}.{cls}.{attr}"
                elif info.rhs is not None:
                    alias = self.resolve_value(info.rhs, module, cls, {})
                    if alias is not None and alias[0] == "lock":
                        got = alias[1]
        self._canon_memo[key] = got
        return got

    def class_of_instance(self, module: str, cname: str) -> Optional[Tuple[str, str]]:
        """Resolve a dotted class-name string in a module's namespace."""
        mi = self.modules.get(module)
        if mi is None:
            return None
        head, _, rest = cname.partition(".")
        if rest == "" and head in mi.classes:
            return (module, head)
        if head in mi.imports:
            tmod, sym = mi.imports[head]
            target = self._find_module(tmod)
            if sym is None:
                # `import pkg.mod as head` → rest names the class
                if target is not None and rest:
                    sub = rest.rsplit(".", 1)
                    if len(sub) == 1 and rest in self.modules[target].classes:
                        return (target, rest)
                return None
            if target is not None:
                tmi = self.modules[target]
                if rest == "" and sym in tmi.classes:
                    return (target, sym)
        return None

    def returns_instance(self, qual: str) -> Optional[Tuple[str, str]]:
        """(module, Class) a function returns, from its annotation or from
        all-return-constructor bodies."""
        if qual in self._ret_memo:
            return self._ret_memo[qual]
        self._ret_memo[qual] = None
        fi = self.functions.get(qual)
        got: Optional[Tuple[str, str]] = None
        if fi is not None:
            cname = _annotation_class(getattr(fi.node, "returns", None))
            if cname:
                got = self.class_of_instance(fi.module, cname)
            if got is None:
                for sub in ast.walk(fi.node):
                    if isinstance(sub, ast.Return) and isinstance(sub.value, ast.Call):
                        got = self.class_of_instance(
                            fi.module, dotted_name(sub.value.func)
                        )
                        if got:
                            break
        self._ret_memo[qual] = got
        return got

    def resolve_value(
        self,
        expr: ast.AST,
        module: str,
        cls: Optional[str],
        locals_: Dict[str, Tuple[str, ...]],
    ) -> Optional[Tuple]:
        """Best-effort static value of an expression:
        ``("instance", mod, Class)`` | ``("class", mod, Class)`` |
        ``("func", qualname)`` | ``("lock", lock_id)`` |
        ``("module", mod)`` | None."""
        if isinstance(expr, ast.Name):
            if expr.id == "self" and cls is not None:
                return ("instance", module, cls)
            if expr.id in locals_:
                return locals_[expr.id]
            mi = self.modules.get(module)
            if mi is None:
                return None
            if expr.id in mi.globals_lock:
                return ("lock", mi.globals_lock[expr.id])
            if expr.id in mi.globals_instance:
                got = self.class_of_instance(module, mi.globals_instance[expr.id][1])
                if got:
                    return ("instance", got[0], got[1])
                # singleton built by a factory function
                fq = self._resolve_func_name(module, mi.globals_instance[expr.id][1])
                if fq:
                    inst = self.returns_instance(fq)
                    if inst:
                        return ("instance", inst[0], inst[1])
                return None
            if expr.id in mi.classes:
                return ("class", module, expr.id)
            if expr.id in mi.functions:
                return ("func", mi.functions[expr.id].qualname)
            if expr.id in mi.imports:
                tmod, sym = mi.imports[expr.id]
                target = self._find_module(tmod)
                if sym is None:
                    return ("module", target or tmod)
                if target is not None:
                    tmi = self.modules[target]
                    if sym in tmi.classes:
                        return ("class", target, sym)
                    if sym in tmi.functions:
                        return ("func", tmi.functions[sym].qualname)
                    if sym in tmi.globals_lock:
                        return ("lock", tmi.globals_lock[sym])
                    if sym in tmi.globals_instance:
                        got = self.class_of_instance(target, tmi.globals_instance[sym][1])
                        if got:
                            return ("instance", got[0], got[1])
                # `from pkg import submodule`: the bound name IS a module
                sub = self._find_module(f"{tmod}.{sym}" if tmod else sym)
                if sub is not None:
                    return ("module", sub)
                return None
            return None
        if isinstance(expr, ast.Attribute):
            base = self.resolve_value(expr.value, module, cls, locals_)
            if base is None:
                return None
            if base[0] == "module":
                mi = self.modules.get(base[1])
                if mi is None:
                    return None
                if expr.attr in mi.classes:
                    return ("class", base[1], expr.attr)
                if expr.attr in mi.functions:
                    return ("func", mi.functions[expr.attr].qualname)
                if expr.attr in mi.globals_lock:
                    return ("lock", mi.globals_lock[expr.attr])
                if expr.attr in mi.globals_instance:
                    got = self.class_of_instance(
                        base[1], mi.globals_instance[expr.attr][1]
                    )
                    if got:
                        return ("instance", got[0], got[1])
                return None
            if base[0] == "instance":
                ci = self.classes.get((base[1], base[2]))
                if ci is None:
                    return None
                info = ci.attrs.get(expr.attr)
                if info is not None:
                    if info.kind == "lock":
                        lock = self.canonical_lock(base[1], base[2], expr.attr)
                        return ("lock", lock) if lock else None
                    inst = self.attr_instance(base[1], base[2], expr.attr)
                    if inst:
                        return ("instance", inst[0], inst[1])
                    # alias attr pointing at a lock elsewhere
                    if info.rhs is not None:
                        alias = self.resolve_value(info.rhs, base[1], base[2], {})
                        if alias is not None and alias[0] == "lock":
                            return alias
                    return None
                if expr.attr in ci.methods:
                    return ("func", ci.methods[expr.attr].qualname)
            if base[0] == "class":
                ci = self.classes.get((base[1], base[2]))
                if ci is not None:
                    if expr.attr in ci.methods:
                        return ("func", ci.methods[expr.attr].qualname)
                    info = ci.attrs.get(expr.attr)
                    if info is not None and info.kind == "lock":
                        lock = self.canonical_lock(base[1], base[2], expr.attr)
                        return ("lock", lock) if lock else None
            return None
        if isinstance(expr, ast.Call):
            f = self.resolve_value(expr.func, module, cls, locals_)
            if f is None:
                return None
            if f[0] == "class":
                return ("instance", f[1], f[2])
            if f[0] == "func":
                inst = self.returns_instance(f[1])
                if inst:
                    return ("instance", inst[0], inst[1])
            return None
        if isinstance(expr, ast.IfExp):
            return self.resolve_value(expr.body, module, cls, locals_) or self.resolve_value(
                expr.orelse, module, cls, locals_
            )
        return None

    def _resolve_func_name(self, module: str, dotted: str) -> Optional[str]:
        mi = self.modules.get(module)
        if mi is None or not dotted:
            return None
        head, _, rest = dotted.partition(".")
        if not rest and head in mi.functions:
            return mi.functions[head].qualname
        if head in mi.imports:
            tmod, sym = mi.imports[head]
            target = self._find_module(tmod)
            if target is not None:
                tmi = self.modules[target]
                name = sym if rest == "" else rest
                if name and name in tmi.functions:
                    return tmi.functions[name].qualname
        return None

    def attr_instance(self, module: str, cls: str, attr: str) -> Optional[Tuple[str, str]]:
        ci = self.classes.get((module, cls))
        if ci is None:
            return None
        info = ci.attrs.get(attr)
        if info is None:
            return None
        if info.instance_of is not None:
            return info.instance_of
        if info.ann_class is not None:
            got = self.class_of_instance(module, info.ann_class)
            if got is not None:
                info.instance_of = got
                return got
        if info.rhs is not None:
            got = self.resolve_value(info.rhs, module, cls, {})
            if got is not None and got[0] == "instance":
                info.instance_of = (got[1], got[2])
                return info.instance_of
        return None

    def is_thread_subclass(self, module: str, cls: str) -> bool:
        ci = self.classes.get((module, cls))
        if ci is None:
            return False
        return any(b.rsplit(".", 1)[-1] == "Thread" for b in ci.bases)

    # -- phase 2: per-function scan -----------------------------------------

    def _scan_functions(self, ctx: FileContext) -> None:
        mi = self.modules[ctx.module]
        for fi in list(mi.functions.values()):
            self._scan_function(ctx, fi)
        for ci in mi.classes.values():
            for fi in ci.methods.values():
                self._scan_function(ctx, fi)

    def _scan_function(self, ctx: FileContext, fi: FunctionInfo) -> None:
        locals_: Dict[str, Tuple] = {}
        node = fi.node
        # typed parameters (the typed core annotates its signatures)
        for arg in list(node.args.args) + list(node.args.kwonlyargs):
            cname = _annotation_class(arg.annotation)
            if cname:
                got = self.class_of_instance(fi.module, cname)
                if got:
                    locals_[arg.arg] = ("instance", got[0], got[1])
        # first-assignment local inference (calls with known return types)
        for sub in ast.walk(node):
            tgt = None
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 and isinstance(
                sub.targets[0], ast.Name
            ):
                tgt, rhs = sub.targets[0].id, sub.value
            elif isinstance(sub, ast.AnnAssign) and isinstance(sub.target, ast.Name):
                cname = _annotation_class(sub.annotation)
                if cname:
                    got = self.class_of_instance(fi.module, cname)
                    if got and sub.target.id not in locals_:
                        locals_[sub.target.id] = ("instance", got[0], got[1])
                continue
            else:
                continue
            if tgt in locals_:
                continue
            got = self.resolve_value(rhs, fi.module, fi.cls, locals_)
            if got is not None and got[0] in ("instance", "lock"):
                locals_[tgt] = got
        scanner = _FunctionScanner(self, ctx, fi, locals_)
        for stmt in getattr(node, "body", []):
            scanner.visit(stmt)

    def _note_call(self, site: CallSite) -> None:
        self.calls_from.setdefault(site.caller, []).append(site)
        if site.callee:
            self.call_sites.setdefault(site.callee, []).append(site)

    # -- derived queries -----------------------------------------------------

    def direct_locks(self, qual: str) -> List[CriticalSection]:
        return [cs for cs in self.critical_sections if cs.func == qual]

    def locks_within(self, qual: str, depth: int = 2, _seen=None) -> List[Tuple[str, str]]:
        """Lock ids a function acquires, through ``depth`` call levels.
        Returns (lock id, via-text) pairs."""
        if _seen is None:
            _seen = set()
        if qual in _seen or depth < 0:
            return []
        _seen.add(qual)
        out = [(cs.lock, "") for cs in self.direct_locks(qual)]
        if depth > 0:
            for site in self.calls_from.get(qual, []):
                if site.callee:
                    for lock, via in self.locks_within(site.callee, depth - 1, _seen):
                        short = site.callee.rsplit(".", 2)
                        out.append((lock, site.target or ".".join(short[-2:])))
        return out

    def attributed_to_lock(self, qual: str, lock: str) -> bool:
        """True when every intra-project call site of ``qual`` runs inside a
        critical section of ``lock`` (directly, or in a caller that is
        itself attributed — the call-graph attribution the OSL1201
        annotations lean on). A function nobody calls is NOT attributed.

        Sound on recursion: attribution is computed over the condensation
        of the caller graph, so a mutual-recursion cluster is attributed
        iff every entry INTO the cluster is held-or-attributed (and at
        least one exists) — a lock-free cycle can never attest itself,
        while a locked helper pair that recurses into each other stays
        annotation-clean. Intra-cluster call sites change no lock state
        and are ignored unless they are themselves held."""
        attributed = self._attr_memo.get(lock)
        if attributed is None:
            attributed = self._attr_memo[lock] = self._attribution_for(lock)
        return qual in attributed

    def _attribution_for(self, lock: str) -> Set[str]:
        # dependency edge q -> caller for every call site of q not already
        # inside the lock; SCCs of that graph are the recursion clusters
        deps: Dict[str, List[str]] = {}
        for qual, sites in self.call_sites.items():
            deps[qual] = [
                s.caller
                for s in sites
                if not any(lid == lock for lid, _n in s.held)
            ]
        order: List[str] = []  # iterative post-order DFS over deps
        seen: Set[str] = set()
        for root in deps:
            if root in seen:
                continue
            stack: List[Tuple[str, int]] = [(root, 0)]
            seen.add(root)
            while stack:
                node, i = stack.pop()
                nxt = deps.get(node, ())
                while i < len(nxt) and nxt[i] in seen:
                    i += 1
                if i < len(nxt):
                    stack.append((node, i + 1))
                    seen.add(nxt[i])
                    stack.append((nxt[i], 0))
                else:
                    order.append(node)
        # Kosaraju phase 2: DFS the reverse graph in reverse post-order
        rdeps: Dict[str, List[str]] = {}
        for q, callers in deps.items():
            for c in callers:
                rdeps.setdefault(c, []).append(q)
        scc_of: Dict[str, int] = {}
        for node in reversed(order):
            if node in scc_of:
                continue
            sid = len(scc_of)
            work = [node]
            scc_of[node] = sid
            while work:
                n = work.pop()
                for m in rdeps.get(n, ()):
                    if m not in scc_of and (m in deps or m in rdeps):
                        scc_of[m] = scc_of[node]
                        work.append(m)
        clusters: Dict[int, List[str]] = {}
        for q in deps:
            clusters.setdefault(scc_of[q], []).append(q)
        attributed: Set[str] = set()
        # a cluster is attributed iff every entry into it — every call
        # site of every member whose caller sits outside the cluster, plus
        # any held intra-cluster site — is inside the lock or in an
        # attributed caller, and at least one such entry exists. Iterate
        # to a fixpoint: coverage through attributed callers cascades.
        changed = True
        while changed:
            changed = False
            for sid, members in clusters.items():
                if members[0] in attributed:
                    continue
                entries = 0
                ok = True
                for q in members:
                    for s in self.call_sites.get(q, ()):
                        if any(lid == lock for lid, _n in s.held):
                            entries += 1
                            continue
                        if scc_of.get(s.caller) == sid and s.caller in deps:
                            continue  # intra-cluster, unheld: no state change
                        entries += 1
                        if s.caller not in attributed:
                            ok = False
                if ok and entries:
                    attributed.update(members)
                    changed = True
        return attributed

    def resolve_guard(self, module: str, cls: str, token: str) -> Optional[str]:
        """Resolve a ``# guarded-by:`` token to a canonical lock id: a bare
        attr of the same class, ``GLOBAL.lockattr`` / ``Class._lock`` via
        the module namespace, or a module-global lock."""
        if "." not in token:
            got = self.canonical_lock(module, cls, token)
            if got:
                return got
            mi = self.modules.get(module)
            if mi and token in mi.globals_lock:
                return mi.globals_lock[token]
            # fall through: a bare name can also be an import
            # (`from .locks import GLOBAL_LOCK`), which resolve_value sees
        try:
            expr = ast.parse(token, mode="eval").body
        except SyntaxError:
            return None  # malformed token -> the unresolved-guard finding
        got = self.resolve_value(expr, module, cls, {})
        if got is not None and got[0] == "lock":
            return got[1]
        return None


class _FunctionScanner(ast.NodeVisitor):
    """One pass over a function body: critical sections, lock edges, call
    sites (with the lexically-held lock stack), resolved attribute
    accesses. Nested defs/lambdas are scanned with the definition-site
    lock stack (the dominant use is an immediately-invoked key/callback
    while the lock is held)."""

    def __init__(self, project: ProjectContext, ctx: FileContext, fi: FunctionInfo,
                 locals_: Dict[str, Tuple]) -> None:
        self.p = project
        self.ctx = ctx
        self.fi = fi
        self.locals = locals_
        self.held: List[Tuple[str, frozenset]] = []
        self._seen_attr: Set[ast.AST] = set()
        self.in_init = fi.name in ("__init__", "__post_init__") and fi.cls is not None

    # -- helpers -------------------------------------------------------------

    def _resolve(self, expr: ast.AST):
        return self.p.resolve_value(expr, self.fi.module, self.fi.cls, self.locals)

    def _lock_of(self, expr: ast.AST) -> Optional[Tuple[str, frozenset]]:
        names = frozenset(
            n.attr if isinstance(n, ast.Attribute) else n.id
            for n in ast.walk(expr)
            if isinstance(n, (ast.Attribute, ast.Name))
        )
        got = self._resolve(expr)
        if got is not None and got[0] == "lock":
            return got[1], names
        dotted = dotted_name(expr)
        leaf = dotted.rsplit(".", 1)[-1].lower() if dotted else ""
        if leaf.endswith(_LOCKISH_SUFFIX):
            return f"{self.ctx.module}:<{dotted}>", names
        return None

    def _record_access(self, node: ast.Attribute, kind: str) -> None:
        if node in self._seen_attr:
            return
        self._seen_attr.add(node)
        base = self._resolve(node.value)
        if base is None or base[0] != "instance":
            return
        ci = self.p.classes.get((base[1], base[2]))
        if ci is None or node.attr not in ci.attrs:
            return
        self.p.accesses.append(
            AttrAccess(
                owner=(base[1], base[2]),
                attr=node.attr,
                kind=kind,
                path=self.ctx.path,
                func=self.fi.qualname,
                node=node,
                held=tuple(lid for lid, _n in self.held),
                in_init=self.in_init
                and base[1] == self.fi.module
                and base[2] == self.fi.cls
                and isinstance(node.value, ast.Name)
                and node.value.id == "self",
            )
        )

    # -- visitors ------------------------------------------------------------

    def visit_With(self, node: ast.With) -> None:
        acquired = 0
        for item in node.items:
            # the item's context expression runs under every lock acquired
            # so far (items evaluate left-to-right, each entered before the
            # next evaluates): calls made here — `with lock, open(p):` —
            # belong in the call graph with that held stack
            self.visit(item.context_expr)
            got = self._lock_of(item.context_expr)
            if got is None:
                continue
            lock_id, names = got
            self.p.critical_sections.append(
                CriticalSection(
                    lock=lock_id, names=set(names), path=self.ctx.path,
                    func=self.fi.qualname, node=node,
                )
            )
            for held_id, _hn in self.held:
                if held_id != lock_id:
                    key = (held_id, lock_id)
                    if key not in self.p.lock_edges:
                        self.p.lock_edges[key] = LockEdge(
                            src=held_id, dst=lock_id, path=self.ctx.path,
                            node=node, via="",
                        )
            self.held.append((lock_id, names))
            acquired += 1
        for stmt in node.body:
            self.visit(stmt)
        if acquired:
            del self.held[len(self.held) - acquired:]

    visit_AsyncWith = visit_With

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, ast.Subscript) and isinstance(t.value, ast.Attribute):
                self._record_access(t.value, "mutate")
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if isinstance(node.target, ast.Subscript) and isinstance(
            node.target.value, ast.Attribute
        ):
            self._record_access(node.target.value, "mutate")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        # receiver mutation: self.attr.append(...)
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in MUTATOR_METHODS
            and isinstance(node.func.value, ast.Attribute)
        ):
            self._record_access(node.func.value, "mutate")
        callee = None
        got = self._resolve(node.func)
        if got is not None and got[0] == "func":
            callee = got[1]
        elif got is not None and got[0] == "class":
            callee = f"{got[1]}.{got[2]}.__init__"
        site = CallSite(
            caller=self.fi.qualname,
            callee=callee,
            target=dotted_name(node.func),
            path=self.ctx.path,
            node=node,
            held=tuple(self.held),
        )
        self.p._note_call(site)
        # thread spawn sites (OSL1204): Thread(target=f) / pool.submit(f)
        leaf = site.target.rsplit(".", 1)[-1] if site.target else ""
        if leaf == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    tgt = self._resolve(kw.value)
                    self.p.spawn_sites.append(
                        (self.ctx, node, "thread",
                         tgt[1] if tgt and tgt[0] == "func" else None)
                    )
        elif leaf in ("submit", "start_new_thread") and node.args:
            tgt = self._resolve(node.args[0])
            self.p.spawn_sites.append(
                (self.ctx, node, "submit",
                 tgt[1] if tgt and tgt[0] == "func" else None)
            )
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        kind = "load"
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            kind = "store"
        self._record_access(node, kind)
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for stmt in node.body:  # definition-site held stack, see class doc
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self.visit(node.body)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def _select_rules(rules: Optional[Sequence[str]]) -> List[Rule]:
    if rules is None:
        return list(RULES.values())
    out = []
    by_code = {r.code.lower(): r for r in RULES.values()}
    for name in rules:
        key = name.strip().lower()
        rule = RULES.get(key) or by_code.get(key)
        if rule is None:
            raise KeyError(f"unknown rule {name!r}; known: {sorted(RULES)}")
        out.append(rule)
    return out


def _make_context(source: str, path: str) -> Tuple[Optional[FileContext], Optional[Finding]]:
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return None, Finding(
            rule="parse-error",
            code="OSL000",
            path=path,
            line=e.lineno or 1,
            col=e.offset or 0,
            message=f"syntax error: {e.msg}",
        )
    lines = source.splitlines()
    ctx = FileContext(
        path=path, source=source, tree=tree, lines=lines, module=_module_name(path)
    )
    ctx.suppress_line, ctx.suppress_file = _suppressions(lines)
    return ctx, None


def _check_file(ctx: FileContext, rules: Sequence[Rule]) -> List[Finding]:
    """Per-file rules over one context, suppression applied. Shared by the
    plain and cached lint flows so their results stay byte-identical."""
    out: List[Finding] = []
    for rule in rules:
        if rule.project_rule or not rule.applies_to(ctx.path):
            continue
        for f in rule.check(ctx):
            if not _suppressed(f, ctx.suppress_line, ctx.suppress_file):
                out.append(f)
    return out


def _check_project(project: ProjectContext, rules: Sequence[Rule]) -> List[Finding]:
    """Project-rule pass, path filters and suppression applied. Shared by
    the plain and cached lint flows."""
    out: List[Finding] = []
    for rule in rules:
        if not rule.project_rule:
            continue
        for f in rule.project_check(project):
            fctx = project.by_path.get(f.path)
            if fctx is not None and not rule.applies_to(f.path):
                continue
            if fctx is None or not _suppressed(f, fctx.suppress_line, fctx.suppress_file):
                out.append(f)
    return out


def _run(
    contexts: List[FileContext],
    parse_errors: List[Finding],
    rules: Optional[Sequence[str]],
) -> List[Finding]:
    selected = _select_rules(rules)
    project: Optional[ProjectContext] = None
    if any(r.project_rule or r.needs_project for r in selected):
        project = ProjectContext(contexts)
    findings: List[Finding] = list(parse_errors)
    for ctx in contexts:
        ctx.project = project
        findings.extend(_check_file(ctx, selected))
    if project is not None:
        findings.extend(_check_project(project, selected))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return findings


def lint_source(
    source: str,
    path: str = "<string>",
    rules: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Lint one source string (the unit tests' entry point). The
    whole-program context is built over just this file."""
    ctx, err = _make_context(source, path)
    if ctx is None:
        return [err] if err else []
    return _run([ctx], [], rules)


def _iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames if d not in ("__pycache__", ".git"))
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def lint_paths(
    paths: Sequence[str],
    rules: Optional[Sequence[str]] = None,
    stats: Optional[dict] = None,
    cache_path: Optional[str] = None,
    jobs: Optional[int] = None,
) -> List[Finding]:
    """Lint files/directories; directories are walked for ``.py`` files.
    Every file is parsed ONCE and the AST shared across all rules; pass a
    ``stats`` dict to receive ``{"files", "rules", "seconds"}`` for the
    `make lint` wall-time report.

    With ``cache_path``, lint results are cached by content hash
    (``analysis/cache.py``): unchanged files skip their per-file rules
    (and, when nothing in the project changed, everything skips — no
    parses at all). ``stats`` then also carries ``cache_hits``,
    ``cache_misses`` and ``project_pass`` ("reused"/"rebuilt"/"n/a").

    ``jobs`` fans the per-file rule tier (the cache-miss loop) out over a
    process pool; the whole-program tier stays serial (it is one shared
    symbol table). ``None`` auto-sizes to the machine; ``1`` forces the
    serial path. Results are byte-identical either way: workers return
    per-file findings that are merged back in walk order."""
    t0 = time.perf_counter()
    if cache_path is None:
        contexts: List[FileContext] = []
        parse_errors: List[Finding] = []
        for fpath in _iter_py_files(paths):
            with open(fpath, "r", encoding="utf-8") as fh:
                source = fh.read()
            ctx, err = _make_context(source, fpath)
            if ctx is not None:
                contexts.append(ctx)
            elif err is not None:
                parse_errors.append(err)
        findings = _run(contexts, parse_errors, rules)
        if stats is not None:
            stats["files"] = len(contexts) + len(parse_errors)
            stats["rules"] = len(_select_rules(rules))
            stats["seconds"] = time.perf_counter() - t0
        return findings
    findings = _lint_paths_cached(paths, rules, stats, cache_path, jobs)
    if stats is not None:
        stats["seconds"] = time.perf_counter() - t0
    return findings


def _lint_file_worker(item: Tuple[str, str, Tuple[str, ...]]) -> Tuple[str, List[dict]]:
    """Per-file rule tier for ONE file — the process-pool unit. Top-level
    so the executor can pickle it; re-parses the source (ASTs don't cross
    process boundaries) and runs the named rules through the same
    ``_check_file`` dispatch as the serial path, so findings are
    byte-identical. Returns ``(path, finding dicts)``."""
    fpath, source, rule_names = item
    selected = _select_rules(list(rule_names))
    ctx, err = _make_context(source, fpath)
    out: List[Finding] = []
    if err is not None:
        out.append(err)
    if ctx is not None:
        out.extend(_check_file(ctx, selected))
    return fpath, [f.as_dict() for f in out]


#: Below this many cache misses the pool's fork/import overhead exceeds
#: the rule work; the miss loop stays serial.
_PARALLEL_MIN_MISSES = 8


def _resolve_jobs(jobs: Optional[int], n_misses: int) -> int:
    """Worker count for the per-file tier. ``None``/``0`` auto-sizes to
    the machine (capped — lint is parse-bound, not embarrassingly wide);
    small miss counts and single-core boxes degrade to serial."""
    if not jobs:
        jobs = min(os.cpu_count() or 1, 8)
    if jobs <= 1 or n_misses < _PARALLEL_MIN_MISSES:
        return 1
    return min(jobs, n_misses)


def _lint_paths_cached(
    paths: Sequence[str],
    rules: Optional[Sequence[str]],
    stats: Optional[dict],
    cache_path: str,
    jobs: Optional[int] = None,
) -> List[Finding]:
    """The content-hash-cached lint flow (see :mod:`analysis.cache`).

    Rule split: *local* rules (per-file, no whole-program context) cache
    per file; *global* rules (``project_rule`` or ``needs_project``) cache
    as one unit keyed by a digest over every file hash — the symbol
    table/call graph they consult is global, so any edit rebuilds them."""
    import hashlib

    from .cache import LintCache, analyzer_fingerprint

    selected = _select_rules(rules)
    local_rules = [r for r in selected if not (r.project_rule or r.needs_project)]
    global_rules = [r for r in selected if r.project_rule or r.needs_project]
    local_key = ",".join(sorted(r.code for r in local_rules))
    cache = LintCache(cache_path)

    sources: Dict[str, str] = {}
    shas: Dict[str, str] = {}
    order: List[str] = []
    for fpath in _iter_py_files(paths):
        with open(fpath, "r", encoding="utf-8") as fh:
            src = fh.read()
        order.append(fpath)
        sources[fpath] = src
        shas[fpath] = hashlib.sha256(src.encode()).hexdigest()

    local_findings: List[Finding] = []
    misses: List[str] = []
    hits = 0
    for fpath in order:
        got = cache.file_findings(fpath, shas[fpath], local_key)
        if got is not None:
            hits += 1
            local_findings.extend(Finding(**d) for d in got)
        else:
            misses.append(fpath)

    h = hashlib.sha256()
    for fpath in order:
        h.update(fpath.encode())
        h.update(shas[fpath].encode())
    h.update(",".join(sorted(r.code for r in global_rules)).encode())
    h.update(analyzer_fingerprint().encode())
    project_digest = h.hexdigest()

    project_findings: List[Finding] = []
    project_state = "n/a"
    cached_project = cache.project_findings(project_digest) if global_rules else None
    if global_rules and cached_project is not None:
        project_findings = [Finding(**d) for d in cached_project]
        project_state = "reused"

    # parse what we must: cache-missed files (unless pool workers will
    # re-parse them in their own processes); every file when the project
    # pass has to rebuild
    use_jobs = _resolve_jobs(jobs, len(misses))
    need_parse = set(misses) if use_jobs == 1 else set()
    if global_rules and cached_project is None:
        need_parse = set(order)
        project_state = "rebuilt"
    pos = {p: i for i, p in enumerate(order)}
    contexts: Dict[str, FileContext] = {}
    parse_errors: Dict[str, Finding] = {}
    for fpath in sorted(need_parse, key=pos.__getitem__):
        ctx, err = _make_context(sources[fpath], fpath)
        if ctx is not None:
            contexts[fpath] = ctx
        elif err is not None:
            parse_errors[fpath] = err

    # per-file rules over the cache misses (same dispatch as _run). With
    # jobs > 1 the misses fan out over a process pool — each worker
    # re-parses its file and returns finding dicts; merging back in walk
    # order keeps the output byte-identical to the serial loop.
    miss_results: Dict[str, List[dict]] = {}
    if use_jobs > 1:
        from concurrent.futures import ProcessPoolExecutor

        rule_names = tuple(r.name for r in local_rules)
        payload = [(p, sources[p], rule_names) for p in misses]
        with ProcessPoolExecutor(max_workers=use_jobs) as pool:
            for fpath, dicts in pool.map(_lint_file_worker, payload):
                miss_results[fpath] = dicts
    else:
        for fpath in misses:
            out: List[Finding] = []
            err = parse_errors.get(fpath)
            if err is not None:
                out.append(err)
            ctx = contexts.get(fpath)
            if ctx is not None:
                out.extend(_check_file(ctx, local_rules))
            miss_results[fpath] = [f.as_dict() for f in out]
    for fpath in misses:
        dicts = miss_results[fpath]
        cache.put_file(fpath, shas[fpath], local_key, dicts)
        local_findings.extend(Finding(**d) for d in dicts)

    # whole-program pass when anything changed (same dispatch as _run)
    if global_rules and project_state == "rebuilt":
        ordered_ctx = [contexts[p] for p in order if p in contexts]
        project = ProjectContext(ordered_ctx)
        for ctx in ordered_ctx:
            ctx.project = project
        out = []
        for ctx in ordered_ctx:
            out.extend(_check_file(ctx, global_rules))
        out.extend(_check_project(project, global_rules))
        project_findings = out
        cache.put_project(project_digest, [f.as_dict() for f in out])

    cache.prune(order)
    cache.save()
    findings = local_findings + project_findings
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    if stats is not None:
        stats["files"] = len(order)
        stats["rules"] = len(selected)
        stats["cache_hits"] = hits
        stats["cache_misses"] = len(misses)
        stats["project_pass"] = project_state
        stats["jobs"] = use_jobs
    return findings


def render_human(findings: List[Finding], stats: Optional[dict] = None) -> str:
    lines = [
        f"{f.path}:{f.line}:{f.col + 1}: {f.code} [{f.rule}] {f.message}" for f in findings
    ]
    tail = f"opensim-lint: {len(findings)} finding(s)" if findings else "opensim-lint: clean"
    if stats:
        tail += (
            f" ({stats.get('files', 0)} files parsed once, "
            f"{stats.get('rules', 0)} rules, {stats.get('seconds', 0.0):.2f}s)"
        )
        if "cache_hits" in stats:
            tail += (
                f" [cache: {stats['cache_hits']} hit / "
                f"{stats.get('cache_misses', 0)} miss, project pass "
                f"{stats.get('project_pass', 'n/a')}, "
                f"{stats.get('jobs', 1)} worker(s)]"
            )
    lines.append(tail)
    return "\n".join(lines)


def render_json(findings: List[Finding]) -> str:
    return json.dumps([f.as_dict() for f in findings], indent=2)


def render_sarif(findings: List[Finding]) -> str:
    """SARIF 2.1.0 — CI annotators and editors ingest this directly
    (``python -m opensim_tpu.analysis --format sarif``)."""
    rule_ids: Dict[str, dict] = {}
    for r in RULES.values():
        rule_ids[r.code] = {
            "id": r.code,
            "name": r.name,
            "shortDescription": {"text": r.description or r.name},
        }
    rule_ids["OSL000"] = {
        "id": "OSL000",
        "name": "parse-error",
        "shortDescription": {"text": "file failed to parse"},
    }
    results = []
    for f in findings:
        results.append(
            {
                "ruleId": f.code,
                "level": "error" if f.code == "OSL000" else "warning",
                "message": {"text": f"[{f.rule}] {f.message}"},
                "locations": [
                    {
                        "physicalLocation": {
                            "artifactLocation": {
                                "uri": f.path.replace(os.sep, "/"),
                            },
                            "region": {
                                "startLine": max(1, f.line),
                                "startColumn": f.col + 1,
                            },
                        }
                    }
                ],
            }
        )
    doc = {
        "$schema": "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/master/Schemata/sarif-schema-2.1.0.json",
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "opensim-lint",
                        "informationUri": "docs/static-analysis.md",
                        "rules": sorted(rule_ids.values(), key=lambda r: r["id"]),
                    }
                },
                "results": results,
            }
        ],
    }
    return json.dumps(doc, indent=2)
