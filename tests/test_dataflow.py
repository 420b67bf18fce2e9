"""The interprocedural dataflow engine (analysis/dataflow.py): CFG shape
and reaching definitions, effect inference as a call-graph fixpoint, jit
region tracking through decorators/partials/markers, the forward taint
lattice (flow-sensitive, sanitizer-aware, interprocedural via summaries),
and the tracer-leak pass."""

import ast
import os
import textwrap

from opensim_tpu.analysis import dataflow as dfm
from opensim_tpu.analysis.core import ProjectContext, _make_context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _project(src, path="opensim_tpu/server/fixture.py"):
    ctx, err = _make_context(textwrap.dedent(src), path)
    assert err is None, err
    return ProjectContext([ctx])


def _engine(src, path="opensim_tpu/server/fixture.py"):
    return dfm.DataflowEngine(_project(src, path))


MOD = "opensim_tpu.server.fixture"


# ---------------------------------------------------------------------------
# CFG + reaching definitions
# ---------------------------------------------------------------------------


def test_cfg_if_else_shape():
    src = textwrap.dedent(
        """
        def f(a):
            if a:
                x = 1
            else:
                x = 2
            return x
        """
    )
    fn = ast.parse(src).body[0]
    cfg = dfm.build_cfg(fn)
    # entry block must branch two ways and both arms rejoin before exit
    entry_succ = cfg.blocks[cfg.entry].succ
    assert len(entry_succ) == 2
    preds = cfg.preds()
    join = [b.id for b in cfg.blocks if len(preds[b.id]) == 2]
    assert join, "no join block for the if/else"


def test_cfg_while_has_back_edge():
    src = textwrap.dedent(
        """
        def f(n):
            i = 0
            while i < n:
                i = i + 1
            return i
        """
    )
    cfg = dfm.build_cfg(ast.parse(src).body[0])
    back = [
        (b.id, s)
        for b in cfg.blocks
        for s in b.succ
        if s < b.id  # an edge to an earlier block = the loop back edge
    ]
    assert back


def test_reaching_defs_join_over_branches():
    src = textwrap.dedent(
        """
        def f(a):
            x = 1
            if a:
                x = 2
            return x
        """
    )
    cfg = dfm.build_cfg(ast.parse(src).body[0])
    reach = cfg.reaching_defs()
    # at the block holding `return x`, both defs of x (lines 2 and 4) may reach
    ret_block = next(
        b.id
        for b in cfg.blocks
        if any(isinstance(a.node, ast.Return) for a in b.atoms)
    )
    assert reach[ret_block].get("x") == {3, 5}


def test_cfg_try_edges_into_handlers():
    src = textwrap.dedent(
        """
        def f(g):
            try:
                x = g()
            except ValueError:
                x = 0
            return x
        """
    )
    cfg = dfm.build_cfg(ast.parse(src).body[0])
    handler = next(
        b.id
        for b in cfg.blocks
        if any(a.role == "except" for a in b.atoms)
    )
    assert cfg.preds()[handler], "handler unreachable"


# ---------------------------------------------------------------------------
# function discovery: nested scopes
# ---------------------------------------------------------------------------


def test_units_include_nested_class_methods():
    eng = _engine(
        """
        def make_handler(server):
            class Handler:
                def do_GET(self):
                    return server

            return Handler
        """
    )
    assert f"{MOD}.make_handler.Handler.do_GET" in eng.units


def test_self_calls_resolve_inside_nested_classes():
    eng = _engine(
        """
        def make_handler():
            class Handler:
                def helper(self):
                    return 1

                def do_GET(self):
                    return self.helper()
        """
    )
    do_get = eng.units[f"{MOD}.make_handler.Handler.do_GET"]
    calls = list(eng._own_calls(do_get))
    assert eng.resolve_call(do_get, calls[0]) == f"{MOD}.make_handler.Handler.helper"


# ---------------------------------------------------------------------------
# effect inference
# ---------------------------------------------------------------------------


def test_direct_effects_by_kind():
    eng = _engine(
        """
        import os
        import random
        import time

        G = {}

        def clock():
            return time.monotonic()

        def rng():
            return random.random()

        def io():
            return open("/tmp/x")

        def sync(x):
            return x.item()

        def state(v):
            G["k"] = v

        def pure(a, b):
            return a + b
        """
    )
    kinds = {
        name: {e.kind for e in eng.direct_effects(f"{MOD}.{name}")}
        for name in ("clock", "rng", "io", "sync", "state", "pure")
    }
    assert kinds == {
        "clock": {"clock"},
        "rng": {"rng"},
        "io": {"io"},
        "sync": {"host-sync"},
        "state": {"state-write"},
        "pure": set(),
    }


def test_transitive_effects_fixpoint_through_recursion():
    eng = _engine(
        """
        import time

        def a(n):
            return b(n - 1) if n else 0

        def b(n):
            time.sleep(0.1)
            return a(n)
        """
    )
    eff = eng.transitive_effects(f"{MOD}.a")
    assert any(e.kind == "clock" for e in eff), "effect did not propagate through the cycle"
    assert eff[next(iter(eff))] == f"{MOD}.b"  # attributed to the direct site


def test_np_coercion_only_flags_parameters():
    eng = _engine(
        """
        import numpy as np

        def on_param(x):
            return np.asarray(x)

        def on_static():
            table = [1, 2, 3]
            return np.asarray(table)
        """
    )
    assert {e.kind for e in eng.direct_effects(f"{MOD}.on_param")} == {"host-sync"}
    assert eng.direct_effects(f"{MOD}.on_static") == ()


# ---------------------------------------------------------------------------
# jit regions
# ---------------------------------------------------------------------------


def test_jit_roots_decorator_partial_marker_and_scan_arg():
    eng = _engine(
        """
        import functools

        import jax

        @jax.jit
        def decorated(x):
            return x

        @functools.partial(jax.jit, static_argnames=("n",))
        def partial_decorated(x, n):
            return x

        def marked(x):  # opensim-lint: jit-region
            return x

        def body(c, x):
            return c, x

        def outer(xs):
            f = functools.partial(body)
            return jax.lax.scan(f, 0, xs)
        """
    )
    roots = eng.jit_roots()
    for name in ("decorated", "partial_decorated", "marked", "body"):
        assert f"{MOD}.{name}" in roots, (name, roots)
    assert f"{MOD}.outer" not in roots


def test_jit_reachability_crosses_call_graph():
    eng = _engine(
        """
        import jax

        def leaf(c):
            return c * 2

        def mid(c):
            return leaf(c)

        @jax.jit
        def root(x):
            return mid(x)

        def host(x):
            return leaf(x)
        """
    )
    reach = eng.jit_reachable()
    assert f"{MOD}.leaf" in reach and f"{MOD}.mid" in reach
    root, chain = reach[f"{MOD}.leaf"]
    assert root == f"{MOD}.root"
    assert chain == (f"{MOD}.root", f"{MOD}.mid")


def test_module_marker_promotes_every_function():
    eng = _engine(
        """
        # opensim-lint: jit-region-module
        def anything(x):
            return x
        """
    )
    assert f"{MOD}.anything" in eng.jit_roots()


# ---------------------------------------------------------------------------
# taint
# ---------------------------------------------------------------------------


def _hits(src, path="opensim_tpu/server/fixture.py"):
    return dfm.get_taint_hits(_project(src, path))


def test_taint_source_to_sink_intraprocedural():
    hits = _hits(
        """
        from urllib.parse import parse_qs

        def handler(q):
            name = parse_qs(q).get("f", [""])[-1]
            return open(name)
        """
    )
    assert len(hits) == 1
    assert hits[0].sink == "open()"
    assert "http-query" in hits[0].desc


def test_taint_is_flow_sensitive_about_sanitizers():
    # sanitize-then-open is clean; open-then-sanitize still fires
    clean = """
        from urllib.parse import parse_qs

        def sanitizer(fn):
            return fn

        @sanitizer
        def check(p):
            return p

        def handler(q):
            p = parse_qs(q).get("f", [""])[-1]
            p = check(p)
            return open(p)
        """
    assert _hits(clean) == []
    dirty = """
        from urllib.parse import parse_qs

        def sanitizer(fn):
            return fn

        @sanitizer
        def check(p):
            return p

        def handler(q):
            p = parse_qs(q).get("f", [""])[-1]
            fh = open(p)
            p = check(p)
            return fh
        """
    assert len(_hits(dirty)) == 1


def test_taint_interprocedural_param_to_sink():
    hits = _hits(
        """
        import sys

        def writer(path, data):
            with open(path, "w") as fh:
                fh.write(data)

        def main():
            writer(sys.argv[1], "x")
        """
    )
    assert len(hits) == 1
    assert "via writer()" in hits[0].desc and "cli-arg" in hits[0].desc


def test_taint_through_returns_and_coercions():
    hits = _hits(
        """
        def read_name(q):
            from urllib.parse import parse_qs

            return parse_qs(q).get("n", [""])[-1]

        def numeric(q):
            return int(read_name(q))  # coercion sanitizes

        def bad(q):
            return open(read_name(q))  # tainted return into sink

        def fine(q):
            return open("fixed-%d.log" % numeric(q))
        """
    )
    assert len(hits) == 1
    assert hits[0].unit.endswith(".bad")


def test_taint_yaml_documents():
    hits = _hits(
        """
        import yaml

        def load(path):
            doc = yaml.safe_load(open(path).read())
            return open(doc["include"])
        """
    )
    assert any("yaml-field" in h.desc for h in hits)


# ---------------------------------------------------------------------------
# tracer leaks
# ---------------------------------------------------------------------------


def test_tracer_leak_instance_and_module_state():
    leaks = dfm.get_tracer_leaks(
        _project(
            """
            import jax
            import jax.numpy as jnp

            _LAST = []

            class Rec:
                @jax.jit
                def step(self, x):
                    y = jnp.sum(x)
                    self.last = y
                    _LAST.append(x)
                    n = int(3)
                    self.gen = n        # concrete: clean
                    local = [y]
                    local.append(y)     # local container: clean
                    return y
            """
        )
    )
    sinks = sorted(h.sink for h in leaks)
    assert len(leaks) == 2
    assert any("self.last" in s for s in sinks)
    assert any("_LAST" in s for s in sinks)
