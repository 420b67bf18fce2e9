"""server/pool.py process mode (ISSUE 15 satellite): the fork+probe path
has been opt-in and untested since PR 8 — these are its direct gates:

- the probe proves a forked worker executes and answers (and a failing
  probe falls back to threads, never a broken server);
- unpicklable tasks transparently run on the thread executor;
- COW arena inheritance reaches the worker: a worker forked AFTER the
  parent built its warm ``Prepared`` reads the inherited arenas (numpy
  only, no XLA dispatch in the child) and finds them byte for byte the
  parent's.
"""

import hashlib
import multiprocessing

import numpy as np
import pytest

from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.server import pool as pool_mod
from opensim_tpu.server.pool import WorkerPool

# module-level state the forked workers inherit copy-on-write; built
# lazily so importing this module stays cheap
_PREP = None


def _build_prep():
    global _PREP
    if _PREP is None:
        rt = ResourceTypes()
        for i in range(4):
            rt.nodes.append(fx.make_fake_node(f"n{i:02d}", "16", "64Gi"))
        rt.pods.append(
            fx.make_fake_pod("seed", "100m", "128Mi", fx.with_node_name("n00"))
        )
        app = ResourceTypes()
        app.add(fx.make_fake_deployment("cow", 3, "500m", "1Gi"))
        _PREP = prepare(rt, [AppResource("deploy", app)])
    return _PREP


def _arena_digest(prep) -> str:
    """Every host arena of ``prep`` and its pod stream, hashed."""
    h = hashlib.sha256()
    for name, arr in zip(prep.ec_np._fields, prep.ec_np):
        h.update(name.encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    h.update(np.asarray(prep.tmpl_ids).tobytes())
    return h.hexdigest()


def _cow_digest() -> str:
    """Runs INSIDE a forked worker: read the parent's arenas (numpy only —
    no XLA dispatch in the child). Module-level so it pickles by
    reference."""
    prep = _PREP  # inherited COW from the parent — never rebuilt here
    assert prep is not None, "fork did not inherit the parent's Prepared"
    return _arena_digest(prep)


def _probe_ok() -> str:
    return "alive"


def _fork_available() -> bool:
    return "fork" in multiprocessing.get_all_start_methods()


def _process_pool() -> WorkerPool:
    if not _fork_available():  # pragma: no cover - non-posix
        pytest.skip("fork start method unavailable")
    p = WorkerPool(workers=2, mode="process")
    if p.mode != "process":  # pragma: no cover - wedged platform
        p.shutdown()
        pytest.skip("process pool probe failed on this platform")
    return p


def test_probe_brings_up_process_mode_and_executes():
    p = _process_pool()
    try:
        assert p.submit(_probe_ok).result(timeout=60.0) == "alive"
    finally:
        p.shutdown()


def test_no_fork_platform_falls_back_to_threads(monkeypatch):
    monkeypatch.setattr(multiprocessing, "get_all_start_methods", lambda: ["spawn"])
    p = WorkerPool(workers=2, mode="process")
    try:
        assert p.mode == "thread"
        assert p.submit(_probe_ok).result(timeout=30.0) == "alive"
    finally:
        p.shutdown()


def test_probe_failure_falls_back_to_threads(monkeypatch):
    """A forked child that answers the probe WRONG (stand-in for a wedged
    runtime) must demote the pool to threads at startup, not surface on
    the first real request."""
    if not _fork_available():  # pragma: no cover - non-posix
        pytest.skip("fork start method unavailable")
    # fork children inherit the patched module COW, so the probe really
    # executes the broken version in the child
    monkeypatch.setattr(pool_mod, "_probe", lambda: -1)
    p = WorkerPool(workers=2, mode="process")
    try:
        assert p.mode == "thread"
    finally:
        p.shutdown()


def test_unpicklable_task_runs_on_threads():
    p = _process_pool()
    try:
        captured = []  # closure: unpicklable by reference

        def task():
            captured.append(1)
            return "threads"

        assert p.submit(task).result(timeout=30.0) == "threads"
        assert captured == [1]  # ran in THIS process (thread fallback)
        assert p._warned_unpicklable
    finally:
        p.shutdown()


def test_cow_arena_inheritance_reaches_the_worker():
    """The point of fork mode: a worker forked after the parent's warm
    prepare reads the inherited arenas — no re-prepare, and they match the
    parent's byte for byte."""
    prep = _build_prep()
    expected = _arena_digest(prep)
    # the pool is created AFTER the prep: workers inherit it copy-on-write
    p = _process_pool()
    try:
        got = p.submit(_cow_digest).result(timeout=120.0)
        assert got == expected
    finally:
        p.shutdown()
