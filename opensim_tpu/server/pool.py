"""Bounded worker pool for the concurrent serving core (ISSUE 8).

Unbatchable requests (newnodes, explain mode, mixed twin generations) used
to serialize behind the single-flight TryLock; now they run concurrently
through this pool, bounded by ``OPENSIM_WORKERS`` so a traffic spike
degrades into queueing + shedding (``server/admission.py``) instead of
unbounded thread creation.

Two modes (``OPENSIM_WORKERS_MODE``):

- ``thread`` (the ``auto`` default): a ``ThreadPoolExecutor``. The engine
  phase already parallelizes past the GIL here — the C++ scan engine runs
  through ctypes (which releases the GIL for the call) and XLA dispatches
  block off-thread — so threads buy real concurrency for the dominant
  cost. Host prep (expand + encode, pure Python/numpy) still contends.
- ``process``: a forked worker pool for the GIL-bound host half. Workers
  are forked at pool start, inheriting the server's warm NodeArenas and
  prep cache copy-on-write, and execute *closed* top-level functions
  (payload → serialized JSON-safe response) so nothing unpicklable crosses
  the pipe. Platforms without ``fork`` (or where the probe task fails —
  e.g. an XLA runtime that does not survive forking) fall back to threads
  with a warning, never a broken server. On a TPU backend process mode
  (like the ``--workers`` fleet) refuses to start: one process per chip.

The pool never owns correctness: per-entry prep-cache locks still
serialize touches of shared pod objects, exactly as on the solo path.
"""

from __future__ import annotations

import concurrent.futures
import logging
import multiprocessing
import os
from typing import Callable, Optional

from ..utils import envknobs

log = logging.getLogger("opensim_tpu.server")

__all__ = [
    "OneProcessPerChip", "WorkerPool", "one_process_per_chip", "worker_count",
    "worker_mode",
]


class OneProcessPerChip(RuntimeError):
    """A multi-process serving mode was asked for on a TPU backend."""


def one_process_per_chip(what: str) -> Optional[str]:
    """The refusal message for a multi-process serving mode on a TPU
    backend, None elsewhere. A chip belongs to one process at a time: N
    engine processes with no device assigned would crash-loop on the chip or
    serve from whatever platform each one lands on, so start-up refuses
    instead. (Initializes the JAX backend — callers that fork do it AFTER
    forking.)"""
    import jax

    if jax.default_backend() != "tpu":
        return None
    return (
        f"{what} puts more than one engine process on the accelerator, but a "
        "TPU chip belongs to one process at a time; refusing to start. Run "
        "one single-process `simon server` per chip (mapping a fleet onto "
        "chips is not supported yet)"
    )


def worker_count() -> int:
    """``OPENSIM_WORKERS``: bounded concurrency for unbatchable requests.
    Default: half the visible cores, clamped to [2, 8] — enough to overlap
    engine runs without oversubscribing the box the engines compute on. A
    typo degrades to the default with a warning (the env-knob contract
    every server knob follows), never a startup crash."""
    raw = envknobs.raw("OPENSIM_WORKERS")
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            log.warning("ignoring unparseable OPENSIM_WORKERS=%r", raw)
    return max(2, min(8, (os.cpu_count() or 2) // 2))


def worker_mode() -> str:
    raw = envknobs.raw("OPENSIM_WORKERS_MODE", "auto").strip().lower() or "auto"
    if raw not in ("auto", "thread", "process"):
        log.warning("ignoring unknown OPENSIM_WORKERS_MODE=%r (using auto)", raw)
        return "auto"
    return raw


def _probe() -> int:
    """Trivial top-level task proving a forked worker can execute and
    answer — must be module-level (picklable by reference)."""
    return 42


class WorkerPool:
    """submit(fn, *args) -> Future, over threads or forked processes.

    In process mode only *picklable* tasks cross into the forked workers;
    anything that cannot pickle (bound methods, admission Tickets carrying
    ``threading.Event``s — whose resolution could not propagate back from
    a child process anyway) transparently runs on the thread executor
    instead, with a one-time warning. A submit() can therefore never hang
    a client on an unobservable pickling error."""

    def __init__(self, workers: Optional[int] = None, mode: Optional[str] = None):
        self.workers = workers if workers is not None else worker_count()
        want = mode if mode is not None else worker_mode()
        self.mode = "thread"
        self._proc_pool: Optional[concurrent.futures.Executor] = None
        # deliberately unguarded (no `# guarded-by:`): a boolean one-shot
        # flag whose worst-case race is a duplicate log line — the GIL
        # makes the flip atomic, and the executors themselves are the
        # stdlib's thread-safe objects (everything else here is
        # init-published before the first submit())
        self._warned_unpicklable = False
        if want == "process":
            pool = self._try_process_pool()
            if pool is not None:
                self._proc_pool, self.mode = pool, "process"
            else:
                log.warning(
                    "OPENSIM_WORKERS_MODE=process unavailable on this "
                    "platform; falling back to threads"
                )
        # the thread executor always exists: it is the sole executor in
        # thread mode and the unpicklable-task fallback in process mode
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="simon-worker"
        )

    def _try_process_pool(self) -> Optional[concurrent.futures.Executor]:
        """Fork-based pool, proven live by a probe task: fork is the point
        (COW inheritance of the warm arenas), and a runtime whose forked
        children wedge (XLA holds locks across fork on some platforms)
        must surface NOW, at startup, not on the first real request."""
        if "fork" not in multiprocessing.get_all_start_methods():
            return None
        try:
            ctx = multiprocessing.get_context("fork")
            pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self.workers, mp_context=ctx
            )
            if pool.submit(_probe).result(timeout=10.0) != 42:
                pool.shutdown(wait=False)
                return None
            # checked only now: the workers forked above with no JAX backend
            # initialized, exactly as on a CPU host before this check existed
            refusal = one_process_per_chip("OPENSIM_WORKERS_MODE=process")
            if refusal is not None:
                pool.shutdown(wait=False)
                raise OneProcessPerChip(refusal)
            return pool
        except OneProcessPerChip:
            raise
        except Exception as e:  # platform-specific fork/pipe failures
            log.warning(
                "process worker pool probe failed (%s: %s)", type(e).__name__, e
            )
            return None

    def submit(self, fn: Callable, *args, **kwargs) -> concurrent.futures.Future:
        if self._proc_pool is not None:
            import pickle

            try:
                pickle.dumps((fn, args, kwargs))
            except Exception:
                if not self._warned_unpicklable:
                    self._warned_unpicklable = True
                    log.warning(
                        "process worker pool: task %r is not picklable; "
                        "running such tasks on threads instead",
                        getattr(fn, "__qualname__", fn),
                    )
                return self._pool.submit(fn, *args, **kwargs)
            return self._proc_pool.submit(fn, *args, **kwargs)
        return self._pool.submit(fn, *args, **kwargs)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
        if self._proc_pool is not None:
            self._proc_pool.shutdown(wait=False, cancel_futures=True)
