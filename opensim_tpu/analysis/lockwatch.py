"""lockwatch — runtime lock-order sanitizer for the threaded serving core.

The dynamic half of the OSL12xx concurrency family (the static half is
``analysis/rules_concurrency.py``): a lockdep-style instrumented lock
wrapper that records, per thread, the stack of currently-held locks and
folds every observed acquisition order into one process-global order
graph. The moment two locks are ever taken in both orders — even on two
different *runs through the code*, never mind an actual interleaving —
the cycle is reported with both acquisition stacks. This is how the Go
reference gets its guarantees from ``-race`` + deadlock-free informer
discipline without ever deadlocking in CI: the *order violation* is
caught deterministically, the deadlock itself would need scheduler luck.

Also measured: per-acquisition **hold time**. A critical section that
holds any lock longer than ``OPENSIM_LOCKWATCH_HOLD_MS`` (default 500)
is recorded as an outlier with its release stack — the convoy-maker
OSL1001/OSL1203 hunt statically, caught at runtime.

Usage:

- ``make tsan`` (tools/tsan.py): installs the wrapper, runs the threaded
  test modules under it, fails on any inversion or hold-time outlier,
  and proves the detector works via a seeded A→B/B→A self-test.
- ``OPENSIM_LOCKWATCH=1 python ...`` + :func:`install` early in startup:
  every ``threading.Lock()`` / ``threading.RLock()`` (and therefore
  ``Condition``/``Event`` internals) created *afterwards from repo code*
  is instrumented. Locks created from stdlib/third-party frames are left
  raw, so the graph stays signal.

Design notes:

- Lock **identity is the creation site** (``file:line``), not the object:
  every ``Timeline._lock`` instance shares one graph node, exactly like
  lockdep's lock-class keying. Same-site pairs (two cache entries' locks)
  are not ordered against each other — document hierarchies separately.
- The bookkeeping mutex is a raw ``_thread`` lock and is strictly
  leaf-level (never held while taking a user lock), so the sanitizer
  cannot deadlock the program it watches.
- ``Condition.wait`` support: the wrapper implements the
  ``_release_save``/``_acquire_restore``/``_is_owned`` protocol, so a
  wait correctly pops the lock from the held stack (a parked consumer is
  NOT holding its lock) and hold time is charged per ownership segment,
  not across the wait.
"""

from __future__ import annotations

import _thread
import linecache
import logging
import os
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

from ..utils import envknobs

log = logging.getLogger("opensim_tpu.analysis")

__all__ = ["LockWatch", "TracedLock", "enabled", "install", "uninstall", "current"]


def enabled() -> bool:
    """``OPENSIM_LOCKWATCH=1`` switches the sanitizer on (tools/tsan.py
    sets it; production serving never pays the bookkeeping)."""
    return envknobs.raw("OPENSIM_LOCKWATCH").strip().lower() in ("1", "on", "true")


def hold_threshold_ms() -> float:
    """``OPENSIM_LOCKWATCH_HOLD_MS`` (default 500): ownership segments
    longer than this are reported as hold-time outliers. A typo degrades
    to the default with a warning (the env-knob contract)."""
    raw = envknobs.raw("OPENSIM_LOCKWATCH_HOLD_MS")
    if raw:
        try:
            return max(1.0, float(raw))
        except ValueError:
            log.warning("ignoring unparseable OPENSIM_LOCKWATCH_HOLD_MS=%r", raw)
    return 500.0


def hold_exempt() -> Tuple[str, ...]:
    """``OPENSIM_LOCKWATCH_HOLD_EXEMPT``: comma-separated creation-site
    substrings whose holds are tracked but never *outliers* (an ad-hoc
    escape hatch for local runs; empty by default so a new convoy-maker
    anywhere fails ``make tsan``). The durable mechanism is per-lock: a
    trailing ``# lockwatch: hold-exempt`` comment on the creating source
    line, justification riding the same line, mirroring the opensim-lint
    suppression convention — the by-design long holders (REST
    single-flight/probe locks, prep-cache per-entry lock, watch flush
    lock, all of which span engine work whose latency is gated by
    loadgen-smoke instead) are marked that way. Inversions
    are NEVER exempt either way."""
    raw = envknobs.raw("OPENSIM_LOCKWATCH_HOLD_EXEMPT")
    return tuple(s.strip() for s in raw.split(",") if s.strip())


def _stack(limit: int = 14) -> str:
    frames = traceback.extract_stack()
    keep = [
        f"{os.path.basename(fr.filename)}:{fr.lineno} in {fr.name}"
        for fr in frames
        if "lockwatch" not in fr.filename
    ]
    return " <- ".join(reversed(keep[-limit:]))


class LockWatch:
    """The global order graph + per-thread held stacks. One instance is
    process-global under :func:`install`; tests build private instances
    and wrap locks explicitly with :meth:`wrap`."""

    def __init__(
        self,
        hold_ms: Optional[float] = None,
        hold_exempt_sites: Optional[Tuple[str, ...]] = None,
    ) -> None:
        self._mu = _thread.allocate_lock()  # leaf-only bookkeeping lock
        self._tls = threading.local()
        self.hold_ms = hold_threshold_ms() if hold_ms is None else float(hold_ms)
        self.hold_exempt_sites = (
            hold_exempt() if hold_exempt_sites is None else hold_exempt_sites
        )
        self.locks_created = 0
        self.acquisitions = 0
        # id(lock) -> (owner thread's counts dict, held-stack entry): lets a
        # cross-thread release (legal on a plain Lock — handoff signaling)
        # find and close the acquiring thread's entry instead of leaving it
        # stale on that thread's stack manufacturing false order edges
        self._live: Dict[int, Tuple[dict, list]] = {}
        # (src_name, dst_name) -> {"count", "stack"} — first observed stack
        self.edges: Dict[Tuple[str, str], dict] = {}
        self.adj: Dict[str, set] = {}
        self.inversions: List[dict] = []
        self.hold_outliers: List[dict] = []
        self.max_hold_ms: Dict[str, float] = {}
        self._seen_cycles: set = set()

    # -- per-thread state ----------------------------------------------------

    def _stackframe(self) -> List[list]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _counts(self) -> Dict[int, int]:
        c = getattr(self._tls, "counts", None)
        if c is None:
            c = self._tls.counts = {}
        return c

    # -- graph ---------------------------------------------------------------

    def _path(self, src: str, dst: str) -> Optional[List[str]]:
        """A path src -> ... -> dst in the order graph (caller holds _mu)."""
        seen = {src}
        stackq = [(src, [src])]
        while stackq:
            node, path = stackq.pop()
            for nxt in self.adj.get(node, ()):
                if nxt == dst:
                    return path + [dst]
                if nxt not in seen:
                    seen.add(nxt)
                    stackq.append((nxt, path + [nxt]))
        return None

    def note_acquire(self, lock: "TracedLock") -> None:
        """Called before a first-level acquire: record edges from every
        held lock to this one, detecting inversions as they form."""
        held = self._prune(self._stackframe())
        with self._mu:
            self.acquisitions += 1
        if not held:
            return
        dst = lock.name
        for entry in held:
            src = entry[0].name
            if src == dst:
                continue  # same lock class (e.g. two cache entries): unordered
            key = (src, dst)
            with self._mu:
                known = key in self.edges
            if known:
                with self._mu:
                    self.edges[key]["count"] += 1
                continue
            stack = _stack()
            with self._mu:
                if key in self.edges:
                    self.edges[key]["count"] += 1
                    continue
                # inversion check BEFORE inserting: does dst already reach src?
                path = self._path(dst, src)
                self.edges[key] = {"count": 1, "stack": stack}
                self.adj.setdefault(src, set()).add(dst)
                if path is not None:
                    cycle = tuple(sorted(set(path + [dst])))
                    if cycle in self._seen_cycles:
                        continue
                    self._seen_cycles.add(cycle)
                    prior = self.edges.get((path[0], path[1]), {}).get("stack", "?")
                    self.inversions.append(
                        {
                            "acquiring": dst,
                            "held": src,
                            "cycle": path + [dst],
                            "thread": threading.current_thread().name,
                            "stack": stack,
                            "prior_stack": prior,
                        }
                    )

    @staticmethod
    def _prune(held: List[list]) -> List[list]:
        """Drop entries closed by a cross-thread release (lock slot nulled
        by :meth:`note_pop` on the releasing thread)."""
        if any(e[0] is None for e in held):
            held[:] = [e for e in held if e[0] is not None]
        return held

    def note_push(self, lock: "TracedLock") -> None:
        entry = [lock, time.monotonic()]
        self._stackframe().append(entry)
        with self._mu:
            self._live[id(lock)] = (self._counts(), entry)

    def note_pop(self, lock: "TracedLock") -> None:
        held = self._prune(self._stackframe())
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is lock:
                _l, t0 = held.pop(i)
                with self._mu:
                    self._live.pop(id(lock), None)
                self._close_segment(lock, t0)
                return
        # not on this thread's stack: a plain Lock released by a thread
        # other than the acquirer (handoff signaling). Close the owner's
        # entry in place — nulling the lock slot marks it for pruning —
        # and clear the owner's reentrancy count so its next acquire of
        # this lock is tracked as first-level again.
        with self._mu:
            rec = self._live.pop(id(lock), None)
        if rec is not None:
            owner_counts, entry = rec
            owner_counts.pop(id(lock), None)
            t0 = entry[1]
            entry[0] = None
            self._close_segment(lock, t0)

    def _close_segment(self, lock: "TracedLock", t0: float) -> None:
        ms = (time.monotonic() - t0) * 1000.0
        with self._mu:
            if ms > self.max_hold_ms.get(lock.name, 0.0):
                self.max_hold_ms[lock.name] = ms
        if (
            ms > self.hold_ms
            and not lock.hold_exempt
            and not any(s in lock.name for s in self.hold_exempt_sites)
        ):
            stack = _stack()
            with self._mu:
                self.hold_outliers.append(
                    {
                        "lock": lock.name,
                        "ms": round(ms, 3),
                        "thread": threading.current_thread().name,
                        "stack": stack,
                    }
                )

    # -- construction / reporting -------------------------------------------

    def wrap(self, inner, name: str, hold_exempt: bool = False) -> "TracedLock":
        with self._mu:
            self.locks_created += 1
        return TracedLock(self, inner, name, hold_exempt)

    def report(self) -> dict:
        with self._mu:
            return {
                "locks": self.locks_created,
                "acquisitions": self.acquisitions,
                "edges": len(self.edges),
                "inversions": list(self.inversions),
                "hold_outliers": list(self.hold_outliers),
                "hold_threshold_ms": self.hold_ms,
                "max_hold_ms": dict(
                    sorted(self.max_hold_ms.items(), key=lambda kv: -kv[1])[:10]
                ),
            }


def format_report(rep: dict) -> str:
    lines = [
        f"lockwatch: {rep['locks']} lock(s), {rep['acquisitions']} acquisition(s), "
        f"{rep['edges']} order edge(s), {len(rep['inversions'])} inversion(s), "
        f"{len(rep['hold_outliers'])} hold outlier(s) "
        f"(threshold {rep['hold_threshold_ms']:.0f} ms)"
    ]
    for inv in rep["inversions"]:
        lines.append(
            f"  INVERSION acquiring {inv['acquiring']} while holding "
            f"{inv['held']} on {inv['thread']} (cycle: {' -> '.join(inv['cycle'])})"
        )
        lines.append(f"    now:   {inv['stack']}")
        lines.append(f"    prior: {inv['prior_stack']}")
    for h in rep["hold_outliers"]:
        lines.append(f"  HOLD {h['lock']} for {h['ms']:.1f} ms on {h['thread']}")
        lines.append(f"    at: {h['stack']}")
    if rep["max_hold_ms"]:
        worst = ", ".join(f"{k}={v:.1f}ms" for k, v in rep["max_hold_ms"].items())
        lines.append(f"  longest holds: {worst}")
    return "\n".join(lines)


class TracedLock:
    """Lock/RLock wrapper feeding a :class:`LockWatch`. Implements the
    full lock protocol including the Condition integration hooks, so it
    can sit underneath ``threading.Condition``/``Event`` transparently."""

    __slots__ = ("_w", "_inner", "name", "hold_exempt")

    def __init__(
        self, watch: LockWatch, inner, name: str, hold_exempt: bool = False
    ) -> None:
        self._w = watch
        self._inner = inner
        self.name = name
        self.hold_exempt = hold_exempt

    def acquire(self, blocking: bool = True, timeout: float = -1):
        counts = self._w._counts()
        me = id(self)
        if counts.get(me, 0) > 0:  # reentrant re-acquire (RLock)
            ok = self._inner.acquire(blocking, timeout)
            if ok:
                counts[me] += 1
            return ok
        self._w.note_acquire(self)  # order is recorded at the attempt
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            counts[me] = 1
            self._w.note_push(self)
        return ok

    def release(self) -> None:
        counts = self._w._counts()
        me = id(self)
        n = counts.get(me, 0)
        if n > 1:
            counts[me] = n - 1
            self._inner.release()
            return
        counts.pop(me, None)
        self._w.note_pop(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked() if hasattr(self._inner, "locked") else False

    # -- Condition protocol (threading.Condition borrows these) -------------

    def _is_owned(self) -> bool:
        return self._w._counts().get(id(self), 0) > 0

    def _release_save(self):
        counts = self._w._counts()
        n = counts.pop(id(self), 0)
        self._w.note_pop(self)  # a parked waiter does NOT hold the lock
        if hasattr(self._inner, "_release_save"):
            state = self._inner._release_save()
        else:
            self._inner.release()
            state = None
        return (state, n)

    def _acquire_restore(self, saved) -> None:
        state, n = saved
        if hasattr(self._inner, "_acquire_restore"):
            self._inner._acquire_restore(state)
        else:
            self._inner.acquire()
        self._w._counts()[id(self)] = max(1, n)
        self._w.note_push(self)

    def __repr__(self) -> str:
        return f"<TracedLock {self.name} over {self._inner!r}>"


# ---------------------------------------------------------------------------
# process-global installation (make tsan / OPENSIM_LOCKWATCH=1)
# ---------------------------------------------------------------------------

WATCH: Optional[LockWatch] = None
_ORIG: Dict[str, object] = {}


def _creation_site() -> Optional[Tuple[str, bool]]:
    """(file:line, hold-exempt?) of the repo frame creating a lock, or
    None for stdlib/third-party creations (left uninstrumented — noise
    control). A trailing ``# lockwatch: hold-exempt`` comment on the
    creating source line marks the lock's holds as by-design long (the
    flush/serialization locks that legitimately span engine work);
    inversions are still tracked for it."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        base = os.path.basename(fn)
        if (
            "lockwatch" in base
            or base == "threading.py"
            or fn.startswith("<")
        ):
            f = f.f_back
            continue
        norm = fn.replace(os.sep, "/")
        if "opensim_tpu" in norm or "/tests/" in norm or base.startswith("test_"):
            parts = norm.rsplit("/", 2)
            src = linecache.getline(fn, f.f_lineno)
            return (
                f"{'/'.join(parts[-2:])}:{f.f_lineno}",
                "lockwatch: hold-exempt" in src,
            )
        return None
    return None


def _factory(orig):
    def make(*args, **kwargs):
        inner = orig(*args, **kwargs)
        w = WATCH
        if w is None:
            return inner
        site = _creation_site()
        if site is None:
            return inner
        return w.wrap(inner, site[0], hold_exempt=site[1])

    return make


def install(hold_ms: Optional[float] = None) -> LockWatch:
    """Monkeypatch ``threading.Lock``/``threading.RLock`` so every lock
    created afterwards **from repo code** is traced. Idempotent. Call as
    early as possible (module-level singletons created before install stay
    raw)."""
    global WATCH
    if WATCH is not None:
        return WATCH
    WATCH = LockWatch(hold_ms)
    _ORIG["Lock"] = threading.Lock
    _ORIG["RLock"] = threading.RLock
    threading.Lock = _factory(_ORIG["Lock"])  # type: ignore[misc]
    threading.RLock = _factory(_ORIG["RLock"])  # type: ignore[misc]
    return WATCH


def uninstall() -> Optional[dict]:
    """Restore the original constructors; returns the final report."""
    global WATCH
    if WATCH is None:
        return None
    rep = WATCH.report()
    threading.Lock = _ORIG.pop("Lock")  # type: ignore[misc]
    threading.RLock = _ORIG.pop("RLock")  # type: ignore[misc]
    WATCH = None
    return rep


def current() -> Optional[LockWatch]:
    return WATCH


def self_test() -> bool:
    """Seeded A→B/B→A inversion a healthy sanitizer MUST catch — the
    `make tsan` proof that a green run means 'no inversions observed',
    not 'detector asleep'. Runs on a private LockWatch; the global graph
    is untouched."""
    w = LockWatch(hold_ms=10_000)
    a = w.wrap(_thread.allocate_lock(), "selftest:A")
    b = w.wrap(_thread.allocate_lock(), "selftest:B")
    with a:
        with b:
            pass
    with b:
        with a:  # the inversion
            pass
    return len(w.report()["inversions"]) == 1
