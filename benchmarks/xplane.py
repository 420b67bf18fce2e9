"""The reduction from a profiler trace (`.xplane.pb`) to numbers: the device's
busy and idle time inside the traced window, the time of each device
operation, and the owner among the benchmark's host spans of each idle gap.

Read with `jax.profiler.ProfileData` alone. Device planes are those named
`/device:TPU:<n>`; their `XLA Modules` line holds one event per executed
program (its `XLA Ops` line holds every operation of every step of a scan,
millions of events, and gives the same union, so it is not walked). The window
is the `bench.window` annotation the harness wrote into the host plane; the
same event ties the trace's clock to the host's monotonic clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MARKER = "bench.window"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_name(raw: str) -> str:
    """`jit_wrapped(14235…)` -> `jit_wrapped`: the fingerprint changes with
    every compile and is no part of a name to compare across runs."""
    return re.sub(r"\(\d+\)$", "", raw)


def reduce(path: str, marker: str = MARKER) -> dict:
    """All times in seconds on the trace's own clock. `busy` holds one merged
    list of intervals per device, clipped to the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    devices: Dict[str, List[Interval]] = {}
    ops: Dict[str, float] = {}
    raw_ops: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == marker:
                        window = (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Modules") or lines.get("XLA Ops")
            evs = [] if line is None else [
                (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
            ]
            devices[plane.name] = [(s, e) for _n, s, e in evs]
            raw_ops.extend(evs)
    if window is None:
        raise ValueError(f"{path}: no `{marker}` annotation in the host plane")
    lo, hi = window
    busy = {name: merge(clip(iv, lo, hi)) for name, iv in devices.items()}
    for name, s, e in raw_ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + d
    n = max(1, len(busy))
    return {
        "window": window,
        "window_s": hi - lo,
        "busy": busy,
        "busy_s": sum(total(iv) for iv in busy.values()) / n,
        "device_ops": sorted(([k, v / n] for k, v in ops.items()), key=lambda r: -r[1]),
    }


def _self_intervals(span: dict, out: List[Tuple[str, float, float]]) -> None:
    """A span's own time: its interval less its children's."""
    kids = merge([(c["start"], c["end"]) for c in span.get("children", [])])
    for s, e in gaps(clip(kids, span["start"], span["end"]), span["start"], span["end"]):
        out.append((span["name"], s, e))
    for c in span.get("children", []):
        _self_intervals(c, out)


def idle_gaps(red: dict, forests: List[dict], host_at_window_open: float, top: int = 10) -> List[list]:
    """Idle seconds of the first device by the host span that owns them: each
    gap is split over the deepest spans that cover it; what no span covers is
    `(no span)`. `forests` are span trees on the host's monotonic clock."""
    offset = red["window"][0] - host_at_window_open
    own: List[Tuple[str, float, float]] = []
    for tree in forests:
        _self_intervals(tree, own)
    own = [(n, s + offset, e + offset) for n, s, e in own]
    lo, hi = red["window"]
    first = next(iter(red["busy"].values()), [])
    by: Dict[str, float] = {}
    for gs, ge in gaps(first, lo, hi):
        covered = 0.0
        for name, s, e in own:
            d = min(e, ge) - max(s, gs)
            if d > 0:
                by[name] = by.get(name, 0.0) + d
                covered += d
        if ge - gs - covered > 1e-9:
            by["(no span)"] = by.get("(no span)", 0.0) + (ge - gs - covered)
    return sorted(([k, v] for k, v in by.items()), key=lambda r: -r[1])[:top]
