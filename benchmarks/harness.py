"""One run of one cell: resolve the cell's files by name, set up, measure for
the window, reduce, compare, and print the last line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in `BENCHMARK.json`:

  cell      -> its `config` and `traffic` names, and `chips`
  config    -> `configs[].file` (sizes, generator, guarantees)
  traffic   -> `benchmarks/traffic/<traffic>.json` (driver, parameters, which
               window statistic each end-to-end metric is, limits of the
               comparison)
  metric    -> `benchmarks/metrics/<metric>.json` (reader and its arguments)
  generator -> `benchmarks/generators/<name>.py`, driver ->
               `benchmarks/drivers/<name>.py`, reader -> `benchmarks/readers/<name>.py`
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass
from typing import Dict, List, Optional

from benchmarks import lastline, xplane
from benchmarks.drivers import Context
from benchmarks.readers import window_compiles
from benchmarks.window import Window, run_window

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass
class RunData:
    """What the per-layer readers read."""

    window: Window
    questions: List[dict]
    config: dict
    device_kind: str
    compiles: Optional[dict] = None  # CompileWatch snapshots before and after
    prom: Optional[dict] = None  # parsed /metrics before and after
    trace: Optional[dict] = None  # xplane.reduce() of the traced window


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str) -> dict:
    """The cell, its configuration, its traffic mix and its metrics."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    end_to_end = [m for m in bench["end_to_end"]
                  if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def statistic(window: Window, spec) -> Optional[float]:
    """An end-to-end metric as the traffic file names it: a statistic of the
    whole window, over all its items and all its time."""
    if spec == "seconds_per_item":
        return window.seconds_per_item()
    if spec == "rate":
        return window.rate()
    if isinstance(spec, list) and spec[0] == "quantile":
        return window.quantile(float(spec[1]))
    raise ValueError(f"no such window statistic: {spec!r}")


def read_per_layer(run: RunData, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        reader = importlib.import_module("benchmarks.readers." + spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def print_spans(tree: dict, depth: int = 0, limit: int = 3) -> None:
    print(f"[bench]   {'  ' * depth}{tree['name']} {tree['end'] - tree['start']:.4f}s", file=sys.stderr)
    if depth < limit:
        for child in tree.get("children", []):
            if child["end"] > child["start"]:
                print_spans(child, depth + 1, limit)


def say(t_process: float, text: str) -> None:
    print(f"[bench +{time.monotonic() - t_process:7.2f}s] {text}", file=sys.stderr)


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def parse_args(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one run of one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; prints no result and exits 3")
    return ap.parse_args(argv)


def run_cell(args: argparse.Namespace, t_process: float, emit) -> int:
    """`emit(line)` receives the validated last line exactly once, also when
    the run raises after the device was found."""
    parts = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    cell, config, traffic = parts["cell"], parts["config"], parts["traffic"]
    traced = bool(args.trace)

    from opensim_tpu.utils.jitcache import maybe_enable  # the cache utils/jitcache.py resolves

    maybe_enable(default=True)
    from opensim_tpu.obs.profile import COMPILES  # noqa: F401  (import installs the listeners)
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} x {kind} ({platform}). No result.", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": kind, "count": len(devices), "memory_peak_bytes": 0}
    expected = [m["name"] for m in (parts["per_layer"] if traced else parts["end_to_end"])]
    scratch = os.path.join(HERE, ".cache", f"{args.workload}-{args.seed}-t{args.trace}")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    ctx = Context(config=config, traffic=traffic, seed=args.seed, scratch=scratch,
                  rehearse=args.rehearse, sizes=config["tiny" if args.rehearse else "sizes"], traced=traced)
    driver = importlib.import_module("benchmarks.drivers." + traffic["driver"].replace("-", "_")).Driver(ctx)
    window: Optional[Window] = None
    line: Optional[dict] = None
    profiling = False
    try:
        say(t_process, f"cell {args.workload} seed {args.seed} on {len(devices)} x {kind} ({platform})")
        driver.setup()
        # a traced window is `traced_items` items long, the same items for
        # every seed: a trace of one 50,000-step scan is already tens of
        # seconds of the profiler's work
        limit = int(traffic["traced_items"]) if traced else None
        trace_dir = os.path.join(scratch, "profile")
        measuring = False

        def item(i: int):
            return driver.one(i if measuring else -1 - i, traced)

        # The warm-up and the window go through one call site, this loop's
        # `run_window`: the compile-cache key of a Mosaic kernel holds the
        # Python call stack it was traced under, line numbers and all, so a
        # warm-up called from anywhere else leaves the window's first item to
        # compile the kernel once more in the first process of a checkout.
        for measuring in (False, True):
            if measuring:
                before = driver.counters()
                setup_s = time.monotonic() - t_process
                say(t_process, f"set-up done in {setup_s:.2f}s; window of {args.seconds:g}s opens")
            if measuring and traced:
                # the device's trace and the harness's own annotation: the profiler's
                # Python tracer would make every host call of a plan three times as long
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                profiling = True
                marked_at = time.monotonic()
            with jax.profiler.TraceAnnotation(xplane.MARKER) if profiling else contextlib.nullcontext():
                got = run_window(args.seconds if measuring else math.inf, item,
                                 limit=limit if measuring else driver.warmup_items)
            if not measuring:
                driver.warmed(got)
        window = got
        if traced:
            # collecting a trace of 100,000 scan steps takes the profiler three
            # minutes, and three times that from any thread but this one
            jax.profiler.stop_trace()
            profiling = False
            say(t_process, "trace collected")
        after = driver.counters()
        say(t_process, f"window closed: {window.attempted} item(s) in {window.elapsed:.3f}s; "
                       f"{window_compiles.missed(before['compiles'], after['compiles'])} compile(s) "
                       "in it that the cache did not answer")
        driver.after_window(window)
        device["memory_peak_bytes"] = memory_peak_bytes()
        if traced:
            if hasattr(driver, "fetch_spans"):
                driver.fetch_spans(window)
            shown = next((it.spans for it in window.items if it.spans), None)
            if shown:
                print("[bench] spans of the first item:", file=sys.stderr)
                print_spans(shown)
            questions = driver.questions(window)

        # the reference runs once the window has closed, the peak has been
        # read and the program's state is freed; its time is in no metric
        driver.close()
        t_ref = time.monotonic()
        checks = driver.compare(window)
        say(t_process, f"reference and comparison took {time.monotonic() - t_ref:.2f}s")

        metrics: Dict[str, dict] = {}
        breakdown = None
        if traced:
            path = xplane.find_xplane(trace_dir)
            red = xplane.reduce(path) if path else None
            say(t_process, f"trace read: {os.path.getsize(path) if path else 0} bytes")
            run = RunData(
                window=window, questions=questions, config=config, device_kind=kind,
                compiles={"before": before["compiles"], "after": after["compiles"]},
                prom=({"before": before["prom"], "after": after["prom"]}
                      if before.get("prom") and after.get("prom") else None),
                trace=red,
            )
            metrics = read_per_layer(run, parts["per_layer"])
            if red is not None:
                device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
                forests = [it.spans for it in window.items if it.spans]
                breakdown = {"device_ops": red["device_ops"][:10],
                             "idle_gaps": xplane.idle_gaps(red, forests, marked_at)}
        else:
            unit = {m["name"]: m["unit"] for m in parts["end_to_end"]}
            for name, spec in traffic["end_to_end"].items():
                if name in unit:
                    metrics[name] = {"value": statistic(window, spec), "unit": unit[name]}
            metrics["setup_s"] = {"value": setup_s, "unit": unit["setup_s"]}
        correct = all(c["value"] <= c["limit"] for c in checks)
        line = lastline.build(correct=correct, attempted=window.attempted, failed=window.failed,
                              metrics=metrics, device=device, breakdown=breakdown, checks=checks)
        lastline.validate(line, traced=traced, expected=expected, device_times=not args.rehearse)
        return 0
    except BaseException:
        traceback.print_exc()
        line = None
        raise
    finally:
        if profiling:
            try:
                jax.profiler.stop_trace()
            except Exception:
                traceback.print_exc()
        try:
            driver.close()
        except Exception:
            traceback.print_exc()
        if line is None:
            line = lastline.failure(device, attempted=window.attempted if window else 0)
            lastline.validate(line, traced=traced, expected=expected, failed_run=True)
        shutil.rmtree(scratch, ignore_errors=True)
        for c in line["checks"]:
            print(f"check {c['name']}: {c['value']} (limit {c['limit']})", file=sys.stderr)
        emit(line)


def main(argv: List[str], t_process: float) -> int:
    args = parse_args(argv)
    emitted = []

    def emit(line: dict) -> None:
        emitted.append(line)
        text = json.dumps(line)
        if args.rehearse:
            print("REHEARSAL (tiny sizes, not the chip, not a result): " + text, file=sys.stderr)
        else:
            sys.stderr.flush()
            print(text, flush=True)

    try:
        rc = run_cell(args, t_process, emit)
    except SystemExit:
        raise
    except BaseException:
        if not emitted:  # it raised before the run began: say why, and print no result
            traceback.print_exc()
        return 1
    return 3 if args.rehearse and rc == 0 else rc
