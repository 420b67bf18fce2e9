#!/usr/bin/env python
"""Headline benchmark: the 50k-pod / 5k-node capacity plan.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
`value` = wall-clock seconds for the full plan (workload expansion →
encoding → 50k-step scheduling scan → decode), measured on the available
accelerator. `vs_baseline` = the <10 s target from BASELINE.md divided by
the measured time (>1 means the target is beaten). The reference publishes
no numbers (SURVEY.md §6), so the driver-set target is the yardstick.

Usage: python bench.py [--pods N] [--nodes N] [--config NAME] [--scenarios N]
"""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# persistent XLA compilation cache (utils/jitcache.py): the directory is
# JAX_COMPILATION_CACHE_DIR when set, else the fixed .jit_cache/ in this
# checkout, so cold_s is comparable across runs; OPENSIM_JIT_CACHE=0 opts out
from opensim_tpu.utils.jitcache import maybe_enable  # noqa: E402

maybe_enable(default=True)

# The measurement path fails without a chip; it never falls back. The
# platform is whatever JAX selects from JAX_PLATFORMS, and a CPU run has to
# be asked for by name. Anything but that runs strict, as `--backend tpu`
# does: a megakernel that does not compile fails the run instead of being
# demoted to a slower engine (children — bench servers — inherit it).
CPU_REQUESTED = os.environ.get("JAX_PLATFORMS") == "cpu"
if not CPU_REQUESTED:
    os.environ["OPENSIM_REQUIRE_TPU"] = "1"

import numpy as np  # noqa: E402

from opensim_tpu.engine.simulator import AppResource, simulate  # noqa: E402
from opensim_tpu.models import ResourceTypes, fixtures as fx  # noqa: E402
from opensim_tpu.obs.profile import device_stamp  # noqa: E402


# failure contract (NOTES invariant: the driver parses exactly ONE JSON
# line from stdout): every failure path must emit a single-line JSON error
# object and exit nonzero — never a bare traceback. _STAGE tracks how far
# the run got so the error line says which phase died.
_STAGE = ["startup"]


def _stage(name: str) -> None:
    _STAGE[0] = name


def _check_device(device: dict) -> dict:
    if device["platform"] != "tpu" and not CPU_REQUESTED:
        raise RuntimeError(
            f"no TPU found (platform={device['platform']!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); a CPU run must be "
            "requested explicitly with JAX_PLATFORMS=cpu"
        )
    return device


def _emit(record: dict, device: dict = None) -> None:
    """THE row printer: every row names the device its numbers came from —
    this process's (obs.profile.device_stamp) unless a server's is passed."""
    record.update(_check_device(device or device_stamp()))
    print(json.dumps(record))


def _fmt(n: int) -> str:
    return f"{n // 1000}k" if n >= 1000 and n % 1000 == 0 else str(n)


def _serial_floors(config: str, pods: int, nodes: int):
    """Measured serial baselines (tools/serial_baseline.py) for the same
    workload at the same shape, if recorded. Returns (python_rec, cxx_rec),
    either None. The python-serial floor UNDERSTATES the Go reference's
    speed; the c++-serial row (native/serial_engine.cc) is the measured
    stand-in for the Go constant factor. bench's `plan` config and the
    baseline tool's `synthetic` use the same generators, so either key
    matches by shape."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BASELINE_MEASURED.json")
    try:
        with open(path) as f:
            measured = json.load(f)
    except (OSError, ValueError):
        return None, None
    cfgs = {"plan": ("plan", "synthetic")}.get(config, (config,))

    def find(cxx):
        for rec in measured.values():
            if not isinstance(rec, dict) or rec.get("config") not in cfgs:
                continue
            # classify by the record's own impl field, not key naming
            if str(rec.get("impl", "")).startswith("c++") != cxx:
                continue
            if rec.get("pods") == pods and rec.get("nodes") == nodes:
                return rec
        return None

    return find(False), find(True)


def synthetic_cluster(n_nodes: int) -> ResourceTypes:
    rt = ResourceTypes()
    zones = [f"zone-{z}" for z in range(4)]
    for i in range(n_nodes):
        rt.nodes.append(
            fx.make_fake_node(
                f"node-{i:05d}",
                "64",
                "256Gi",
                "256",
                fx.with_labels(
                    {
                        "topology.kubernetes.io/zone": zones[i % len(zones)],
                        "node-role.kubernetes.io/worker": "",
                        "disk": "ssd" if i % 3 else "hdd",
                    }
                ),
            )
        )
    return rt


def synthetic_apps(n_pods: int) -> ResourceTypes:
    """~20 workload templates covering the kernel surface: resource fit,
    tolerations, node selectors, spread, anti-affinity."""
    rt = ResourceTypes()
    n_workloads = 20
    per = n_pods // n_workloads
    for w in range(n_workloads):
        opts = []
        if w % 4 == 0:
            opts.append(fx.with_node_selector({"disk": "ssd"}))
        if w % 5 == 0:
            opts.append(
                fx.with_topology_spread(
                    [
                        {
                            "maxSkew": 5,
                            "topologyKey": "topology.kubernetes.io/zone",
                            "whenUnsatisfiable": "ScheduleAnyway",
                            "labelSelector": {"matchLabels": {"app": f"bench-{w}"}},
                        }
                    ]
                )
            )
        rt.deployments.append(
            fx.make_fake_deployment(
                f"bench-{w}", per, f"{100 + 20 * (w % 8)}m", f"{256 + 64 * (w % 6)}Mi", *opts
            )
        )
    return rt


def bigu_apps(n_pods: int, n_templates: int = 1000) -> ResourceTypes:
    """Template-heavy workload (verdict envelope target: 1000 distinct pod
    specs): exercises the megakernel's big-U mode (HBM template tables)."""
    rt = ResourceTypes()
    per = max(n_pods // n_templates, 1)
    for w in range(n_templates):
        rt.deployments.append(
            fx.make_fake_deployment(
                f"t{w:04d}", per, f"{100 + (w % 400)}m", f"{128 + (w % 97)}Mi"
            )
        )
    return rt


def forced_cluster(n_nodes: int, n_bound: int) -> ResourceTypes:
    """Live-cluster replay shape: a snapshot full of pre-bound pods (the
    server re-binds them as forced pods every request)."""
    rt = synthetic_cluster(n_nodes)
    for i in range(n_bound):
        rt.pods.append(
            fx.make_fake_pod(
                f"bound-{i:05d}", "500m", "1Gi", fx.with_node_name(f"node-{i % n_nodes:05d}")
            )
        )
    return rt


def _tmpl_annotate(deploy, anno: dict) -> None:
    """Pod-TEMPLATE annotations on a workload (gpu-share / open-local pod
    requests live on the pod template, not the controller metadata)."""
    deploy.template_metadata.annotations.update(anno)
    deploy.template_raw.setdefault("metadata", {}).setdefault(
        "annotations", {}
    ).update(anno)


def gpu_cluster(n_nodes: int) -> ResourceTypes:
    """All-GPU fleet (ISSUE-19 envelope target): every node advertises
    gpu-share devices — 8 × 8Gi per the reference NewGpuNodeInfo semantics
    (per-device memory = total gpu-mem / gpu-count)."""
    rt = ResourceTypes()
    zones = [f"zone-{z}" for z in range(4)]
    for i in range(n_nodes):
        rt.nodes.append(
            fx.make_fake_node(
                f"node-{i:05d}", "64", "256Gi", "256",
                fx.with_labels({"topology.kubernetes.io/zone": zones[i % len(zones)]}),
                fx.with_allocatable({
                    "alibabacloud.com/gpu-mem": "64Gi",
                    "alibabacloud.com/gpu-count": "8",
                }),
            )
        )
    return rt


def gpu_apps(n_pods: int) -> ResourceTypes:
    """All-GPU workload mix: gpu-share templates (pod-template gpu-mem
    annotations → the per-GPU-index headroom carry) plus whole-GPU
    templates (spec gpu-count requests → the gc_dyn dynamic-allocatable
    filter/score, Reserve-rewritten at every bind)."""
    rt = ResourceTypes()
    n_workloads = 10
    per = n_pods // n_workloads
    for w in range(n_workloads):
        if w % 5 == 4:
            rt.deployments.append(
                fx.make_fake_deployment(
                    f"gpu-{w}", per, "250m", "512Mi",
                    fx.with_requests({"alibabacloud.com/gpu-count": "1"}),
                )
            )
            continue
        d = fx.make_fake_deployment(f"gpu-{w}", per, "250m", "512Mi")
        _tmpl_annotate(d, {
            "alibabacloud.com/gpu-mem": f"{2 + 2 * (w % 3)}Gi",
            "alibabacloud.com/gpu-count": "1",
        })
        rt.deployments.append(d)
    return rt


def local_pv_cluster(n_nodes: int) -> ResourceTypes:
    """All-local-PV fleet (ISSUE-19 envelope target): every node carries an
    open-local LVM volume group plus exclusive devices."""
    rt = ResourceTypes()
    zones = [f"zone-{z}" for z in range(4)]
    for i in range(n_nodes):
        rt.nodes.append(
            fx.make_fake_node(
                f"node-{i:05d}", "64", "256Gi", "256",
                fx.with_labels({"topology.kubernetes.io/zone": zones[i % len(zones)]}),
                fx.with_node_local_storage(
                    vgs=[{"name": "pool0", "capacity": 600 * 1024**3}],
                    devices=[
                        {"device": "/dev/vdb", "capacity": 100 * 1024**3, "mediaType": "ssd"},
                        {"device": "/dev/vdc", "capacity": 100 * 1024**3, "mediaType": "ssd"},
                    ],
                ),
            )
        )
    return rt


def local_pv_apps(n_pods: int) -> ResourceTypes:
    """All-local-PV workload mix: every template requests an open-local LVM
    volume (per-disk allocation carry + the w_local score term); one
    template in ten adds an exclusive SSD device volume."""
    rt = ResourceTypes()
    n_workloads = 10
    per = n_pods // n_workloads
    for w in range(n_workloads):
        vols = [{
            "size": str((5 + 5 * (w % 3)) * 1024**3),
            "kind": "LVM", "scName": "open-local-lvm",
        }]
        if w == 4:
            vols.append({
                "size": str(20 * 1024**3),
                "kind": "SSD", "scName": "open-local-device",
            })
        d = fx.make_fake_deployment(f"loc-{w}", per, "250m", "512Mi")
        _tmpl_annotate(d, {"simon/pod-local-storage": json.dumps({"volumes": vols})})
        rt.deployments.append(d)
    return rt


def _verify_envelope(cluster, apps) -> dict:
    """ISSUE 19 in-row bit-equality gates (gpu / local-pv configs): one
    shared Prepared encoding driven through the incremental C++ path, the
    forced-generic C++ path, and the XLA scan — placements, failure
    attribution, and final state must agree element-for-element."""
    from opensim_tpu.engine import nativepath
    from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
    from opensim_tpu.engine.simulator import prepare

    prep = prepare(cluster, apps, node_pad=128)
    P = len(prep.ordered)
    pv = np.ones(P, bool)
    inc = nativepath.schedule(prep, pv)
    prior = os.environ.get("OPENSIM_NATIVE_FORCE_GENERIC")
    os.environ["OPENSIM_NATIVE_FORCE_GENERIC"] = "1"
    try:
        gen = nativepath.schedule(prep, pv)
    finally:
        if prior is None:
            del os.environ["OPENSIM_NATIVE_FORCE_GENERIC"]
        else:
            os.environ["OPENSIM_NATIVE_FORCE_GENERIC"] = prior
    t, v, f = pad_pod_stream(prep.tmpl_ids, pv, prep.forced)
    xout = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    inc_stats = inc.native_stats or {}
    gen_stats = gen.native_stats or {}
    return {
        "verify_native_path": inc_stats.get("path"),
        "verify_classes": (inc_stats.get("steps") or {}).get("classes") or {},
        "placements_identical_generic": int(
            gen_stats.get("path") == "generic"
            and np.array_equal(inc.chosen, gen.chosen)
            and np.array_equal(inc.fail_counts, gen.fail_counts)
            and np.array_equal(inc.final_state.used, gen.final_state.used)
        ),
        "placements_identical_xla": int(
            np.array_equal(np.asarray(xout.chosen)[:P], inc.chosen)
            and np.array_equal(np.asarray(xout.fail_counts)[:P], inc.fail_counts)
            and np.array_equal(np.asarray(xout.final_state.used), inc.final_state.used)
        ),
    }


def bench_defrag(n_scenarios: int, n_nodes: int, n_pods: int, warmup: bool) -> int:
    """BASELINE.md config 5: parallel what-if node-drain scenarios.
    Metric: scenarios/sec/chip."""
    from opensim_tpu.planner.defrag import plan_drains

    cluster = synthetic_cluster(n_nodes)
    apps = [AppResource("bench", synthetic_apps(n_pods))]
    candidates = [n.metadata.name for n in cluster.nodes[:n_scenarios]]
    if warmup:
        plan_drains(cluster, apps, candidates=candidates[:8])
    t0 = time.time()
    result = plan_drains(cluster, apps, candidates=candidates)
    dt = time.time() - t0
    record = {
        "metric": f"defrag sweep ({len(candidates)} drain scenarios, {n_pods} pods/{n_nodes} nodes)",
        "value": round(len(candidates) / dt, 2),
        "unit": "scenarios/s/chip",
        "vs_baseline": round(len(candidates) / dt, 2),  # no reference number exists
        "drainable": len(result.drainable()),
        "wall_s": round(dt, 2),
    }
    serial, cxx = _serial_floors("defrag", n_pods, n_nodes)
    if serial and serial.get("scenarios_per_sec"):
        record["vs_serial"] = round(record["value"] / serial["scenarios_per_sec"], 1)
    if cxx and cxx.get("scenarios_per_sec"):
        record["vs_serial_cxx"] = round(record["value"] / cxx["scenarios_per_sec"], 1)
    _emit(record)
    return 0


def _campaign_inputs(n_nodes: int, n_pods: int):
    """Synthetic lifecycle-campaign scenario (ISSUE 13): the bench cluster
    owns the workloads (campaigns drain/reschedule cluster pods), a quarter
    of them guarded by PDBs, and the campaign mixes the four acceptance
    step shapes: PDB-aware drain wave, reclaim storm, deploy, scale-down
    check."""
    from opensim_tpu.models.objects import PodDisruptionBudget

    cluster = synthetic_cluster(n_nodes)
    cluster.deployments.extend(synthetic_apps(n_pods).deployments)
    for w in cluster.deployments[:5]:
        cluster.pdbs.append(
            PodDisruptionBudget.from_dict(
                {
                    "apiVersion": "policy/v1",
                    "kind": "PodDisruptionBudget",
                    "metadata": {"name": f"{w.metadata.name}-pdb", "namespace": "default"},
                    "spec": {
                        "maxUnavailable": "25%",
                        "selector": {"matchLabels": {"app": w.metadata.name}},
                    },
                }
            )
        )
    drain_n = max(2, n_nodes // 10)
    storm_n = max(1, n_nodes // 20)
    steps = [
        {"name": "upgrade", "type": "drain-wave", "count": drain_n, "wave": max(1, drain_n // 4)},
        {"name": "spot-storm", "type": "reclaim-storm", "count": storm_n},
        {
            "name": "push",
            "type": "deploy",
            "app": {"name": "push"},
            "resources": [
                {
                    "apiVersion": "apps/v1",
                    "kind": "Deployment",
                    "metadata": {"name": "push", "namespace": "default"},
                    "spec": {
                        "replicas": max(4, n_pods // 20),
                        "selector": {"matchLabels": {"app": "push"}},
                        "template": {
                            "metadata": {"labels": {"app": "push"}},
                            "spec": {
                                "containers": [
                                    {
                                        "name": "c",
                                        "resources": {
                                            "requests": {"cpu": "250m", "memory": "512Mi"}
                                        },
                                    }
                                ]
                            },
                        },
                    },
                }
            ],
        },
        {"name": "shrink-check", "type": "scale-down-check", "count": 8},
    ]
    return cluster, steps


def bench_campaign(n_nodes: int, n_pods: int, warmup: bool) -> int:
    """Campaign-engine throughput (ISSUE 13): a 4-step mixed lifecycle
    campaign (drain wave w/ PDBs + reclaim storm + deploy + scale-down
    check) on the warm delta path. Metrics: steps/s and pods rescheduled/s;
    at small sizes the row also gates warm-vs-cold fingerprint equality
    in-row (the delta-execution proof)."""
    from opensim_tpu.planner import campaign as campaign_mod

    cluster, steps_raw = _campaign_inputs(n_nodes, n_pods)
    if warmup:
        campaign_mod.run_campaign(cluster, campaign_mod.parse_steps(steps_raw), mode="warm")
    t0 = time.time()
    res = campaign_mod.run_campaign(
        cluster, campaign_mod.parse_steps(steps_raw), mode="warm", name="bench"
    )
    dt = time.time() - t0
    n_steps = len(res.steps)
    rescheduled = sum(s.rescheduled for s in res.steps)
    record = {
        "metric": f"campaign ({n_steps} scored steps, {_fmt(n_pods)} pods/{_fmt(n_nodes)} nodes)",
        "value": round(dt, 3),
        "unit": "s",
        "vs_baseline": round(10.0 / dt, 2) if dt > 0 else 0.0,
        "config": "campaign",
        "steps": n_steps,
        "steps_per_s": round(n_steps / dt, 2) if dt > 0 else 0.0,
        "rescheduled": rescheduled,
        "rescheduled_per_s": round(rescheduled / dt, 1) if dt > 0 else 0.0,
        "evicted": sum(s.evicted for s in res.steps),
        "blocked": sum(len(s.blocked) for s in res.steps),
        # pods still pending at campaign end (the capacity sample, not the
        # last step's scan report — a what-if final step never scans)
        "unschedulable": int((res.steps[-1].capacity or {}).get("pods_pending", 0)),
        "full_prepares": res.full_prepares,
        "fingerprint": res.fingerprint,
    }
    if n_pods <= 5000:
        # the delta-execution gate, in-row: the warm campaign's per-step
        # fingerprints must be bit-identical to cold per-step prepares
        cold = campaign_mod.run_campaign(
            cluster, campaign_mod.parse_steps(steps_raw), mode="cold", name="bench"
        )
        record["verified_vs_cold"] = bool(
            [s.fingerprint for s in res.steps] == [s.fingerprint for s in cold.steps]
        )
        if not record["verified_vs_cold"]:
            raise RuntimeError("campaign warm-delta fingerprints diverged from cold per-step prepares")
    _emit(record)
    return 0


def affinity_apps(n_pods: int) -> ResourceTypes:
    """BASELINE.md config 4: InterPodAffinity + PodTopologySpread heavy."""
    rt = ResourceTypes()
    n_workloads = 10
    per = n_pods // n_workloads
    for w in range(n_workloads):
        opts = [
            fx.with_topology_spread(
                [
                    {
                        "maxSkew": 3,
                        "topologyKey": "topology.kubernetes.io/zone",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": f"aff-{w}"}},
                    }
                ]
            )
        ]
        if w % 2 == 0:
            opts.append(
                fx.with_affinity(
                    {
                        "podAntiAffinity": {
                            "preferredDuringSchedulingIgnoredDuringExecution": [
                                {
                                    "weight": 100,
                                    "podAffinityTerm": {
                                        "labelSelector": {"matchLabels": {"app": f"aff-{w}"}},
                                        "topologyKey": "kubernetes.io/hostname",
                                    },
                                }
                            ]
                        }
                    }
                )
            )
        else:
            opts.append(
                fx.with_affinity(
                    {
                        "podAffinity": {
                            "requiredDuringSchedulingIgnoredDuringExecution": [
                                {
                                    "labelSelector": {"matchLabels": {"app": f"aff-{w - 1}"}},
                                    "topologyKey": "topology.kubernetes.io/zone",
                                }
                            ]
                        }
                    }
                )
            )
        rt.deployments.append(fx.make_fake_deployment(f"aff-{w}", per, "100m", "256Mi", *opts))
    return rt


def bench_reference_example(config_path: str, extended: str, warmup: bool, label: str) -> int:
    """BASELINE.md configs 1-2: the reference repo's example simon configs,
    run through the full `simon apply` pipeline."""
    from opensim_tpu.planner.apply import Applier, Options

    def run() -> float:
        t0 = time.time()
        rc = Applier(
            Options(
                simon_config=config_path,
                output_file="/dev/null",
                extended_resources=[r for r in extended.split(",") if r],
            )
        ).run()
        if rc != 0:
            raise RuntimeError(f"simon apply failed with rc={rc}")
        return time.time() - t0

    if warmup:
        run()
    dt = run()
    _emit(
        {
            "metric": f"simon apply {label} wall-clock",
            "value": round(dt, 3),
            "unit": "s",
            "vs_baseline": round(1.0 / dt, 2) if dt > 0 else 0.0,  # reference trace threshold: 1 s
        }
    )
    return 0


def _core_guard_note(config: str, host_cores: int):
    """Serving QPS is core-count-bound: comparing a fresh row against a
    baseline recorded on a different core count measures the boxes, not
    the code. Every serving row records host_cores; when the committed
    baseline for this config was measured on a different count, the row
    carries an explicit refusal note (and tools/perf_guard.py refuses to
    compute ratios at all). Returns None when comparable or unknown."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_BASELINE.json")
    try:
        with open(path) as f:
            baselines = json.load(f).get("baselines", {})
    except (OSError, ValueError):
        return None
    for entry in baselines.values():
        row = entry.get("row", {})
        if row.get("config") != config or "host_cores" not in row:
            continue
        if int(row["host_cores"]) != host_cores:
            return (
                f"refused: baseline measured on {row['host_cores']} core(s), "
                f"this box has {host_cores} — re-baseline on a same-core box"
            )
    return None


def bench_serving(concurrency: int, duration_s: float) -> int:
    """ISSUE 8 + 16 acceptance run: the closed loop against live
    stub-backed twin servers — single-flight vs admission queue +
    request-axis batching (ISSUE 8 pair), then serial-batch vs the staged
    admission pipeline with the placement-parity gate and the measured
    prep-under-dispatch overlap (ISSUE 16 pair) — ALL numbers in the one
    JSON line. The bars: qps ≥ 4× qps_single_flight at bounded p99, and
    pipelined ≥ 2× non-pipelined (the multiple needs ≥4 host cores; the
    row records host_cores so cross-box readers can tell)."""
    from opensim_tpu.server.loadgen import run_pipeline_benchmark, run_stub_benchmark

    _stage("serving")
    # hundreds of clients need sharded client processes or the loadgen's
    # own GIL throttles the offered load (docs/serving.md)
    client_procs = 4 if concurrency >= 128 else 0
    report = run_stub_benchmark(
        concurrency=concurrency, duration_s=duration_s, base_port=18980,
        client_procs=client_procs,
    )
    _check_device(report["device"])
    _stage("serving-pipeline")
    pipe = run_pipeline_benchmark(
        concurrency=concurrency, duration_s=duration_s, base_port=19080,
        client_procs=client_procs,
    )
    record = {
        "metric": (
            f"serving closed loop ({concurrency} clients, "
            f"{duration_s:.0f}s, stub-apiserver twin)"
        ),
        "value": pipe["qps"],
        "unit": "req/s",
        "config": "serving",
        # the ISSUE 8 acceptance pair: batched QPS vs the seed's single-flight
        "qps_single_flight": report["qps_single_flight"],
        "qps_admission": report["qps"],
        "vs_single_flight": report["speedup"],
        "p50_s": pipe["p50_s"],
        "p99_s": pipe["p99_s"],
        "p99_single_flight_s": report["p99_single_flight_s"],
        "batches": pipe["batches"],
        "mean_batch_size": pipe["mean_batch_size"],
        "shed": pipe["shed"],
        "shed_single_flight": report["shed_single_flight"],
        "errors": pipe["errors"],
        "queue_wait_p99_s": report["admission"]["queue_wait_p99_s"],
        # the ISSUE 16 acceptance pair: staged pipeline vs serial batches,
        # same box, same stub cluster, plus the in-row parity gate
        "qps_non_pipelined": pipe["qps_non_pipelined"],
        "vs_non_pipelined": pipe["vs_non_pipelined"],
        "p99_non_pipelined_s": pipe["p99_non_pipelined_s"],
        "overlapped_batches": pipe["overlapped_batches"],
        "prep_overlap_s": pipe["prep_overlap_s"],
        "placements_identical": pipe["placements_identical"],
        "client_procs": client_procs,
        "host_cores": os.cpu_count() or 0,
    }
    note = _core_guard_note("serving", record["host_cores"])
    if note:
        record["baseline_comparison"] = note
    _emit(record, device=pipe["device"])
    return 0


def bench_serving_fleet(workers: int, concurrency: int, duration_s: float) -> int:
    """ISSUE 15 acceptance run: the closed loop against a multi-process
    fleet (`--workers N`: twin owner + shm publication + SO_REUSEPORT
    workers) vs ONE single-process admission server, same stub cluster,
    same concurrency. The bar is fleet qps above single-process at p99 no
    worse, placements bit-identical (the in-row ``placements_identical``
    gate), and zero torn-generation attach abandonments."""
    from opensim_tpu.server.loadgen import run_fleet_benchmark

    _stage("serving-fleet")
    report = run_fleet_benchmark(
        workers=workers, concurrency=concurrency, duration_s=duration_s,
        base_port=19480,
        # hundreds of clients need sharded client processes or the
        # loadgen's own GIL throttles the offered load (docs/serving.md)
        client_procs=4 if concurrency >= 128 else 0,
    )
    record = {
        "metric": (
            f"fleet serving closed loop ({concurrency} clients, "
            f"{duration_s:.0f}s, {workers}-worker shm fleet vs single process)"
        ),
        "value": report["qps"],
        "unit": "req/s",
        "config": "serving-fleet",
        "workers": workers,
        # the acceptance pair: fleet QPS vs one admission-batched process
        "qps_single_process": report["qps_single_process"],
        "vs_single_process": report["vs_single_process"],
        "p50_s": report["p50_s"],
        "p99_s": report["p99_s"],
        "p99_single_process_s": report["p99_single_process_s"],
        "batches": report["batches"],
        "mean_batch_size": report["mean_batch_size"],
        "shed": report["shed"],
        "errors": report["errors"],
        # in-row gates: bit-identical placements across the process
        # boundary, zero seqlock-retry exhaustion, no crash-respawns
        "placements_identical": report["placements_identical"],
        "torn_generation_exhausted": report["torn_generation_exhausted"],
        "respawns": report["respawns"],
        "fleet_generation": report["fleet_generation"],
        "fleet_publishes": report["fleet_publishes"],
        # context for cross-box comparison: on a 2-core box the workers
        # and the sharded clients contend for the same cores, so the
        # fleet's headroom shows as p99 first, absolute QPS second
        "host_cores": os.cpu_count() or 0,
    }
    note = _core_guard_note("serving-fleet", record["host_cores"])
    if note:
        record["baseline_comparison"] = note
    _emit(record, device=report["device"])
    return 0


def _synth_storm_journal(path: str, n_events: int, n_nodes: int) -> None:
    """Record a synthetic event storm into a fresh journal: one checkpoint
    anchoring a node fleet, then a pod churn stream (adds, node-bound adds,
    and deletes — tombstones included) with monotonic resourceVersions, the
    same wire shapes the live twin journals."""
    from opensim_tpu.server.journal import Journal

    cluster = synthetic_cluster(n_nodes)
    journal = Journal(path, policy={"fsync": "off"})
    try:
        rv = 1000
        journal.record_checkpoint(
            {"nodes": [n.raw for n in cluster.nodes]},
            generation=1,
            resume_rvs={"nodes": str(rv), "pods": str(rv)},
            why="bench",
        )
        gen = 1
        for i in range(n_events):
            rv += 1
            gen += 1
            if i % 10 == 9:
                # a delete of an earlier add: replay must tombstone it
                victim = i - 9 + (i % 3)
                journal.record_event(
                    "pods", "DELETED",
                    {"metadata": {"name": f"storm-{victim:06d}", "namespace": "bench",
                                  "resourceVersion": str(rv)}},
                    gen,
                )
                continue
            pod = {
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"storm-{i:06d}", "namespace": "bench",
                             "resourceVersion": str(rv)},
                "spec": {"containers": [
                    {"name": "c", "resources": {"requests": {
                        "cpu": "100m", "memory": "256Mi"}}}
                ]},
                "status": {"phase": "Pending"},
            }
            if i % 3:
                pod["spec"]["nodeName"] = f"node-{i % n_nodes:05d}"
                pod["status"]["phase"] = "Running"
            journal.record_event("pods", "ADDED", pod, gen)
    finally:
        journal.close()


def bench_replay(journal_path: str, n_events: int, n_nodes: int, speed: float) -> int:
    """ISSUE 11 benchmark row: stream a recorded (or synthesized) watch-event
    journal through the twin's apply path + the capacity observatory at
    ``speed``× (0 = as fast as possible) and report event throughput. The
    random-access ``rebuild_twin`` view must land bit-equal to the streamed
    replay — the determinism gate that makes recorded production traces a
    repeatable scenario corpus (docs/live-twin.md 'Durability & replay')."""
    import tempfile

    _stage("replay")
    label = journal_path
    tmp = None
    if not journal_path:
        tmp = tempfile.mkdtemp(prefix="bench-replay-")
        journal_path = os.path.join(tmp, "journal")
        label = f"synthetic storm ({_fmt(n_events)} events, {_fmt(n_nodes)} nodes)"
        _synth_storm_journal(journal_path, n_events, n_nodes)
    try:
        return _bench_replay_run(journal_path, label, speed)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def _bench_replay_run(journal_path: str, label: str, speed: float) -> int:
    from opensim_tpu.obs.capacity import CapacityEngine
    from opensim_tpu.server.journal import rebuild_twin, replay_events

    capacity = CapacityEngine()
    counts = {}
    twin = None
    t0 = time.time()
    for rec, twin, change in replay_events(journal_path, speed=speed):
        counts[rec["t"]] = counts.get(rec["t"], 0) + 1
        capacity.on_replay(rec, twin, change)
    wall = time.time() - t0
    if twin is None:
        raise RuntimeError(f"{journal_path}: no replayable records")
    fp = twin.fingerprint()
    rebuilt, meta = rebuild_twin(journal_path)
    if rebuilt.fingerprint() != fp:
        raise RuntimeError(
            "rebuild_twin fingerprint diverged from the streamed replay "
            f"({rebuilt.fingerprint()} != {fp})"
        )
    events = counts.get("ev", 0)
    sample = capacity.sample()
    record = {
        "metric": f"journal replay event storm ({label})",
        "value": round(wall, 3),
        "unit": "s",
        "config": "replay",
        "events": events,
        "rebases": counts.get("rb", 0),
        "checkpoints": counts.get("ck", 0),
        "events_per_s": round(events / wall, 1) if wall > 0 else 0.0,
        "generation": twin.generation,
        "fingerprint": fp,
        "rebuild_bit_equal": True,
        "speed": speed,
    }
    if sample is not None:
        record["nodes"] = sample.nodes
        record["pods_bound"] = sample.pods_bound
        record["pods_pending"] = sample.pods_pending
        record["cpu_utilization"] = round(sample.utilization.get("cpu", 0.0), 4)
    _emit(record)
    return 0


def bench_steady(n_pods: int, n_nodes: int, repeats: int) -> int:
    """Steady-state re-simulation: N repeated simulates against ONE cluster
    through the encode cache (opensim_tpu/engine/prepcache.py). The metric
    pair that matters is host_prep_s (warm, cache-hit prepare) vs
    cold_host_prep_s (the one full expand+encode) — the incremental-prepare
    acceptance bar is warm ≥ 5× faster than cold."""
    import statistics

    from opensim_tpu.engine import prepcache
    from opensim_tpu.utils.trace import PREP_STATS

    cluster = synthetic_cluster(n_nodes)
    apps = [AppResource("bench", synthetic_apps(n_pods))]
    cache = prepcache.PrepareCache()
    PREP_STATS.reset()

    t0 = time.time()
    r0 = prepcache.simulate_cached(cluster, apps, cache, node_pad=128)
    cold_s = time.time() - t0
    cold_prep_s = PREP_STATS.snapshot()["seconds"].get("full", 0.0)
    scheduled0 = sum(len(ns.pods) for ns in r0.node_status)

    warm_wall, warm_prep = [], []
    for _ in range(repeats):
        t0 = time.time()
        r = prepcache.simulate_cached(cluster, apps, cache, node_pad=128)
        warm_wall.append(time.time() - t0)
        kind, secs = PREP_STATS.snapshot()["last"]
        if kind != "hit":
            raise RuntimeError(f"steady-state iteration re-prepared (kind={kind})")
        warm_prep.append(secs)
        scheduled = sum(len(ns.pods) for ns in r.node_status)
        if scheduled != scheduled0 or len(r.unscheduled_pods) != len(r0.unscheduled_pods):
            raise RuntimeError("cached re-simulation diverged from the cold run")

    host_prep_s = statistics.median(warm_prep)
    record = {
        "metric": f"steady-state re-simulation ({_fmt(n_pods)} pods/{_fmt(n_nodes)} nodes, {repeats} warm runs)",
        "value": round(statistics.median(warm_wall), 3),
        "unit": "s",
        "vs_baseline": round(cold_s / statistics.median(warm_wall), 2),
        "host_prep_s": round(host_prep_s, 4),
        "cold_host_prep_s": round(cold_prep_s, 3),
        "prep_speedup": round(cold_prep_s / host_prep_s, 1) if host_prep_s > 0 else float("inf"),
        "cold_s": round(cold_s, 3),
        "prep_cache": cache.stats.as_dict(),
        "scheduled": scheduled0,
        "unscheduled": len(r0.unscheduled_pods),
    }
    _emit(record)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=50000)
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument(
        "--warmup",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="run once first to populate the jit cache (--no-warmup to measure cold)",
    )
    ap.add_argument(
        "--config",
        default="plan",
        choices=["plan", "defrag", "affinity", "gpu", "local-pv", "example", "gpushare", "bigu", "forced", "steady", "serving", "replay", "campaign"],
        help=(
            "plan = capacity-plan wall-clock (headline); defrag = drain-scenario "
            "sweep; affinity = interpod+spread heavy; gpu = all-GPU-share + "
            "whole-GPU (gc_dyn) envelope row; local-pv = all-open-local "
            "LVM/device envelope row (both carry in-row bit-equality gates "
            "vs the generic C++ path and the XLA scan); example/gpushare = the "
            "shipped example simon configs; bigu = 1000 distinct templates "
            "(big-U megakernel mode); forced = live-cluster replay (90%% "
            "pre-bound pods); steady = repeated re-simulation of one cluster "
            "through the encode cache (host-side prepare trajectory); serving "
            "= closed-loop QPS of the live server, admission-batched vs "
            "single-flight (docs/serving.md); replay = stream a recorded "
            "watch-event journal (--journal, or a synthesized storm) through "
            "the twin + capacity observatory (docs/live-twin.md)"
        ),
    )
    ap.add_argument(
        "--journal", default="",
        help="replay: journal directory recorded by `simon server --journal` "
        "(default: synthesize an event storm of --events events)",
    )
    ap.add_argument("--events", type=int, default=20000, help="replay: synthesized storm size")
    ap.add_argument(
        "--speed", type=float, default=0.0,
        help="replay: pace at N× recorded gaps (0 = as fast as possible)",
    )
    ap.add_argument("--concurrency", type=int, default=48, help="serving: closed-loop clients")
    ap.add_argument("--duration", type=float, default=10.0, help="serving: measured seconds per mode")
    ap.add_argument(
        "--workers", type=int, default=0,
        help="serving: ≥2 measures the multi-process fleet (`simon server "
        "--workers N`, docs/serving.md 'Scaling past one process') against "
        "a single-process admission server instead of admission vs "
        "single-flight",
    )
    ap.add_argument("--scenarios", type=int, default=1000, help="defrag: number of drain scenarios")
    ap.add_argument("--repeats", type=int, default=10, help="steady: number of warm re-simulations")
    ap.add_argument(
        "--explain",
        action="store_true",
        help=(
            "run the measured simulation with the decision audit enabled "
            "(plan-family configs): the JSON line gains filter_rejects (nodes "
            "rejected per filter across all steps) and unschedulable_reasons. "
            "Forces the C++ generic path / XLA count_all scan, so the wall "
            "time measures the audited path, not the headline"
        ),
    )
    ap.add_argument(
        "--trace",
        default="",
        metavar="FILE",
        help=(
            "write a Chrome-trace/Perfetto JSON of the measured run (plan-"
            "family configs): every phase span — prepare, encode, engine "
            "attempts, decode — with the C++ engine's profile attached. "
            "Load at chrome://tracing or ui.perfetto.dev"
        ),
    )
    args = ap.parse_args()
    _stage("measure")

    repo = os.path.dirname(os.path.abspath(__file__))
    if args.config == "serving":
        # the servers own the device; this process never initializes JAX
        if args.workers >= 2:
            return bench_serving_fleet(args.workers, args.concurrency, args.duration)
        return bench_serving(args.concurrency, args.duration)
    _stage("device")
    _check_device(device_stamp())  # fail before measuring, not after
    _stage("measure")
    if args.config == "replay":
        return bench_replay(args.journal, args.events, args.nodes, args.speed)
    if args.config == "steady":
        return bench_steady(args.pods, args.nodes, args.repeats)
    if args.config == "campaign":
        return bench_campaign(args.nodes, args.pods, args.warmup)
    if args.config == "defrag":
        return bench_defrag(args.scenarios, args.nodes, args.pods, args.warmup)
    if args.config == "example":
        return bench_reference_example(
            os.path.join(repo, "example/simon-config.yaml"), "", args.warmup, "example/simon-config"
        )
    if args.config == "gpushare":
        return bench_reference_example(
            os.path.join(repo, "example/simon-gpushare-config.yaml"),
            "gpu",
            args.warmup,
            "example/simon-gpushare-config",
        )

    if args.config == "forced":
        # 90% of the pod stream is pre-bound snapshot pods
        cluster = forced_cluster(args.nodes, int(args.pods * 0.9))
        apps = [AppResource("bench", synthetic_apps(args.pods - int(args.pods * 0.9)))]
    elif args.config == "gpu":
        cluster = gpu_cluster(args.nodes)
    elif args.config == "local-pv":
        cluster = local_pv_cluster(args.nodes)
    else:
        cluster = synthetic_cluster(args.nodes)
    if args.config == "affinity":
        apps = [AppResource("bench", affinity_apps(args.pods))]
    elif args.config == "gpu":
        apps = [AppResource("bench", gpu_apps(args.pods))]
    elif args.config == "local-pv":
        apps = [AppResource("bench", local_pv_apps(args.pods))]
    elif args.config == "bigu":
        rt = bigu_apps(args.pods)
        # per-template replica rounding changes the real pod count: keep the
        # reported label honest (the driver parses the metric line)
        args.pods = sum(w.replicas for w in rt.deployments)
        apps = [AppResource("bench", rt)]
    elif args.config != "forced":
        apps = [AppResource("bench", synthetic_apps(args.pods))]

    from opensim_tpu.utils.trace import PREP_STATS

    cold_s = None
    if args.warmup:
        _stage("warmup")
        t0 = time.time()
        simulate(cluster, apps, node_pad=128)
        cold_s = round(time.time() - t0, 3)

    _stage("measure")
    PREP_STATS.reset()
    # --trace: span-trace the measured run (the explicit flag wins over
    # OPENSIM_TRACE=0); the root span brackets exactly the timed region, so
    # the exported trace's total time matches the reported wall time
    from opensim_tpu.obs import trace as tracing

    tr = tracing.start_trace("bench", force=True) if args.trace else None
    t0 = time.time()
    with tracing.trace_scope(tr):
        result = simulate(cluster, apps, node_pad=128, explain=args.explain)
    dt = time.time() - t0
    if tr is not None:
        tr.finish()
    prep_last = PREP_STATS.snapshot()["last"]  # the measured run's prepare

    scheduled = sum(len(ns.pods) for ns in result.node_status)
    target_s = 10.0
    record = {
        "metric": f"{_fmt(args.pods)}-pod/{_fmt(args.nodes)}-node "
        + {
            "affinity": "affinity-heavy ", "bigu": "1000-template ",
            "forced": "forced-replay ", "gpu": "all-GPU-share ",
            "local-pv": "all-local-PV ",
        }.get(args.config, "")
        + "capacity plan wall-clock",
        "value": round(dt, 3),
        "unit": "s",
        "vs_baseline": round(target_s / dt, 2) if dt > 0 else 0.0,
        "scheduled": scheduled,
        "unscheduled": len(result.unscheduled_pods),
        "pods_per_sec": round((scheduled + len(result.unscheduled_pods)) / dt, 1),
    }
    if cold_s is not None:
        record["cold_s"] = cold_s  # includes first-compile (cached across runs)
    if prep_last is not None:
        # host-side expand+encode seconds of the measured run (the cold full
        # prepare; --config steady reports the warm/cached trajectory)
        record["host_prep_s"] = round(prep_last[1], 3)
    if result.engine is not None:
        # engine attribution (VERDICT r4 #3): which engine produced this
        # number, and why the faster ones (if any) were skipped
        record["engine"] = result.engine.name
        if result.engine.skipped:
            record["engine_skipped"] = result.engine.skipped
        # C++ engine path attribution (ISSUE 4): incremental vs generic —
        # a cache disengage must be visible in the record, never inferred
        if result.engine.native_path is not None:
            record["native_path"] = result.engine.native_path
            record["native_steps"] = result.engine.native_steps
        # decision audit (--explain): per-filter reject totals + pods by
        # primary unschedulable reason, straight off the EngineDecision
        if args.explain and result.engine.filter_rejects is not None:
            record["filter_rejects"] = result.engine.filter_rejects
            reason_hist = {}
            for e in result.engine.explanations or []:
                if e.status != "scheduled":
                    from opensim_tpu.engine.reasons import primary_code

                    code = primary_code(e.reasons)
                    key = code.name.lower() if code is not None else e.status
                    reason_hist[key] = reason_hist.get(key, 0) + 1
            record["unschedulable_reasons"] = reason_hist
    if args.config in ("gpu", "local-pv"):
        # ISSUE 19 in-row gates: the measured (incremental) placements must
        # be bit-identical to the generic C++ path AND the XLA scan, and the
        # incremental envelope must actually have engaged — a row that went
        # generic measures the wrong thing even when it is fast enough
        _stage("verify")
        gates = _verify_envelope(cluster, apps)
        record["native_engaged"] = int(
            result.engine is not None
            and result.engine.native_path == "incremental"
            and gates.pop("verify_native_path") == "incremental"
            and bool(gates.pop("verify_classes"))
        )
        record.update(gates)
    if os.environ.get("OPENSIM_NATIVE_PROFILE"):
        # per-stage engine timings as structured data (still ONE JSON line);
        # populated by the C++ engine when profiling is enabled
        from opensim_tpu.engine import nativepath as _np_path

        prof = _np_path.last_profile()
        if prof is not None:
            record["native_profile"] = prof
    serial, cxx = _serial_floors(
        args.config, scheduled + len(result.unscheduled_pods), args.nodes
    )
    if serial and serial.get("schedule_s") and dt > 0:
        record["vs_serial"] = round(serial["schedule_s"] / dt, 1)
        record["serial_schedule_s"] = serial["schedule_s"]
    if cxx and cxx.get("schedule_s") and dt > 0:
        # the headline honest ratio: vectorized wall-clock vs the measured
        # compiled-serial (Go-cost stand-in) schedule time
        record["vs_serial_cxx"] = round(cxx["schedule_s"] / dt, 1)
        record["cxx_serial_schedule_s"] = cxx["schedule_s"]
    if tr is not None:
        tracing.write_chrome(tr, args.trace)
        # the measured wall time and the trace's root span, side by side —
        # the two must agree (acceptance: within 10%)
        record["trace_file"] = args.trace
        record["trace_span_s"] = round(tr.root.duration_s, 3)
    _emit(record)
    return 0


def _guarded_main() -> int:
    """Top-level failure contract: one JSON line on stdout, nonzero exit.
    argparse's own exits (usage errors print to stderr) are translated into
    the same one-line shape so the driver never sees an empty stdout."""
    try:
        return main()
    except SystemExit as e:
        if e.code in (0, None):
            return 0
        print(json.dumps({"error": f"exited with status {e.code}", "stage": _STAGE[0]}))
        return e.code if isinstance(e.code, int) else 1
    except KeyboardInterrupt:
        print(json.dumps({"error": "interrupted", "stage": _STAGE[0]}))
        return 130
    except BaseException as e:
        print(json.dumps({"error": f"{type(e).__name__}: {e}", "stage": _STAGE[0]}))
        return 1


if __name__ == "__main__":
    sys.exit(_guarded_main())
