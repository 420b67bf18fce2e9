#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that opensim-tpu still starts on the chip.

Drives the system's main paths once, through the entry points a user calls
(``python -m opensim_tpu apply`` and ``python -m opensim_tpu server``), at the
repo's headline size, and checks what comes out by the repo's own means:

  plan-fit      simon apply --backend tpu, 50,000 pods / 5,000 nodes (bench.py's
                headline shape). Pass: everything scheduled on ``megakernel``,
                no rung skipped.
  plan-fit-xla  the same plan on the XLA scan (--backend xla), own process.
                Pass: pods per (node, workload) identical to plan-fit — the
                compiled kernel against the reference engine at full size.
  plan-short    the same apps on a cluster that is too small, with a newNode
                template: a megakernel pass kept without failure reasons
                (none are asked), count sweep, masked final pass. Pass:
                ``(added K new node(s))``.
  plan-short-xla  the same input with --backend xla. Pass: the same K.
  server        simon server --backend tpu against a stub apiserver holding
                3,000 nodes / 30,000 bound pods; a handful of deploy-apps
                requests (two of them concurrent), /healthz, /metrics, SIGTERM.
                Pass: every answer 200 with placements, a device engine span
                and no engine.native span in the flight recorder, exit 0.

This process never initializes a JAX backend: every phase is ONE child process
that owns the chip, one after another (a chip belongs to one process at a
time). Inputs are generated from --seed into --out. Wall times are smoke
observations, not benchmark results. The last line of stdout is one JSON
object; the exit code is 0 only if every phase passed on a TPU.

``--rehearse`` is for debugging this script on a CPU box: tiny sizes, the
Pallas interpreter, ``--backend auto``. A rehearsal prints REHEARSAL on every
line that could be mistaken for a result, never prints the JSON summary with
``"ok": true``, and always exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

try:  # importing the package initializes no JAX backend; these are stdlib + package only
    from opensim_tpu.server.loadgen import _payload, _seed_stub, canon_pod_ref
except ImportError as e:
    print(f"chip_smoke.py: the opensim_tpu package is not beside this script ({e})", file=sys.stderr)
    sys.exit(2)

# the headline shape (bench.py synthetic_cluster / synthetic_apps)
# The short cluster: 2/3 of its nodes are the ssd pool, capped at ssd_cap pods
# a node; the 5 ssd-only workloads want pods/4 slots there. At 4,600 nodes the
# pool holds 3,066 x 4 = 12,264 of the 12,500 it is asked for, so 236 pods fail
# MID-stream (later workloads still bind on the hdd pool) and the planner has
# to add ceil(236 / new_cap) = 24 nodes of the newNode template.
FULL = dict(nodes=5000, pods=50000, short_nodes=4600, ssd_cap=4, new_cap=10,
            server_nodes=3000, server_pods=30000)
TINY = dict(nodes=40, pods=400, short_nodes=26, ssd_cap=4, new_cap=10,
            server_nodes=24, server_pods=96)
N_WORKLOADS = 20
ZONE = "topology.kubernetes.io/zone"
DEVICE_ENGINES = ("engine.megakernel", "engine.xla")


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# inputs, from a seed
# ---------------------------------------------------------------------------


def node_doc(name: str, i: int, pods_cap: str) -> dict:
    alloc = {"cpu": "64", "memory": "256Gi", "pods": pods_cap}
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {
            "name": name,
            "labels": {
                "kubernetes.io/hostname": name,
                ZONE: f"zone-{i % 4}",
                "node-role.kubernetes.io/worker": "",
                "disk": "ssd" if i % 3 else "hdd",
            },
        },
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def deployment_doc(w: int, replicas: int) -> dict:
    """Workload w of bench.py's synthetic_apps: every 4th pinned to ssd nodes,
    every 5th with a soft zone spread."""
    name = f"bench-{w}"
    spec: dict = {
        "containers": [
            {
                "name": "nginx",
                "image": "nginx:latest",
                "resources": {
                    "requests": {
                        "cpu": f"{100 + 20 * (w % 8)}m",
                        "memory": f"{256 + 64 * (w % 6)}Mi",
                    }
                },
            }
        ]
    }
    if w % 4 == 0:
        spec["nodeSelector"] = {"disk": "ssd"}
    if w % 5 == 0:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": 5,
                "topologyKey": ZONE,
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": name}},
            }
        ]
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "default", "labels": {"app": name}},
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": {"app": name}},
            "template": {"metadata": {"labels": {"app": name}}, "spec": spec},
        },
    }


def write_docs(path: str, docs: list) -> None:
    # JSON is YAML: one document per line keeps 5,000 nodes a sub-second write
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for d in docs:
            f.write("---\n" + json.dumps(d) + "\n")


def write_plan_inputs(out: str, size: dict, seed: int) -> dict:
    """cluster dir + app dir + simon Config for the fitting plan and the short
    one. The seed shuffles which node index carries which name, nothing else:
    the shapes are the benchmark's."""
    rng = random.Random(seed)
    order = list(range(size["nodes"]))
    rng.shuffle(order)
    fit_nodes = [node_doc(f"node-{order[i]:05d}", i, "256") for i in range(size["nodes"])]
    # the short cluster (sizing: see FULL): the ssd-only workloads run out of
    # room mid-stream while later workloads still bind: a caller that reads
    # failure reasons would have the megakernel pass discarded and re-scanned
    # for exact attribution; the plan has a newNode template and asks for none
    short_nodes = [
        node_doc(f"node-{order[i]:05d}", i, str(size["ssd_cap"]) if i % 3 else "256")
        for i in range(size["short_nodes"])
    ]
    new_node = node_doc("new-ssd", 1, str(size["new_cap"]))
    apps = [deployment_doc(w, size["pods"] // N_WORKLOADS) for w in range(N_WORKLOADS)]
    write_docs(os.path.join(out, "plan", "cluster-fit", "nodes.yaml"), fit_nodes)
    write_docs(os.path.join(out, "plan", "cluster-short", "nodes.yaml"), short_nodes)
    write_docs(os.path.join(out, "plan", "newnode", "node.yaml"), [new_node])
    write_docs(os.path.join(out, "plan", "apps", "deployments.yaml"), apps)
    configs = {}
    for name, cluster, new in (("fit", "cluster-fit", ""), ("short", "cluster-short", "newnode")):
        path = os.path.join(out, "plan", f"simon-{name}.yaml")
        with open(path, "w") as f:
            f.write(
                "apiVersion: simon/v1alpha1\nkind: Config\nmetadata:\n  name: chip-smoke\n"
                f"spec:\n  cluster:\n    customConfig: {cluster}\n"
                "  appList:\n  - name: bench\n    path: apps\n"
                + (f"  newNode: {new}\n" if new else "")
            )
        configs[name] = path
    return configs


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def child_env(rehearse: bool) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("OPENSIM_FASTPATH", None)  # the interpreter is never a chip result
    if rehearse:
        env.update(JAX_PLATFORMS="cpu", OPENSIM_FASTPATH="interpret", OPENSIM_DISABLE_NATIVE="1")
    return env


_DEVICE_RE = re.compile(
    r"platform=(\S+) device_kind='([^']*)' devices=(\d+) backend_compiles=(\d+) "
    r"compile_s=([\d.]+) cache_hits=(\d+) cache_misses=(\d+) cache_dir=(\S+)"
)


def parse_device_line(text: str) -> dict:
    m = None
    for m in _DEVICE_RE.finditer(text):
        pass
    if m is None:
        raise PhaseFailed("child printed no `platform=… device_kind=…` line")
    return {
        "platform": m.group(1), "device_kind": m.group(2), "device_count": int(m.group(3)),
        "backend_compiles": int(m.group(4)), "compile_s": float(m.group(5)),
        "cache_hits": int(m.group(6)), "cache_misses": int(m.group(7)), "cache_dir": m.group(8),
    }


def parse_report(path: str) -> dict:
    """What `simon apply --report-pods` printed: the verdict lines, pods per
    (node, workload) from the Pod Info table, and the engine line."""
    placements: dict = {}
    added = 0
    engine = ""
    success = False
    section = ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if line == "Simulation success!":
                success = True
            m = re.fullmatch(r"\(added (\d+) new node\(s\)\)", line)
            if m:
                added = int(m.group(1))
            if line.startswith("Scheduling engine: "):
                engine = line[len("Scheduling engine: "):]
            if line in ("Node Info", "Pod Info", "App Info"):
                section = line
                continue
            if section == "Pod Info" and " | " in line and not line.startswith("Node "):
                cols = [c.strip() for c in line.split("|")]
                key = (cols[0], canon_pod_ref(cols[1]))
                placements[key] = placements.get(key, 0) + 1
    return {"success": success, "added": added, "engine": engine, "placements": placements}


def trace_passes(path: str) -> list:
    """The engine of each pass, in order, from the apply run's span trace:
    engine.* spans (one per engine attempt) and sweep.* spans."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = [
        e for e in events
        if e.get("ph") == "X"
        and re.fullmatch(r"(engine\.(megakernel|xla|native)|sweep\.\w+)(\.skipped)?", e["name"])
    ]
    spans.sort(key=lambda e: e.get("ts", 0))
    out = []
    for e in spans:
        label = e["name"]
        args = e.get("args") or {}
        if "scenarios" in args:
            label += f"[{args['scenarios']} scenarios]"
        if e.get("dur"):
            label += f" {e['dur'] / 1e6:.2f}s"
        out.append(label)
    return out


def run_apply(phase: str, config: str, backend: str, out: str, rehearse: bool, timeout_s: float) -> dict:
    report = os.path.join(out, f"{phase}.report.txt")
    trace = os.path.join(out, f"{phase}.trace.json")
    log = os.path.join(out, f"{phase}.log")
    cmd = [
        sys.executable, "-m", "opensim_tpu", "apply", "-f", config,
        "--backend", "auto" if rehearse and backend == "tpu" else backend,
        "--report-pods", "--output-file", report, "--trace", trace,
    ]
    t0 = time.monotonic()
    with open(log, "w") as lf:
        proc = subprocess.run(
            cmd, env=child_env(rehearse), stdout=lf, stderr=subprocess.STDOUT,
            timeout=timeout_s, cwd=REPO,
        )
    wall = time.monotonic() - t0
    with open(log) as lf:
        text = lf.read()
    if proc.returncode != 0:
        raise PhaseFailed(f"{phase}: `{' '.join(cmd[1:])}` exited {proc.returncode}:\n{text[-3000:]}")
    res = parse_report(report)
    res.update(parse_device_line(text))
    res["passes"] = trace_passes(trace)
    res["wall_s"] = round(wall, 2)
    if not res["success"]:
        raise PhaseFailed(f"{phase}: no `Simulation success!` in {report}")
    say(
        f"[{phase}] platform={res['platform']} device_kind={res['device_kind']!r} "
        f"devices={res['device_count']} engine={res['engine']!r}\n"
        f"[{phase}]   passes: {', '.join(res['passes']) or '(none traced)'}\n"
        f"[{phase}]   scheduled={sum(res['placements'].values())} added_nodes={res['added']} "
        f"wall={res['wall_s']}s (smoke observation, not a benchmark) "
        f"compiles={res['backend_compiles']} ({res['compile_s']}s) persistent-cache "
        f"hits={res['cache_hits']} misses={res['cache_misses']} dir={res['cache_dir']}"
    )
    return res


def check_on_chip(phase: str, res: dict, rehearse: bool) -> None:
    if rehearse:
        return  # a rehearsal is on the CPU by construction, and never a pass
    if res["platform"] != "tpu":
        raise PhaseFailed(f"{phase}: ran on platform {res['platform']!r}, not tpu")
    ran = [p for p in res["passes"] if re.match(r"(engine|sweep)\.native\b(?!\.skipped)", p)]
    if ran or res["engine"].startswith("native"):
        raise PhaseFailed(f"{phase}: the C++ engine ran ({ran or res['engine']}) — that is a CPU result")


# ---------------------------------------------------------------------------
# the server phase
# ---------------------------------------------------------------------------


def deploy_payload(i: int, replicas: int) -> tuple:
    """(workload name, body) of loadgen's deploy payload with exactly
    ``replicas`` pods (its replica count is 1 + seq % replicas)."""
    return f"lg-{i}-{replicas - 1}", _payload(i, replicas - 1, replicas, "500m", "1Gi")


def http(url: str, data: bytes = None, timeout_s: float = 600.0):
    req = urllib.request.Request(
        url, data=data, method="POST" if data is not None else "GET",
        headers={"Content-Type": "application/json"} if data is not None else {},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def span_names(node: dict) -> list:
    return [node["name"]] + [n for c in node.get("children", []) for n in span_names(c)]


def run_server(out: str, size: dict, rehearse: bool, port: int, timeout_s: float) -> dict:
    stub = _seed_stub(size["server_nodes"], size["server_pods"])
    log = os.path.join(out, "server.log")
    proc = None
    t0 = time.monotonic()
    try:
        kc = stub.kubeconfig(out)
        cmd = [
            sys.executable, "-m", "opensim_tpu", "server", "--backend",
            "auto" if rehearse else "tpu", "--kubeconfig", kc, "--port", str(port),
        ]
        # a wide coalescing window, so the two concurrent requests below
        # reliably ride one request-axis batch
        env = dict(child_env(rehearse), OPENSIM_BATCH_WINDOW_MS="250")
        with open(log, "w") as lf:
            proc = subprocess.Popen(cmd, env=env, stdout=lf, stderr=subprocess.STDOUT, cwd=REPO)
        url = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 180.0
        while True:
            if proc.poll() is not None:
                raise PhaseFailed(f"server exited at boot (rc={proc.returncode}):\n{open(log).read()[-3000:]}")
            try:
                if http(f"{url}/healthz", timeout_s=2.0)[0] == 200:
                    break
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise PhaseFailed("server did not answer /healthz within 180 s")
            time.sleep(0.25)

        answers = []

        def deploy(i: int, replicas: int) -> None:
            name, payload = deploy_payload(i, replicas)
            t = time.monotonic()
            status, headers, body = http(f"{url}/api/deploy-apps", payload, timeout_s)
            answers.append((name, replicas, status, headers, body, time.monotonic() - t))

        deploy(0, 3)  # the first request pays the compiles
        deploy(1, 300)  # one of a few hundred pods
        pair = [threading.Thread(target=deploy, args=(2 + i, 2 + i)) for i in range(2)]
        for t in pair:
            t.start()
        for t in pair:
            t.join()
        deploy(4, 5)

        engines = set()
        for name, replicas, status, headers, body, secs in answers:
            if status != 200:
                raise PhaseFailed(f"server: deploy {name} answered {status}: {body[:500]!r}")
            doc = json.loads(body)
            placed = sum(
                1 for e in doc.get("nodeStatus", []) for p in e["pods"]
                if canon_pod_ref(p) == f"default/{name}"
            )
            if placed != replicas or doc.get("unscheduledPods"):
                raise PhaseFailed(
                    f"server: deploy {name} placed {placed}/{replicas} pods, "
                    f"unscheduled={doc.get('unscheduledPods')}"
                )
            rid = headers.get("X-Simon-Request-Id", "")
            st, _h, tree = http(f"{url}/api/debug/requests/{rid}")
            if st != 200:
                raise PhaseFailed(f"server: no flight-recorder trace for request {rid!r} ({st})")
            doc = json.loads(tree)
            mine = {n for n in span_names(doc["spans"]) if re.fullmatch(r"engine\.(megakernel|xla|native)", n)}
            if doc.get("engine"):
                # a batch rider's tree carries the batch's engine as an
                # attribute (the engine span sits on the batch's own trace)
                mine.add("engine." + re.split(r"[ /]", doc["engine"])[0])
            mine = sorted(mine)
            say(f"[server]   {name}: 200, {placed} pods placed, {secs:.2f}s, spans {mine}, "
                f"engine {str(doc.get('engine'))[:200]!r}")
            if "engine.native" in mine:
                raise PhaseFailed(f"server: request {name} ran the C++ engine — a CPU result")
            if not set(mine) & set(DEVICE_ENGINES):
                raise PhaseFailed(f"server: request {name} shows no device engine span ({mine})")
            engines |= set(mine)

        if http(f"{url}/healthz")[0] != 200:
            raise PhaseFailed("server: /healthz failed after the requests")
        st, _h, metrics = http(f"{url}/metrics")
        if st != 200:
            raise PhaseFailed(f"server: /metrics answered {st}")
        text = metrics.decode()

        def metric(pattern: str, default: float = 0.0) -> float:
            m = re.search(pattern, text, re.M)
            return float(m.group(1)) if m else default

        m = re.search(r'^simon_device_info\{device_kind="([^"]*)",platform="([^"]*)"\} (\d+)', text, re.M)
        if not m:
            raise PhaseFailed("server: /metrics carries no simon_device_info")
        res = {
            "platform": m.group(2), "device_kind": m.group(1), "device_count": int(m.group(3)),
            "engine": "+".join(sorted(engines)), "passes": sorted(engines),
            "backend_compiles": int(metric(r"^simon_backend_compile_total (\S+)")),
            "compile_s": round(metric(r"^simon_backend_compile_seconds_total (\S+)"), 2),
            "cache_hits": int(metric(r'^simon_jitcache_events_total\{event="cache_hits"\} (\S+)')),
            "cache_misses": int(metric(r'^simon_jitcache_events_total\{event="cache_misses"\} (\S+)')),
            "batches": int(metric(r"^simon_batches_total (\S+)")),
            "batched_requests": int(metric(r"^simon_batch_size_sum (\S+)")),
            "requests": len(answers),
        }
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120.0)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("server: did not exit within 120 s of SIGTERM")
        if rc != 0:
            raise PhaseFailed(f"server: exited {rc} after SIGTERM:\n{open(log).read()[-2000:]}")
        res["wall_s"] = round(time.monotonic() - t0, 2)
        say(
            f"[server] platform={res['platform']} device_kind={res['device_kind']!r} "
            f"devices={res['device_count']} engines={res['engine']} "
            f"{size['server_nodes']} nodes / {size['server_pods']} bound pods, "
            f"{res['requests']} requests all 200, batches={res['batches']} "
            f"(riders {res['batched_requests']}), exit 0\n"
            f"[server]   wall={res['wall_s']}s (smoke observation, not a benchmark) "
            f"compiles={res['backend_compiles']} ({res['compile_s']}s) persistent-cache "
            f"hits={res['cache_hits']} misses={res['cache_misses']}"
        )
        return res
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        stub.stop()


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"))
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--port", type=int, default=18721)
    ap.add_argument("--phase-timeout", type=float, default=900.0)
    ap.add_argument("--rehearse", action="store_true", help="tiny CPU rehearsal; never a pass, exit 3")
    args = ap.parse_args()

    if os.environ.get("OPENSIM_FASTPATH") and not args.rehearse:
        print("chip_smoke.py: OPENSIM_FASTPATH is set; the interpreter is not the chip", file=sys.stderr)
        return 2
    size = TINY if args.rehearse else FULL
    tag = "REHEARSAL (cpu, interpreter, tiny) — NOT A CHIP RESULT: " if args.rehearse else ""
    os.makedirs(args.out, exist_ok=True)
    say(
        f"{tag}chip_smoke seed={args.seed} out={args.out} sizes: plan {size['pods']} pods / "
        f"{size['nodes']} nodes (64 CPU, 256Gi, 256 pods, 4 zones), short plan "
        f"{size['short_nodes']} nodes (ssd pool {size['ssd_cap']} pods a node, hdd pool 256) "
        f"+ newNode template (ssd, {size['new_cap']} pods), server {size['server_nodes']} nodes / "
        f"{size['server_pods']} bound pods; no size cut"
    )
    t0 = time.monotonic()
    configs = write_plan_inputs(args.out, size, args.seed)
    say(f"inputs written in {time.monotonic() - t0:.1f}s")

    results: dict = {}
    try:
        fit = results["plan-fit"] = run_apply(
            "plan-fit", configs["fit"], "tpu", args.out, args.rehearse, args.phase_timeout)
        check_on_chip("plan-fit", fit, args.rehearse)
        if sum(fit["placements"].values()) != size["pods"] or fit["added"]:
            raise PhaseFailed(f"plan-fit: scheduled {sum(fit['placements'].values())}/{size['pods']}")
        if fit["engine"] != "megakernel":
            raise PhaseFailed(f"plan-fit: engine line is {fit['engine']!r}, want 'megakernel' with nothing skipped")

        ref = results["plan-fit-xla"] = run_apply(
            "plan-fit-xla", configs["fit"], "xla", args.out, args.rehearse, args.phase_timeout)
        check_on_chip("plan-fit-xla", ref, args.rehearse)
        if not ref["engine"].startswith("xla"):
            raise PhaseFailed(f"plan-fit-xla: engine line is {ref['engine']!r}, want the XLA scan")
        if ref["placements"] != fit["placements"]:
            diff = set(ref["placements"].items()) ^ set(fit["placements"].items())
            raise PhaseFailed(
                f"plan-fit-xla: megakernel and XLA scan disagree on {len(diff)} "
                f"(node, workload) counts, e.g. {sorted(diff)[:4]}")
        say(f"[plan-fit-xla] pods per (node, workload) identical to the megakernel run "
            f"({len(fit['placements'])} pairs, {size['pods']} pods)")

        short = results["plan-short"] = run_apply(
            "plan-short", configs["short"], "tpu", args.out, args.rehearse, args.phase_timeout)
        check_on_chip("plan-short", short, args.rehearse)
        if not 0 < short["added"] < 128:
            raise PhaseFailed(f"plan-short: added {short['added']} nodes; the cluster was meant to be short by a few dozen")
        sref = results["plan-short-xla"] = run_apply(
            "plan-short-xla", configs["short"], "xla", args.out, args.rehearse, args.phase_timeout)
        check_on_chip("plan-short-xla", sref, args.rehearse)
        if sref["added"] != short["added"]:
            raise PhaseFailed(f"plan-short: added {short['added']} nodes but the --backend xla run added {sref['added']}")
        if sum(short["placements"].values()) != size["pods"]:
            raise PhaseFailed(f"plan-short: final pass placed {sum(short['placements'].values())}/{size['pods']}")
        say(f"[plan-short] added {short['added']} new node(s), same as the --backend xla run")

        srv = results["server"] = run_server(args.out, size, args.rehearse, args.port, args.phase_timeout)
        check_on_chip("server", srv, args.rehearse)
    except (PhaseFailed, subprocess.TimeoutExpired, OSError, ValueError, KeyError) as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 3 if args.rehearse else 1

    if args.rehearse:
        say("REHEARSAL finished: the script runs end to end on the CPU. This is not a chip pass.")
        return 3
    devices = {(r["platform"], r["device_kind"], r["device_count"]) for r in results.values()}
    if len(devices) != 1:
        print(f"chip_smoke.py: FAILED: phases disagree on the device: {devices}", file=sys.stderr)
        return 1
    platform, kind, count = devices.pop()
    phases = {
        name: {
            "engine": r["engine"], "passes": r["passes"], "wall_s_smoke_only": r["wall_s"],
            "backend_compiles": r["backend_compiles"], "persistent_cache_hits": r["cache_hits"],
            **({"added_nodes": r["added"]} if "added" in r else {}),
        }
        for name, r in results.items()
    }
    say("phases " + json.dumps(phases))
    say(f"total wall {time.monotonic() - t0:.1f}s (smoke observation, not a benchmark)")
    say(json.dumps({"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
