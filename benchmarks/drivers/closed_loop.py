"""`closed-loop`: clients that each wait for the answer before they send the
next `POST /api/deploy-apps`, over a loopback socket, against `SimonServer` +
`SimonHTTPServer` with the live twin synced from a stub apiserver, all in the
benchmark's process (the process that holds the chip).

The requests are a fixed cycle drawn from the seed: the traffic file's set of
Deployment sizes in a seeded order, each with a seeded CPU and memory request,
and a name that no other request of the run has, so the full-key prepare
cache never answers. One client (`clients: 1`) sends them back to back. A
traced window sends the traffic file's `traced_replicas` in that order, the
same sizes for every seed, so a per-layer metric does not move with which
sizes a short window happened to hold.
"""

from __future__ import annotations

import http.client
import importlib
import json
import os
import random
import socket
import sys
import threading
import time
from typing import Dict, List, Optional

from benchmarks import promtext
from benchmarks.generators.twin_cluster import MI, deploy_payload
from benchmarks.reference import compare
from benchmarks.reference.kube_reference import Cluster, Reference, Workload
from benchmarks.stubapi import StubApiServer
from benchmarks.window import Item, Window

from . import Context, canon_pod_ref, checks_from

EMPTY_LISTS = (
    "/apis/apps/v1/daemonsets", "/apis/policy/v1/poddisruptionbudgets",
    "/api/v1/services", "/apis/storage.k8s.io/v1/storageclasses",
    "/api/v1/persistentvolumeclaims", "/api/v1/configmaps",
)


class Client:
    """One persistent HTTP/1.1 connection (`server/loadgen.py`'s `_Client`):
    connection churn is no part of a request's latency."""

    def __init__(self, host: str, port: int, timeout_s: float) -> None:
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.conn: Optional[http.client.HTTPConnection] = None

    def request(self, method: str, path: str, body: Optional[bytes] = None):
        """(status, headers, body, start, end); status 0 on a transport error."""
        start = time.monotonic()
        if self.conn is None:
            self.conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout_s)
            self.conn.connect()
            # headers and body go out in two writes: without this the second
            # waits for the server's delayed ACK, 40 ms that are the client's
            self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            self.conn.request(method, path, body=body,
                              headers={"Content-Type": "application/json"} if body else {})
            resp = self.conn.getresponse()
            data = resp.read()
            return resp.status, dict(resp.headers), data, start, time.monotonic()
        except (OSError, http.client.HTTPException) as e:
            print(f"[bench] {method} {path} failed: {type(e).__name__}: {e}", file=sys.stderr)
            self.close()
            return 0, {}, b"", start, time.monotonic()

    def close(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None


def engine_attrs(node: dict) -> List[str]:
    """What the program's own spans say of the engine that ran a request:
    the `schedule` span's attributes and why each rung of the ladder above
    it was turned away (the count of templates, U, stands there)."""
    out = []
    if node.get("attrs") and (node["name"] == "schedule" or node["name"].startswith("engine.")):
        out.append(f"{node['name']} {json.dumps(node['attrs'], sort_keys=True)}")
    for c in node.get("children", []):
        out.extend(engine_attrs(c))
    return out


def recorder_tree(node: dict, anchor: float) -> dict:
    """A flight-recorder span (`/api/debug/requests/<id>`) as plain data on
    the monotonic clock: its times are relative to the request's root."""
    start = anchor + float(node.get("start_s", 0.0))
    return {"name": node["name"], "start": start, "end": start + float(node.get("duration_s", 0.0)),
            "children": [recorder_tree(c, anchor) for c in node.get("children", [])]}


class Driver:
    def __init__(self, ctx: Context) -> None:
        self.ctx = ctx
        self.stub = self.httpd = self.server = self.supervisor = self.thread = None
        self.client: Optional[Client] = None
        self.sent = 0

    # -- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        """The twin's objects and the cycle of requests, from the seed."""
        ctx, p = self.ctx, self.ctx.params
        if p.get("clients", 1) != 1:
            raise ValueError("closed-loop drives one client; more is a later traffic mix's driver")
        gen = importlib.import_module(f"benchmarks.generators.{ctx.config['generator']}")
        self.inputs = gen.generate(ctx.sizes, ctx.seed, ctx.scratch)
        rng = random.Random(ctx.seed * 2654435761 % (2 ** 31) + 17)
        sizes = list(ctx.sizes.get("request_replicas") or p["request_replicas"])
        rng.shuffle(sizes)
        lo_c, hi_c, step_c = p["request_cpu_m"]
        lo_m, hi_m, step_m = p["request_memory_mi"]
        self.cycle = [
            {"replicas": r, "cpu_m": rng.randrange(lo_c, hi_c + 1, step_c),
             "mem_mi": rng.randrange(lo_m, hi_m + 1, step_m)}
            for r in sizes
        ]
        if ctx.traced:
            by_size = {c["replicas"]: c for c in self.cycle}
            self.cycle = [by_size[r] for r in ctx.sizes.get("traced_replicas") or p["traced_replicas"]]
        # the warm-up is one request of every size the mix has, `one(-1)`,
        # `one(-2)`, ..., driven by the harness: each size compiles on first sight
        self.warmup = [{"replicas": r, "cpu_m": lo_c, "mem_mi": lo_m}
                       for r in ctx.sizes.get("warmup_replicas") or p["warmup_replicas"]]
        self.warmup_items = len(self.warmup)

    def setup(self) -> None:
        self.prepare()
        ctx = self.ctx
        if not ctx.rehearse:
            os.environ["OPENSIM_REQUIRE_TPU"] = "1"

        self.stub = StubApiServer(bookmark_interval_s=0.2).start()
        self.stub.seed("/api/v1/nodes", self.inputs["node_docs"])
        self.stub.seed("/api/v1/pods", self.inputs["pod_docs"])
        for path in EMPTY_LISTS:
            self.stub.seed(path, [])
        kubeconfig = self.stub.kubeconfig(ctx.scratch)

        from opensim_tpu.server.rest import SimonHTTPServer, SimonServer, build_twin, make_handler

        self.supervisor, _journal = build_twin(kubeconfig, "", "on", "")
        self.server = SimonServer(kubeconfig=kubeconfig, master="", watch=self.supervisor)
        self.supervisor.prep_cache = self.server.prep_cache
        if not self.supervisor.start(wait_s=120.0):
            raise RuntimeError("the live twin did not sync from the stub apiserver")
        twin, _key, stale = self.supervisor.serving_snapshot()
        print(f"[bench] twin synced: {len(twin.nodes)} nodes, {len(twin.pods)} pods, stale={stale}",
              file=sys.stderr)
        self.httpd = SimonHTTPServer(("127.0.0.1", 0), make_handler(self.server))
        self.thread = threading.Thread(target=self.httpd.serve_forever, name="bench-httpd", daemon=True)
        self.thread.start()
        self.client = Client("127.0.0.1", self.httpd.server_address[1], timeout_s=300.0)

    def warmed(self, window) -> None:
        if window.failed:
            raise RuntimeError("a warm-up request failed")
        print("[bench] warm-up requests (pods: seconds): " + ", ".join(
            f"{it.info['request']['replicas']}: {it.end - it.start:.3f}" for it in window.items),
            file=sys.stderr)

    def counters(self) -> dict:
        from opensim_tpu.obs.profile import COMPILES

        status, _h, body, _s, _e = self.client.request("GET", "/metrics")
        return {"compiles": COMPILES.snapshot(),
                "prom": promtext.parse(body.decode()) if status == 200 else None}

    # -- the window ---------------------------------------------------------

    def _send(self, req: dict, name: str) -> Item:
        body = deploy_payload(name, req["replicas"], req["cpu_m"], req["mem_mi"])
        status, headers, data, start, end = self.client.request("POST", self.ctx.params["endpoint"], body)
        return Item(start=start, end=end, ok=status == 200, answer=data,
                    info={"request": dict(req, name=name), "status": status,
                          "id": headers.get("X-Simon-Request-Id", "")})

    def one(self, i: int, traced: bool) -> Item:
        if i < 0:
            req = self.warmup[-1 - i]
            return self._send(req, f"warm-{req['replicas']}")
        req = self.cycle[self.sent % len(self.cycle)]
        it = self._send(req, f"bench-{self.sent}")
        self.sent += 1
        return it

    def after_window(self, window) -> None:
        for it in window.items:
            try:
                it.info["body"] = json.loads(it.answer) if it.ok else None
            except ValueError:
                it.info["body"], it.ok = None, False
        print("[bench] requests (pods: seconds): " + ", ".join(
            f"{it.info['request']['replicas']}: {it.end - it.start:.3f}" for it in window.items),
            file=sys.stderr)

    def fetch_spans(self, window) -> None:
        """The traced run reads each request's span tree from the flight
        recorder. The tree's clock is relative to its root, which the
        request's own send and receive times bracket."""
        for it in window.items:
            if not it.info.get("id"):
                continue
            status, _h, body, _s, _e = self.client.request("GET", f"/api/debug/requests/{it.info['id']}")
            if status != 200:
                continue
            doc = json.loads(body)
            root = doc["spans"]
            if it is window.items[0]:
                print("[bench] engine of the first request: " + "; ".join(engine_attrs(root)), file=sys.stderr)
            slack = max(0.0, (it.end - it.start) - float(root.get("duration_s", 0.0)))
            tree = recorder_tree(root, it.start + slack / 2.0)
            it.spans = {"name": "request", "start": it.start, "end": it.end, "children": [tree]}

    def questions(self, window) -> List[dict]:
        base: Cluster = self.inputs["cluster"]
        resident = sum(k for _n, k, _c, _m in base.bound)
        return [{"nodes": len(base.nodes), "pods": it.info["request"]["replicas"], "resident": resident}
                for it in window.items]

    # -- the comparison -----------------------------------------------------

    def answer_counts(self, it: Item):
        placed: Dict[str, Dict[str, int]] = {}
        unscheduled: Dict[str, int] = {}
        body = it.info.get("body") or {}
        for entry in body.get("nodeStatus", []):
            for pod in entry.get("pods", []):
                nodes = placed.setdefault(canon_pod_ref(pod), {})
                nodes[entry["node"]] = nodes.get(entry["node"], 0) + 1
        for up in body.get("unscheduledPods", []):
            w = canon_pod_ref(up["pod"])
            unscheduled[w] = unscheduled.get(w, 0) + 1
        return placed, unscheduled

    def request_cluster(self, it: Item) -> Cluster:
        base: Cluster = self.inputs["cluster"]
        r = it.info["request"]
        w = Workload(name=f"default/{r['name']}", replicas=r["replicas"], cpu_m=r["cpu_m"],
                     mem_bytes=r["mem_mi"] * MI, labels={"app": r["name"]})
        return Cluster(nodes=base.nodes, bound=base.bound, workloads=[w])

    def compare(self, window, answers=None) -> List[dict]:
        """Every request of the window: its answer has to be the placements
        of the twin's state plus that request alone. `answers` puts other
        answers in the program's place (the control)."""
        precision = self.ctx.config["precision"]
        values = {"misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0,
                  "unscheduled_diff": 0, "answer_diff": 0, "requests_unanswered": 0}
        for k, it in enumerate(window.items):
            if it.info.get("body") is None and answers is None:
                values["requests_unanswered"] += 1
                continue
            placed, unscheduled = answers[k] if answers is not None else self.answer_counts(it)
            got = compare.replay(self.request_cluster(it), placed, unscheduled, precision)
            for name, v in got.items():
                values[name] = max(values[name], v) if name == "worst_score_gap" else values[name] + v
        return checks_from(values, self.ctx.limits)

    def control(self, precision: str) -> List[dict]:
        """The reference in a lower precision, put in the program's place for
        as many requests of the cycle as a full window compares."""
        items = [Item(start=0.0, end=0.0, ok=True,
                      info={"request": dict(self.cycle[k % len(self.cycle)], name=f"bench-{k}")})
                 for k in range(int(self.ctx.params["control_requests"]))]
        answers = []
        for it in items:
            cluster = self.request_cluster(it)
            placed, unscheduled = Reference(cluster, precision).free_run()
            answers.append(compare.counts_of(placed, unscheduled, cluster))
        return self.compare(Window(opened=0.0, closed=0.0, items=items), answers=answers)

    def close(self) -> None:
        """`rest.serve()`'s drain order; each part is closed once."""
        client, httpd, thread = self.client, self.httpd, self.thread
        server, supervisor, stub = self.server, self.supervisor, self.stub
        self.client = self.httpd = self.thread = self.server = self.supervisor = self.stub = None
        if client is not None:
            client.close()
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if thread is not None:
            thread.join(timeout=30.0)
        if server is not None and server.admission is not None:
            server.admission.stop()
        if supervisor is not None:
            supervisor.stop()
        if server is not None:
            server.close()
        if stub is not None:
            stub.stop()
