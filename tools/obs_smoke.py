#!/usr/bin/env python
"""Observability smoke gate (``make obs-smoke``, part of ``make verify``).

Starts the REST server in-process, drives one deploy-apps request with a
propagated ``X-Simon-Request-Id``, and asserts the whole observability
contract end to end (ISSUE 5 acceptance):

1. the response echoes the request id;
2. the flight recorder serves the request's trace — a span tree covering
   prepare→encode→schedule→decode with engine child spans — at
   ``/api/debug/requests`` and ``/api/debug/requests/<id>``;
3. ``/metrics`` renders ``simon_phase_seconds_bucket`` latency histograms
   (cumulative, ``+Inf``-terminated) for the served phases.

Exit 0 on success; 1 with a one-line reason per failed check.
"""

import json
import os
import sys
import threading
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUEST_ID = "obs-smoke-0001"


def fail(msg: str) -> int:
    print(f"obs-smoke: FAIL: {msg}")
    return 1


def main() -> int:
    from http.server import ThreadingHTTPServer

    from opensim_tpu.models import ResourceTypes, fixtures as fx
    from opensim_tpu.server.rest import SimonServer, make_handler

    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i:02d}", "16", "64Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"}),
            )
        )
    server = SimonServer(base_cluster=cluster)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(server))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = httpd.server_address[1]
    base = f"http://127.0.0.1:{port}"

    try:
        payload = json.dumps(
            {"deployments": [fx.make_fake_deployment("smoke", 6, "100m", "128Mi").raw]}
        ).encode()
        req = urllib.request.Request(
            f"{base}/api/deploy-apps",
            data=payload,
            method="POST",
            headers={"X-Simon-Request-Id": REQUEST_ID},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            if resp.status != 200:
                return fail(f"deploy-apps returned HTTP {resp.status}")
            echoed = resp.headers.get("X-Simon-Request-Id")
            body = json.load(resp)
        if echoed != REQUEST_ID:
            return fail(f"request id not echoed (got {echoed!r})")
        if not body.get("nodeStatus"):
            return fail("deploy-apps scheduled nothing")

        with urllib.request.urlopen(f"{base}/api/debug/requests", timeout=30) as resp:
            summaries = json.load(resp)["requests"]
        if not any(s["request_id"] == REQUEST_ID for s in summaries):
            return fail("flight recorder summary list is missing the request")

        with urllib.request.urlopen(
            f"{base}/api/debug/requests/{REQUEST_ID}", timeout=30
        ) as resp:
            tree = json.load(resp)
        if tree["status"] != "ok" or tree["endpoint"] != "deploy-apps":
            return fail(f"unexpected trace summary: {tree['status']}/{tree['endpoint']}")

        names = set()

        def walk(node):
            names.add(node["name"])
            for c in node.get("children", ()):
                walk(c)

        walk(tree["spans"])
        needed = {"prepare", "encode", "schedule", "decode"}
        if not needed <= names:
            return fail(f"span tree missing phases {sorted(needed - names)} (got {sorted(names)})")
        # an engine-LADDER rung span specifically — engine.device_put (a
        # child of encode) must not satisfy the attribution check
        rungs = {"engine.megakernel", "engine.xla"}
        if not rungs & names:
            return fail(f"span tree has no engine-ladder rung span (got {sorted(names)})")

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
            metrics = resp.read().decode()
        for needle in (
            "# TYPE simon_phase_seconds histogram",
            'simon_phase_seconds_bucket{phase="schedule",endpoint="deploy-apps",le="+Inf"} ',
            'simon_request_seconds_bucket{endpoint="deploy-apps",status="ok",le="+Inf"} ',
            "simon_phase_seconds_count",
        ):
            if needle not in metrics:
                return fail(f"/metrics missing {needle!r}")

        print(
            "obs-smoke: ok — request id echoed, flight-recorder span tree "
            f"({len(names)} distinct spans), phase histograms rendered"
        )
        return 0
    finally:
        httpd.shutdown()


if __name__ == "__main__":
    sys.exit(main())
