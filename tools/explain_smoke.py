#!/usr/bin/env python
"""Decision-audit smoke gate (``make explain-smoke``, part of ``make verify``).

Generates a throwaway simon config (6 nodes; one schedulable and one
infeasible workload), then drives the REAL ``simon explain`` CLI against it
on the XLA scan and asserts the ISSUE 7 acceptance bar end to end:

1. ``simon explain`` renders a kube-style ``0/N nodes are available: …``
   breakdown for the unschedulable workload;
2. the aggregate per-filter reject totals record the fit rejects;
3. the deep single-pod audit resolves a workload-name query, and its
   per-plugin score breakdown sums to the reported total on the winner.

Exit 0 on success; 1 with a one-line reason per failed check.
"""

import contextlib
import io
import json
import os
import re
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fail(msg: str) -> int:
    print(f"explain-smoke: FAIL: {msg}")
    return 1


NODE_TMPL = """apiVersion: v1
kind: Node
metadata:
  name: {name}
  labels:
    kubernetes.io/hostname: {name}
    topology.kubernetes.io/zone: {zone}
status:
  allocatable:
    cpu: "4"
    memory: 8Gi
    pods: "110"
  capacity:
    cpu: "4"
    memory: 8Gi
    pods: "110"
"""

DEPLOY_TMPL = """apiVersion: apps/v1
kind: Deployment
metadata:
  name: {name}
spec:
  replicas: {replicas}
  selector:
    matchLabels:
      app: {name}
  template:
    metadata:
      labels:
        app: {name}
    spec:
      containers:
      - name: c
        resources:
          requests:
            cpu: {cpu}
            memory: {memory}
"""

CONFIG_TMPL = """apiVersion: simon/v1alpha1
kind: Config
metadata:
  name: explain-smoke
spec:
  cluster:
    customConfig: cluster
  appList:
  - name: smoke
    path: apps
"""


def write_config(root: str) -> str:
    nodes_dir = os.path.join(root, "cluster", "nodes")
    apps_dir = os.path.join(root, "apps")
    os.makedirs(nodes_dir)
    os.makedirs(apps_dir)
    for i in range(6):
        with open(os.path.join(nodes_dir, f"n{i:02d}.yaml"), "w") as f:
            f.write(NODE_TMPL.format(name=f"n{i:02d}", zone=f"z{i % 2}"))
    with open(os.path.join(apps_dir, "web.yaml"), "w") as f:
        f.write(DEPLOY_TMPL.format(name="web", replicas=4, cpu="500m", memory="1Gi"))
    with open(os.path.join(apps_dir, "nofit.yaml"), "w") as f:
        f.write(DEPLOY_TMPL.format(name="nofit", replicas=2, cpu="64", memory="1Gi"))
    cfg = os.path.join(root, "config.yaml")
    with open(cfg, "w") as f:
        f.write(CONFIG_TMPL)
    return cfg


_BACKEND_ENV = ("OPENSIM_DISABLE_FASTPATH",)


def run_cli(argv) -> str:
    """One in-process ``simon`` invocation with captured stdout; backend
    env selections are reset afterwards so runs stay independent."""
    from opensim_tpu.cli.main import main

    saved = {k: os.environ.get(k) for k in _BACKEND_ENV}
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = main(argv)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    if rc != 0:
        raise RuntimeError(f"simon {' '.join(argv)} exited {rc}:\n{buf.getvalue()}")
    return buf.getvalue()


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="explain-smoke-") as root:
        cfg = write_config(root)

        # 1+2: summary audit
        out = json.loads(run_cli(["explain", "-f", cfg, "--json", "--backend", "xla"]))
        if not out["unschedulable"]:
            return fail("the infeasible workload was reported schedulable")
        msg = out["unschedulable"][0]["message"]
        if not re.match(r"^0/6 nodes are available: .*Insufficient cpu", msg):
            return fail(f"not a kube-style breakdown: {msg!r}")
        if out["filter_rejects"].get("fit", 0) < 1:
            return fail(f"no fit rejects recorded: {out['filter_rejects']}")

        # 3: deep audit of one scheduled pod by workload name
        deep = json.loads(run_cli(["explain", "-f", cfg, "--json", "--backend", "xla", "web"]))
        if deep["status"] != "scheduled" or not deep.get("scores"):
            return fail(f"deep audit lacks a score breakdown: {deep}")
        if abs(sum(deep["scores"].values()) - deep["score"]) > 0.01:
            return fail(
                f"score parts {deep['scores']} do not sum to total {deep['score']}"
            )
        # and of the unschedulable workload
        deep_bad = json.loads(
            run_cli(["explain", "-f", cfg, "--json", "--backend", "xla", "nofit"])
        )
        if deep_bad["status"] != "unschedulable" or not deep_bad.get("reasons"):
            return fail(f"deep audit of the infeasible pod is wrong: {deep_bad}")

    print(
        "explain-smoke: ok — kube-style breakdown "
        f"({msg!r}), rejects {out['filter_rejects']}, deep audit "
        f"scored {deep['node']} at {deep['score']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
