"""Inputs of the `k8s-*` configurations: a cluster directory, an app directory,
a newNode template and a simon Config, all made from the seed.

The shape is the repo's headline one (`chip_smoke.py`'s `node_doc`,
`deployment_doc` and `write_plan_inputs`, copied here): N nodes of one size in
`zones` zones with two thirds labelled `disk=ssd`, and P pods in W Deployments,
every 4th pinned to ssd by node selector and every 5th with an explicit soft
zone spread. The seed permutes which position of the node list carries which
name, zone and disk label, and draws each Deployment's CPU and memory request
from the shape's ranges; node, pod and replica counts and the order of the
Deployments are the same for every seed.

The same description is returned as plain data (`Cluster`) for the reference,
which never sees the files.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, List

from benchmarks.reference.kube_reference import Cluster, NodeSpec, Workload

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
MI = 1024 * 1024
GI = 1024 * MI


def node_doc(name: str, zone: int, ssd: bool, cpu: int, mem_gi: int, pods_cap: int) -> dict:
    alloc = {"cpu": str(cpu), "memory": f"{mem_gi}Gi", "pods": str(pods_cap)}
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {
            "name": name,
            "labels": {
                HOSTNAME: name,
                ZONE: f"zone-{zone}",
                "node-role.kubernetes.io/worker": "",
                "disk": "ssd" if ssd else "hdd",
            },
        },
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def deployment_doc(w: int, replicas: int, cpu_m: int, mem_mi: int, skew: int) -> dict:
    name = f"bench-{w}"
    spec: dict = {
        "containers": [
            {
                "name": "nginx",
                "image": "nginx:latest",
                "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}},
            }
        ]
    }
    if w % 4 == 0:
        spec["nodeSelector"] = {"disk": "ssd"}
    if w % 5 == 0:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": skew,
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


def write_docs(path: str, docs: List[dict]) -> None:
    # JSON is YAML: one document per line keeps 5,000 nodes a sub-second write
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for d in docs:
            f.write("---\n" + json.dumps(d) + "\n")


def generate(sizes: dict, seed: int, out: str) -> dict:
    """Write the inputs under `out`; return what the drivers and the
    reference need: the simon Config path of each variant and its `Cluster`."""
    rng = random.Random(seed)
    n, zones = sizes["nodes"], sizes["zones"]
    order = list(range(n))
    rng.shuffle(order)  # position in the file (the tie-break index) -> logical node
    cpu, mem_gi, cap = sizes["node_cpu"], sizes["node_memory_gi"], sizes["node_pods"]
    lo_c, hi_c, step_c = sizes["pod_cpu_m"]
    lo_m, hi_m, step_m = sizes["pod_memory_mi"]
    n_w = sizes["workloads"]
    replicas = sizes["pods"] // n_w
    requests = [
        (rng.randrange(lo_c, hi_c + 1, step_c), rng.randrange(lo_m, hi_m + 1, step_m))
        for _ in range(n_w)
    ]

    def is_ssd(j: int) -> bool:
        return j % 3 != 0

    apps = [deployment_doc(w, replicas, c, m, sizes["zone_max_skew"]) for w, (c, m) in enumerate(requests)]
    workloads = []
    for w, (c, m) in enumerate(requests):
        name = f"bench-{w}"
        workloads.append(
            Workload(
                name=f"default/{name}", replicas=replicas, cpu_m=c, mem_bytes=m * MI,
                labels={"app": name},
                node_selector={"disk": "ssd"} if w % 4 == 0 else {},
                spread=[(ZONE, sizes["zone_max_skew"], {"app": name})] if w % 5 == 0 else None,
            )
        )

    def nodes_of(limit: int, ssd_cap: int):
        docs, specs = [], []
        for j in order:
            if j >= limit:
                continue
            name = f"node-{j:05d}"
            pods_cap = ssd_cap if (ssd_cap and is_ssd(j)) else cap
            docs.append(node_doc(name, j % zones, is_ssd(j), cpu, mem_gi, pods_cap))
            specs.append(
                NodeSpec(
                    name=name, cpu_m=cpu * 1000, mem_bytes=mem_gi * GI, pods=pods_cap,
                    labels={HOSTNAME: name, ZONE: f"zone-{j % zones}",
                            "disk": "ssd" if is_ssd(j) else "hdd"},
                )
            )
        return docs, specs

    fit_docs, fit_specs = nodes_of(n, 0)
    # the short cluster: the ssd pool is capped at ssd_cap pods a node, so the
    # ssd-only Deployments (scheduled first: pods with a node selector lead the
    # queue) run out of room while later ones still bind on the hdd pool
    short_docs, short_specs = nodes_of(sizes["short_nodes"], sizes["ssd_cap"])
    new_doc = node_doc("new-ssd", 1, True, cpu, mem_gi, sizes["new_cap"])
    new_spec = NodeSpec(
        name="new-ssd", cpu_m=cpu * 1000, mem_bytes=mem_gi * GI, pods=sizes["new_cap"],
        labels={HOSTNAME: "new-ssd", ZONE: "zone-1", "disk": "ssd"},
    )

    root = os.path.join(out, "plan")
    write_docs(os.path.join(root, "cluster-fit", "nodes.yaml"), fit_docs)
    write_docs(os.path.join(root, "cluster-short", "nodes.yaml"), short_docs)
    write_docs(os.path.join(root, "newnode", "node.yaml"), [new_doc])
    write_docs(os.path.join(root, "apps", "deployments.yaml"), apps)
    variants: Dict[str, dict] = {}
    for name, cluster_dir, new, specs in (
        ("fit", "cluster-fit", "", fit_specs),
        ("short", "cluster-short", "newnode", short_specs),
    ):
        path = os.path.join(root, f"simon-{name}.yaml")
        with open(path, "w") as f:
            f.write(
                "apiVersion: simon/v1alpha1\nkind: Config\nmetadata:\n  name: benchmark\n"
                f"spec:\n  cluster:\n    customConfig: {cluster_dir}\n"
                "  appList:\n  - name: bench\n    path: apps\n"
                + (f"  newNode: {new}\n" if new else "")
            )
        variants[name] = {
            "simon_config": path,
            "cluster": Cluster(nodes=specs, bound=[], workloads=workloads,
                               new_node=new_spec if new else None),
        }
    return {"variants": variants, "max_new_nodes": sizes["max_new_nodes"]}
