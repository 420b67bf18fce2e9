"""Inputs of the `twin-*` configurations: the objects a stub apiserver holds
(nodes and bound pods) and the cycle of deploy requests, made from the seed.

The shape is `server/loadgen.py`'s `_seed_stub` (copied here): N nodes of one
size carrying only their hostname label, and P bound, unlabelled pods of one
request each. The seed permutes which position of the node list carries which
name and how many bound pods (8 to 12, a fifth of the nodes each, ten on
average), draws the memory request of each node's bound pods, the order of
the request sizes and each request's CPU and memory; node and pod counts and the set of request sizes are the same for
every seed.
"""

from __future__ import annotations

import json
import random
from typing import List

from benchmarks.reference.kube_reference import Cluster, NodeSpec

HOSTNAME = "kubernetes.io/hostname"
MI = 1024 * 1024
GI = 1024 * MI


def node_doc(name: str, cpu: int, mem_gi: int, pods: int) -> dict:
    alloc = {"cpu": str(cpu), "memory": f"{mem_gi}Gi", "pods": str(pods)}
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name, "labels": {HOSTNAME: name}},
        "status": {"allocatable": dict(alloc), "capacity": dict(alloc)},
    }


def pod_doc(i: int, node: str, cpu_m: int, mem_mi: int) -> dict:
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": f"seed-{i}", "namespace": "default"},
        "spec": {
            "nodeName": node,
            "containers": [
                {"name": "c", "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}}}
            ],
        },
        "status": {"phase": "Running"},
    }


def deploy_payload(name: str, replicas: int, cpu_m: int, mem_mi: int) -> bytes:
    """`server/loadgen.py`'s `_payload`: one Deployment, named per request so
    that no two payloads of a run are the same."""
    return json.dumps(
        {
            "deployments": [
                {
                    "apiVersion": "apps/v1",
                    "kind": "Deployment",
                    "metadata": {"name": name, "namespace": "default"},
                    "spec": {
                        "replicas": replicas,
                        "selector": {"matchLabels": {"app": name}},
                        "template": {
                            "metadata": {"labels": {"app": name}},
                            "spec": {
                                "containers": [
                                    {"name": "c", "resources": {
                                        "requests": {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}}}
                                ]
                            },
                        },
                    },
                }
            ]
        }
    ).encode()


def generate(sizes: dict, seed: int, out: str) -> dict:
    rng = random.Random(seed)
    n = sizes["nodes"]
    order = list(range(n))
    rng.shuffle(order)  # position in the apiserver's list -> logical node
    cpu, mem_gi, cap = sizes["node_cpu"], sizes["node_memory_gi"], sizes["node_pods"]
    lo, hi = sizes["bound_pods_per_node"]
    spread = hi - lo + 1
    pod_cpu = sizes["bound_pod_cpu_m"]
    lo_m, hi_m, step_m = sizes["bound_pod_memory_mi"]
    node_mem = [rng.randrange(lo_m, hi_m + 1, step_m) for _ in range(n)]  # by logical node
    width = len(str(n - 1))
    node_docs: List[dict] = []
    pod_docs: List[dict] = []
    specs: List[NodeSpec] = []
    bound = []
    for j in order:
        name = f"n{j:0{width}d}"
        node_docs.append(node_doc(name, cpu, mem_gi, cap))
        specs.append(NodeSpec(name, cpu * 1000, mem_gi * GI, cap, {HOSTNAME: name}))
        k = lo + j % spread
        bound.append((name, k, pod_cpu, node_mem[j] * MI))
        for _ in range(k):
            pod_docs.append(pod_doc(len(pod_docs), name, pod_cpu, node_mem[j]))
    if len(pod_docs) != sizes["pods"]:
        raise ValueError(f"bound pods: {len(pod_docs)} made, {sizes['pods']} stated")
    return {"node_docs": node_docs, "pod_docs": pod_docs,
            "cluster": Cluster(nodes=specs, bound=bound, workloads=[])}
