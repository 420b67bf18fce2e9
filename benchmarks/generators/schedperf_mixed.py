"""Inputs of `schedperf-mixed-*`: kube-scheduler's `scheduler_perf` case
`MixedSchedulingBasePod`, as a cluster directory, two app directories and a
simon Config, made from the seed.

The source (`performance-config.yaml`, release 1.20) creates `nodes` nodes
from `node-default.yaml`, all in one zone, then `init_pods` bare pods of each
of five templates in this order (`pod-default`; required affinity to
`color: blue` over the zone; required anti-affinity to `color: green` over
the hostname; preferred affinity to `color: red` and preferred anti-affinity
to `color: yellow` over the hostname, weight 1), then `measure_pods` more of
`pod-default`. Here each template is one Deployment of identical replicas
(an answer is a count per (workload, node)) and the two phases are two apps,
`init` then `measure`. The configuration's `assumed` says what of this the
source does not fix.

The seed draws which position of the node list (the tie-break index) carries
which name, and a system reservation per node that lowers its allocatable CPU
and memory below the source's capacity, as kube-reserved does: with 5,000
identical nodes and one pod size the answer would be round robin by index and
no precision could be told from another. Counts, sizes and the order of the
stream are the same for every seed.

The same description is returned as plain data (`Cluster` of `PodWorkload`)
for the reference, which never sees the files.
"""

from __future__ import annotations

import os
import random
from typing import Dict, List, Optional, Tuple

from benchmarks.generators.k8s_cluster import HOSTNAME, MI, ZONE, write_docs
from benchmarks.reference.kube_interpod_reference import PodWorkload, Term, term
from benchmarks.reference.kube_reference import Cluster, NodeSpec

#: (Deployment name, the pods' colour, which kind of term, over which key)
TEMPLATES: List[Tuple[str, Optional[str], str, str]] = [
    ("pod-default", None, "", ""),
    ("pod-with-pod-affinity", "blue", "affinity", ZONE),
    ("pod-with-pod-anti-affinity", "green", "anti_affinity", HOSTNAME),
    ("pod-with-preferred-pod-affinity", "red", "preferred_affinity", HOSTNAME),
    ("pod-with-preferred-pod-anti-affinity", "yellow", "preferred_anti_affinity", HOSTNAME),
]
AFFINITY_FIELD = {
    "affinity": ("podAffinity", "requiredDuringSchedulingIgnoredDuringExecution"),
    "anti_affinity": ("podAntiAffinity", "requiredDuringSchedulingIgnoredDuringExecution"),
    "preferred_affinity": ("podAffinity", "preferredDuringSchedulingIgnoredDuringExecution"),
    "preferred_anti_affinity": ("podAntiAffinity", "preferredDuringSchedulingIgnoredDuringExecution"),
}


def node_doc(name: str, zone: str, capacity: Dict[str, str], allocatable: Dict[str, str]) -> dict:
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name, "labels": {HOSTNAME: name, ZONE: zone}},
        "status": {"allocatable": allocatable, "capacity": capacity},
    }


def deployment_doc(name: str, namespace: str, replicas: int, labels: Dict[str, str],
                   cpu_m: int, mem_mi: int, kind: str, t: Optional[Term]) -> dict:
    spec: dict = {"containers": [{
        "name": "pause", "image": "k8s.gcr.io/pause:3.2", "ports": [{"containerPort": 80}],
        "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"},
                      "limits": {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}},
    }]}
    if t is not None:
        pod_term = {"labelSelector": {"matchLabels": dict(t.match_labels)},
                    "topologyKey": t.topology_key, "namespaces": list(t.namespaces)}
        group, field = AFFINITY_FIELD[kind]
        entry = {"weight": t.weight, "podAffinityTerm": pod_term} if kind.startswith("preferred") else pod_term
        spec["affinity"] = {group: {field: [entry]}}
    return {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {"name": name, "namespace": namespace, "labels": dict(labels)},
        "spec": {"replicas": replicas, "selector": {"matchLabels": dict(labels)},
                 "template": {"metadata": {"labels": dict(labels)}, "spec": spec}},
    }


def generate(sizes: dict, seed: int, out: str) -> dict:
    """Write the inputs under `out`; return the simon Config path of the one
    variant (`fit`) and its `Cluster`."""
    rng = random.Random(seed)
    n = sizes["nodes"]
    order = list(range(n))
    rng.shuffle(order)  # position in the file (the tie-break index) -> logical node
    cpu_m, mem_mi, cap = sizes["node_cpu"] * 1000, sizes["node_memory_gi"] * 1024, sizes["node_pods"]
    lo_c, hi_c, step_c = sizes["reserved_cpu_m"]
    lo_m, hi_m, step_m = sizes["reserved_memory_mi"]
    reserved = [(rng.randrange(lo_c, hi_c + 1, step_c), rng.randrange(lo_m, hi_m + 1, step_m)) for _ in range(n)]
    capacity = {"cpu": str(sizes["node_cpu"]), "memory": f"{sizes['node_memory_gi']}Gi", "pods": str(cap)}
    zone = sizes["zone"]
    docs, specs = [], []
    for j in order:
        name = f"scheduler-perf-{j:05d}"
        res_c, res_m = reserved[j]
        docs.append(node_doc(name, zone, capacity, {
            "cpu": f"{cpu_m - res_c}m", "memory": f"{mem_mi - res_m}Mi", "pods": str(cap)}))
        specs.append(NodeSpec(name=name, cpu_m=cpu_m - res_c, mem_bytes=(mem_mi - res_m) * MI, pods=cap,
                              labels={HOSTNAME: name, ZONE: zone}))

    init_ns, measure_ns = sizes["init_namespace"], sizes["measure_namespace"]
    pod_c, pod_m = sizes["pod_cpu_m"], sizes["pod_memory_mi"]
    apps: Dict[str, List[dict]] = {"init": [], "measure": []}
    workloads: List[PodWorkload] = []
    for app, namespace, replicas, templates in (
        ("init", init_ns, sizes["init_pods"], TEMPLATES),
        ("measure", measure_ns, sizes["measure_pods"], TEMPLATES[:1]),
    ):
        for name, color, kind, key in templates:
            # a Deployment needs a selector, so the colourless template is
            # labelled with its own name: a label no term selects
            labels = {"color": color} if color else {"name": name}
            t = term({"color": color}, key, sizes["term_namespaces"],
                     weight=1 if kind.startswith("preferred") else 0) if color else None
            apps[app].append(deployment_doc(name, namespace, replicas, labels, pod_c, pod_m, kind, t))
            workloads.append(PodWorkload(
                name=f"{namespace}/{name}", replicas=replicas, cpu_m=pod_c, mem_bytes=pod_m * MI,
                labels=labels, namespace=namespace, **({kind: [t]} if t else {})))

    root = os.path.join(out, "plan")
    write_docs(os.path.join(root, "cluster", "nodes.yaml"), docs)
    for app, deployments in apps.items():
        write_docs(os.path.join(root, f"apps-{app}", "deployments.yaml"), deployments)
    path = os.path.join(root, "simon-fit.yaml")
    with open(path, "w") as f:
        f.write(
            "apiVersion: simon/v1alpha1\nkind: Config\nmetadata:\n  name: benchmark\n"
            "spec:\n  cluster:\n    customConfig: cluster\n  appList:\n"
            "  - name: init\n    path: apps-init\n  - name: measure\n    path: apps-measure\n"
        )
    cluster = Cluster(nodes=specs, bound=[], workloads=workloads, new_node=None)
    return {"variants": {"fit": {"simon_config": path, "cluster": cluster}},
            "max_new_nodes": sizes["max_new_nodes"]}
