"""Inputs of `cl2-load-*`: the cluster of SIG-scalability's *load* test
(kubernetes/perf-tests, `clusterloader2/testing/load/config.yaml` with its
`deployment.yaml`, `statefulset.yaml`, `job.yaml` and `daemonset.yaml`), as a
cluster directory, one app directory a namespace and a simon Config, made from
the seed.

The source's own arithmetic, in integers: `nodes // nodes_per_namespace`
namespaces; in each, `pods_ns = nodes_per_namespace * pods_per_node` pods, a
quarter of them in big groups, a quarter in medium ones and half in small ones
(`pods_ns // (4 * big)`, `pods_ns // (4 * medium)`, `pods_ns // (2 * small)`
groups); of a namespace's groups one small and one medium are StatefulSets,
one small, one medium and one big are Jobs, and the rest are Deployments; one
DaemonSet runs on every node. Object names (`small-deployment-0`, ...) and pod
labels (`group: load`, `name: <object>`) repeat from namespace to namespace.

The DaemonSet lies in the cluster directory beside the nodes; the namespaces
are the entries of `appList`, `namespaces_per_app` of them to a directory, in
order. Within a directory the program schedules Deployments, then
StatefulSets, then Jobs, and `Cluster.workloads` lists them so. The
configuration's `assumed` says what of this the source does not fix.

The seed draws which position of the node list (the tie-break index) carries
which name, and a system reservation per node below the source's capacity, as
`schedperf_mixed` does. Counts, names, labels, requests and the order of the
documents are the same for every seed.

The same description is returned as plain data (`Cluster` of `KindWorkload`)
for the reference, which never sees the files.
"""

from __future__ import annotations

import os
import random
from typing import Dict, List, Tuple

from benchmarks.generators.k8s_cluster import HOSTNAME, MI, ZONE, write_docs
from benchmarks.reference.kube_daemonset_reference import KindWorkload
from benchmarks.reference.kube_reference import Cluster, NodeSpec

#: (size class, kind, how many of the class's groups in a namespace are of that kind)
FIXED = [("small", "StatefulSet", 1), ("medium", "StatefulSet", 1),
         ("small", "Job", 1), ("medium", "Job", 1), ("big", "Job", 1)]
KINDS = {"Deployment": ("apps/v1", "deployment"), "StatefulSet": ("apps/v1", "statefulset"),
         "Job": ("batch/v1", "job"), "DaemonSet": ("apps/v1", "daemonset")}
#: the order the program expands an app's documents in (`models/expand.py`)
EXPANSION_ORDER = ("Deployment", "StatefulSet", "Job")


def groups_of_a_namespace(sizes: dict) -> List[Tuple[str, str, int]]:
    """(kind, object name, replicas) of one namespace's workloads by the
    source's integer formulas, in the source's order of creation."""
    pods_ns = sizes["nodes_per_namespace"] * sizes["pods_per_node"]
    group_size = {"big": sizes["big_group_size"], "medium": sizes["medium_group_size"],
                  "small": sizes["small_group_size"]}
    count = {"big": pods_ns // (4 * group_size["big"]), "medium": pods_ns // (4 * group_size["medium"]),
             "small": pods_ns // (2 * group_size["small"])}
    out = []
    for size in ("big", "medium", "small"):
        left, by_kind = count[size], {}
        for kind in ("Job", "StatefulSet"):  # the fixed ones come out of the class's count, Deployments are the rest
            by_kind[kind] = min(left, sum(n for s, k, n in FIXED if (s, k) == (size, kind)))
            left -= by_kind[kind]
        by_kind["Deployment"] = left
        for kind in ("Deployment", "StatefulSet", "Job"):
            out += [(kind, f"{size}-{KINDS[kind][1]}-{i}", group_size[size]) for i in range(by_kind[kind])]
    return out


def workload_doc(kind: str, name: str, namespace: str, replicas: int, cpu_m: int, mem: str) -> dict:
    labels = {"group": "load", "name": name}
    template = {
        "metadata": {"labels": dict(labels)},
        "spec": {"containers": [{
            "name": name, "image": "k8s.gcr.io/pause:3.1",
            "resources": {"requests": {"cpu": f"{cpu_m}m", "memory": mem}},
        }]},
    }
    spec: dict = {"template": template}
    if kind == "Job":
        spec.update(parallelism=replicas, completions=replicas)
        template["spec"]["restartPolicy"] = "Never"
    else:
        spec["selector"] = {"matchLabels": {"name": name}}
        if kind != "DaemonSet":
            spec["replicas"] = replicas
        if kind == "StatefulSet":
            spec.update(serviceName=name, podManagementPolicy="Parallel")
    return {"apiVersion": KINDS[kind][0], "kind": kind,
            "metadata": {"name": name, "namespace": namespace, "labels": {"group": "load"}}, "spec": spec}


def node_doc(name: str, zone: str, capacity: Dict[str, str], allocatable: Dict[str, str]) -> dict:
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": name, "labels": {HOSTNAME: name, ZONE: zone}},
            "status": {"allocatable": allocatable, "capacity": capacity}}


def generate(sizes: dict, seed: int, out: str) -> dict:
    """Write the inputs under `out`; return the simon Config path of the one
    variant (`fit`) and its `Cluster`."""
    rng = random.Random(seed)
    n = sizes["nodes"]
    order = list(range(n))
    rng.shuffle(order)  # position in the file (the tie-break index) -> logical node
    cpu_m, mem_mi, cap = sizes["node_cpu_m"], sizes["node_memory_mi"], sizes["node_pods"]
    lo_c, hi_c, step_c = sizes["reserved_cpu_m"]
    lo_m, hi_m, step_m = sizes["reserved_memory_mi"]
    reserved = [(rng.randrange(lo_c, hi_c + 1, step_c), rng.randrange(lo_m, hi_m + 1, step_m)) for _ in range(n)]
    capacity = {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi", "pods": str(cap)}
    zone = sizes["zone"]
    node_docs, specs = [], []
    for j in order:
        name = f"{sizes['node_prefix']}-{j:05d}"
        res_c, res_m = reserved[j]
        node_docs.append(node_doc(name, zone, capacity, {
            "cpu": f"{cpu_m - res_c}m", "memory": f"{mem_mi - res_m}Mi", "pods": str(cap)}))
        specs.append(NodeSpec(name=name, cpu_m=cpu_m - res_c, mem_bytes=(mem_mi - res_m) * MI, pods=cap,
                              labels={HOSTNAME: name, ZONE: zone}))

    root = os.path.join(out, "plan")
    pod_c, pod_mem = sizes["pod_cpu_m"], sizes["pod_memory_bytes"]
    ds_ns, ds_name = sizes["daemonset_namespace"], "daemonset-0"
    write_docs(os.path.join(root, "cluster", "nodes.yaml"), node_docs)
    write_docs(os.path.join(root, "cluster", "daemonset.yaml"), [
        workload_doc("DaemonSet", ds_name, ds_ns, 0, sizes["daemonset_cpu_m"], str(sizes["daemonset_memory_bytes"]))])
    # the cluster's DaemonSet pods are scheduled before the apps: one a node, in the order of the node list
    workloads = [KindWorkload(
        name=f"{ds_ns}/{ds_name}", replicas=n, cpu_m=sizes["daemonset_cpu_m"],
        mem_bytes=sizes["daemonset_memory_bytes"], labels={"group": "load", "name": ds_name},
        namespace=ds_ns, kind="DaemonSet")]

    groups = groups_of_a_namespace(sizes)
    namespaces = [f"{sizes['namespace_prefix']}-{i + 1}" for i in range(n // sizes["nodes_per_namespace"])]
    per_app = sizes["namespaces_per_app"]
    apps = []
    for first in range(0, len(namespaces), per_app):
        app = f"app-{first // per_app + 1}"
        apps.append(app)
        # one file an app, in the source's order of creation within each namespace
        created = [(ns, kind, name, replicas) for ns in namespaces[first:first + per_app]
                   for kind, name, replicas in groups]
        write_docs(os.path.join(root, app, "workloads.yaml"),
                   [workload_doc(kind, name, ns, replicas, pod_c, str(pod_mem)) for ns, kind, name, replicas in created])
        for ns, kind, name, replicas in sorted(created, key=lambda c: EXPANSION_ORDER.index(c[1])):
            workloads.append(KindWorkload(
                name=f"{ns}/{name}", replicas=replicas, cpu_m=pod_c, mem_bytes=pod_mem,
                labels={"group": "load", "name": name}, namespace=ns, kind=kind))
    path = os.path.join(root, "simon-fit.yaml")
    with open(path, "w") as f:
        f.write("apiVersion: simon/v1alpha1\nkind: Config\nmetadata:\n  name: benchmark\n"
                "spec:\n  cluster:\n    customConfig: cluster\n  appList:\n"
                + "".join(f"  - name: {app}\n    path: {app}\n" for app in apps))
    cluster = Cluster(nodes=specs, bound=[], workloads=workloads, new_node=None)
    return {"variants": {"fit": {"simon_config": path, "cluster": cluster}},
            "max_new_nodes": sizes["max_new_nodes"]}
