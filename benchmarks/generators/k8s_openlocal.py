"""Inputs of `k8s-5k-50k-openlocal`: `k8s_cluster`'s cluster, apps and newNode
template with open-local's storage on the nodes and in eight of the apps.

What changes, and nothing else (the sizes are the configuration's):

  - Every node carries the `simon/node-local-storage` annotation that
    open-local's `NodeLocalStorage` reports: a `disk=ssd` node one VG
    `open-local-pool-0` of `vg_gi` (built from two NVMe disks, which are not
    listed again) and `ssd_devices` exclusive NVMe devices of `ssd_device_gi`;
    a `disk=hdd` node `hdd_devices` exclusive HDDs of `hdd_device_gi` and no
    VG. The newNode template is an ssd node of `new_cap` pods.
  - Every Deployment pinned to `disk=ssd` (w % 4 == 0) becomes a StatefulSet
    of databases: each pod a `data` claim of `open-local-lvm` drawn for the
    workload from `db_data_gi` and a `wal` claim of `db_wal_gi`. The workloads
    of `device_claims` become StatefulSets whose pods claim whole devices,
    `open-local-device-ssd` or `-hdd`. The rest stay stateless Deployments.
  - No ssd pod cap: the LVM pools decide how many nodes the short cluster
    lacks.

The seed draws what `k8s_cluster` draws, unchanged, and then each
database's data claim. Capacities and claims are whole GiB.

The same description is returned as plain data (`LocalCluster`) for the
reference, which never sees the files. Its workloads are in the order the
simulator expands an app directory: the Deployments, then the StatefulSets.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, List, Tuple

from benchmarks.generators import k8s_cluster
from benchmarks.reference.kube_openlocal_reference import LocalCluster, LocalNodeSpec, LocalWorkload

ANNO_NODE_LOCAL_STORAGE = "simon/node-local-storage"
GI = k8s_cluster.GI
VG_NAME = "open-local-pool-0"
STORAGE_CLASS = {"lvm": "open-local-lvm", "ssd": "open-local-device-ssd", "hdd": "open-local-device-hdd"}


def node_storage(ssd: bool, sizes: dict) -> Tuple[Tuple[Tuple[str, int], ...], Tuple[Tuple[str, int, str], ...]]:
    """(VGs, devices) of a node of one disk class, in bytes."""
    if ssd:
        vgs = ((VG_NAME, sizes["vg_gi"] * GI),)
        devices = tuple((f"/dev/nvme{2 + i}n1", sizes["ssd_device_gi"] * GI, "ssd") for i in range(sizes["ssd_devices"]))
    else:
        vgs = ()
        devices = tuple((f"/dev/sd{chr(ord('b') + i)}", sizes["hdd_device_gi"] * GI, "hdd")
                        for i in range(sizes["hdd_devices"]))
    return vgs, devices


def storage_annotation(vgs, devices) -> str:
    return json.dumps({
        "vgs": [{"name": name, "capacity": str(size)} for name, size in vgs],
        "devices": [{"name": name, "device": name, "capacity": str(size), "mediaType": media}
                    for name, size, media in devices],
    })


def claims_of(w: int, sizes: dict, rng: random.Random) -> List[Tuple[str, str, int]]:
    """(claim name, storage class kind, GiB) of each volumeClaimTemplate of
    workload `w`; none for a stateless one."""
    if w % 4 == 0:
        return [("data", "lvm", rng.choice(sizes["db_data_gi"])), ("wal", "lvm", sizes["db_wal_gi"])]
    return [(f"disk-{i}", media, gi) for i, (media, gi) in enumerate(sizes["device_claims"].get(str(w), []))]


def statefulset_doc(deployment: dict, claims: List[Tuple[str, str, int]]) -> dict:
    """The Deployment as a StatefulSet with the claims as volumeClaimTemplates."""
    doc = json.loads(json.dumps(deployment))
    doc["kind"] = "StatefulSet"
    doc["spec"]["serviceName"] = doc["metadata"]["name"]
    doc["spec"]["volumeClaimTemplates"] = [
        {"metadata": {"name": name},
         "spec": {"accessModes": ["ReadWriteOnce"], "storageClassName": STORAGE_CLASS[kind],
                  "resources": {"requests": {"storage": f"{gi}Gi"}}}}
        for name, kind, gi in claims
    ]
    return doc


def read_docs(path: str) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip() and line.strip() != "---"]


def generate(sizes: dict, seed: int, out: str) -> dict:
    """`k8s_cluster.generate` with no ssd pod cap, then the storage above on
    the files it wrote and on each variant's `Cluster`."""
    made = k8s_cluster.generate(dict(sizes, ssd_cap=0), seed, out)
    rng = random.Random(f"k8s-openlocal-{seed}")
    root = os.path.join(out, "plan")

    for path in (os.path.join(root, d, f) for d, f in
                 (("cluster-fit", "nodes.yaml"), ("cluster-short", "nodes.yaml"), ("newnode", "node.yaml"))):
        docs = read_docs(path)
        for doc in docs:
            anno = storage_annotation(*node_storage(doc["metadata"]["labels"]["disk"] == "ssd", sizes))
            doc["metadata"]["annotations"] = {ANNO_NODE_LOCAL_STORAGE: anno}
        k8s_cluster.write_docs(path, docs)

    apps_path = os.path.join(root, "apps", "deployments.yaml")
    claims: Dict[str, List[Tuple[str, str, int]]] = {}
    docs = []
    for w, doc in enumerate(read_docs(apps_path)):
        own = claims_of(w, sizes, rng)
        if own:
            claims[f"default/{doc['metadata']['name']}"] = own
            doc = statefulset_doc(doc, own)
        docs.append(doc)
    k8s_cluster.write_docs(apps_path, docs)

    def local_node(spec) -> LocalNodeSpec:
        vgs, devices = node_storage(spec.labels["disk"] == "ssd", sizes)
        return LocalNodeSpec(spec.name, spec.cpu_m, spec.mem_bytes, spec.pods, spec.labels, vgs=vgs, devices=devices)

    def local_workload(w) -> LocalWorkload:
        own = claims.get(w.name, [])
        return LocalWorkload(
            name=w.name, replicas=w.replicas, cpu_m=w.cpu_m, mem_bytes=w.mem_bytes, labels=w.labels,
            node_selector=w.node_selector, spread=w.spread, kind="StatefulSet" if own else "Deployment",
            lvm=tuple(gi * GI for _n, kind, gi in own if kind == "lvm"),
            devices=tuple((gi * GI, kind) for _n, kind, gi in own if kind != "lvm"),
        )

    for variant in made["variants"].values():
        c = variant["cluster"]
        workloads = [local_workload(w) for w in c.workloads]
        # the simulator expands an app directory's Deployments before its StatefulSets
        workloads = ([w for w in workloads if w.kind == "Deployment"]
                     + [w for w in workloads if w.kind == "StatefulSet"])
        variant["cluster"] = LocalCluster(
            nodes=[local_node(nd) for nd in c.nodes], bound=c.bound, workloads=workloads,
            new_node=local_node(c.new_node) if c.new_node is not None else None,
        )
    return made
