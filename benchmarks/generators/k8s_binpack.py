"""Inputs of `k8s-5k-50k-binpack`: `k8s_cluster`'s cluster, apps and newNode
template, unchanged, and beside them `scheduler-config.yaml`, the bin-packing
profile of the configuration's `profile` as a v1beta1
KubeSchedulerConfiguration with one `default-scheduler` profile, the file
`simon apply --default-scheduler-config` reads.

The same profile is returned as plain data (`Profile`) for the reference, which
never sees the file."""

from __future__ import annotations

import json
import os

from benchmarks.generators import k8s_cluster
from benchmarks.reference.kube_binpack_reference import ProfiledCluster, Profile

#: the default profile's weights of the plugins the reference scores (kube
#: 1.21's registry, and the simulator's share plugins at 1 each)
DEFAULT_WEIGHTS = {"NodeResourcesBalancedAllocation": 1, "NodeResourcesLeastAllocated": 1,
                   "PodTopologySpread": 2, "RequestedToCapacityRatio": 0}


def profile_of(spec: dict) -> Profile:
    """The configuration's `profile` as the reference reads it: each plugin's
    weight after the file's disables and enables, and RequestedToCapacityRatio's
    arguments."""
    weights = dict(DEFAULT_WEIGHTS)
    for name in spec["score"].get("disabled", []):
        weights[name] = 0
    for entry in spec["score"].get("enabled", []):
        weights[entry["name"]] = entry.get("weight", 1)
    args = spec["pluginConfig"]["RequestedToCapacityRatio"]
    return Profile(
        balanced=float(weights["NodeResourcesBalancedAllocation"]),
        least=float(weights["NodeResourcesLeastAllocated"]),
        rtcr=float(weights["RequestedToCapacityRatio"]),
        spread=float(weights["PodTopologySpread"]),
        shape=tuple((p["utilization"], p["score"]) for p in args["shape"]),
        resources=tuple((r["name"], r["weight"]) for r in args["resources"]),
    )


def scheduler_config(spec: dict) -> dict:
    score = {"disabled": [{"name": n} for n in spec["score"].get("disabled", [])],
             "enabled": [dict(e) for e in spec["score"].get("enabled", [])]}
    return {
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{
            "schedulerName": "default-scheduler",
            "plugins": {"score": score},
            "pluginConfig": [{"name": name, "args": args} for name, args in spec["pluginConfig"].items()],
        }],
    }


def generate(sizes: dict, seed: int, out: str, spec: dict) -> dict:
    """`k8s_cluster.generate`, each variant's cluster carrying the profile,
    and the path of the scheduler-config file under `scheduler_config`."""
    made = k8s_cluster.generate(sizes, seed, out)
    profile = profile_of(spec)
    for variant in made["variants"].values():
        c = variant["cluster"]
        variant["cluster"] = ProfiledCluster(c.nodes, c.bound, c.workloads, c.new_node, profile)
    path = os.path.join(out, "plan", "scheduler-config.yaml")
    with open(path, "w") as f:
        json.dump(scheduler_config(spec), f, indent=1)  # JSON is YAML
    made["scheduler_config"] = path
    return made
