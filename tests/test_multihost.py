"""Two-process jax.distributed test: a scenario sweep sharded across a
DCN-spanning mesh (2 processes × 4 virtual CPU devices) must agree with the
single-process result — the backing for PARITY.md §2.3's multi-host claim.
Each child joins via multihost.initialize()'s env-var path."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow  # nightly tier (README: test tiering)

_CHILD = r"""
import os, sys
import jax
import numpy as np

from opensim_tpu.parallel import multihost
from opensim_tpu.parallel.scenarios import sweep
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx

assert multihost.initialize(), "JAX_COORDINATOR env not picked up"
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())

cluster = ResourceTypes()
for i in range(6):
    cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
app = ResourceTypes()
app.deployments.append(fx.make_fake_deployment("web", 10, "2", "2Gi"))
prep = prepare(cluster, [AppResource("a", app)], node_pad=8)

N = int(np.asarray(prep.ec_np.node_valid).shape[0])
P = len(prep.ordered)
# scenarios: first k nodes enabled, k = 1..8 (padded count)
S = 8
node_masks = np.zeros((S, N), bool)
for s in range(S):
    node_masks[s, : min(s + 1, 6)] = True
pod_masks = np.ones((S, P), bool)

res = sweep(
    prep.ec, prep.st0, prep.tmpl_ids, prep.forced,
    node_masks, pod_masks,
    mesh=multihost.global_mesh(), features=prep.features,
)
if jax.process_index() == 0:
    print("UNSCHED:" + ",".join(str(int(x)) for x in np.asarray(res.unscheduled)))
"""


@pytest.mark.skipif(os.environ.get("OPENSIM_SKIP_MULTIHOST") == "1", reason="opt-out")
def test_two_process_dcn_sweep(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            JAX_COORDINATOR=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH=REPO + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
            ),
        )
        env["JAX_PLATFORMS"] = "cpu"  # virtual host devices exist only on the CPU platform
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process sweep timed out")
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out[-3000:]}"
    line = [ln for ln in outs[0].splitlines() if ln.startswith("UNSCHED:")]
    assert line, outs[0][-2000:]
    got = [int(x) for x in line[0][len("UNSCHED:"):].split(",")]

    # closed-form reference for the same scenarios: 10 pods × 2cpu on
    # k × 8cpu nodes (k capped at the 6 real nodes) → min(4k, 10) bind
    want = [10 - min(4 * min(s + 1, 6), 10) for s in range(8)]
    assert got == want, (got, want)


_PLANNER_CHILD = r"""
import os, sys
import jax

from opensim_tpu.parallel import multihost

# the planner calls initialize() itself, but asserting here catches env rot
assert multihost.initialize(), "JAX_COORDINATOR env not picked up"
assert jax.process_count() == 2, jax.process_count()

import yaml
base = sys.argv[1]  # per-process scratch dir (same content both sides)
os.makedirs(f"{base}/cluster", exist_ok=True)
os.makedirs(f"{base}/app", exist_ok=True)
os.makedirs(f"{base}/newnode", exist_ok=True)

def node(name):
    return {
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": name, "labels": {"kubernetes.io/hostname": name}},
        "status": {"allocatable": {"cpu": "8", "memory": "32Gi", "pods": "110"},
                   "capacity": {"cpu": "8", "memory": "32Gi", "pods": "110"}},
    }

for i in range(2):
    open(f"{base}/cluster/n{i}.yaml", "w").write(yaml.safe_dump(node(f"n{i}")))
open(f"{base}/newnode/tmpl.yaml", "w").write(yaml.safe_dump(node("tmpl")))
open(f"{base}/app/d.yaml", "w").write(yaml.safe_dump({
    "apiVersion": "apps/v1", "kind": "Deployment",
    "metadata": {"name": "web"},
    "spec": {"replicas": 20, "selector": {"matchLabels": {"app": "web"}},
             "template": {"metadata": {"labels": {"app": "web"}},
                          "spec": {"containers": [{"name": "c", "image": "x",
                                   "resources": {"requests": {"cpu": "2", "memory": "2Gi"}}}]}}},
}))
open(f"{base}/config.yaml", "w").write(yaml.safe_dump({
    "apiVersion": "simon/v1alpha1", "kind": "Config",
    "metadata": {"name": "mh"},
    "spec": {"cluster": {"customConfig": f"{base}/cluster"},
             "appList": [{"name": "a", "path": f"{base}/app"}],
             "newNode": f"{base}/newnode"},
}))

from opensim_tpu.planner.apply import Applier, Options

rc = Applier(Options(simon_config=f"{base}/config.yaml",
                     output_file=f"{base}/report.txt",
                     max_new_nodes=16)).run()
assert rc == 0, rc
report = open(f"{base}/report.txt").read()
if jax.process_index() == 0:
    added = [ln for ln in report.splitlines() if "new node(s)" in ln]
    print("ADDED:" + (added[0] if added else "none"))
"""


@pytest.mark.skipif(os.environ.get("OPENSIM_SKIP_MULTIHOST") == "1", reason="opt-out")
def test_two_process_capacity_planner(tmp_path):
    """End-to-end `simon apply` capacity sweep across a 2-process DCN mesh:
    the candidate-count scenarios shard over both hosts and the minimal
    feasible count matches the closed form (40 cpu needed, 16 present,
    8 cpu per new node -> 3 new nodes)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "planner_child.py"
    script.write_text(_PLANNER_CHILD)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            JAX_COORDINATOR=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH=REPO + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
            ),
        )
        env["JAX_PLATFORMS"] = "cpu"  # virtual host devices exist only on the CPU platform
        scratch = tmp_path / f"p{pid}"
        scratch.mkdir()
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), str(scratch)],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process planner timed out")
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out[-3000:]}"
    line = [ln for ln in outs[0].splitlines() if ln.startswith("ADDED:")]
    assert line, outs[0][-2000:]
    assert "added 3 new node(s)" in line[0], line[0]
