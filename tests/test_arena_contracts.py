"""Contract registry integrity: the (dtype, axes) declarations in
``encoding/dtypes.py`` must cover the arena structs key-for-key, resolve
to real policy constants, and — the ground truth — agree with the arrays
a real ``prepare()`` actually builds, field by field, dtype AND rank."""

import numpy as np
import pytest

from opensim_tpu.encoding import dtypes as D
from opensim_tpu.encoding.state import EncodedCluster, ScanState


def _policy(name):
    return np.dtype(getattr(D, name))


def test_arena_contract_keys_match_encoded_cluster_fields():
    assert set(D.ARENA_CONTRACTS) == set(EncodedCluster._fields)


def test_state_contract_keys_match_scan_state_fields():
    assert set(D.STATE_CONTRACTS) == set(ScanState._fields)


def test_every_contract_names_a_policy_constant():
    for table in (D.ARENA_CONTRACTS, D.STATE_CONTRACTS,
                  *D.KERNEL_ARG_CONTRACTS.values()):
        for fname, (policy, axes) in table.items():
            assert policy.endswith("_DTYPE") and hasattr(D, policy), (
                f"{fname}: contract names {policy!r}, not a policy constant")
            assert isinstance(axes, tuple), f"{fname}: axes must be a tuple"


@pytest.fixture(scope="module")
def prepared():
    from opensim_tpu.engine.simulator import AppResource, prepare
    from opensim_tpu.models import ResourceTypes, fixtures as fx

    rt = ResourceTypes()
    for i in range(8):
        rt.nodes.append(fx.make_fake_node(
            f"n{i:03d}", "16", "64Gi", "110",
            fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"})))
    apps_rt = ResourceTypes()
    apps_rt.deployments.append(fx.make_fake_deployment("web", 4, "500m", "1Gi"))
    return prepare(rt, [AppResource(name="web", resources=apps_rt)])


def test_runtime_cluster_arrays_honor_arena_contracts(prepared):
    bad = []
    for fname, (policy, axes) in D.ARENA_CONTRACTS.items():
        arr = np.asarray(getattr(prepared.ec, fname))
        if arr.dtype != _policy(policy):
            bad.append(f"ec.{fname}: dtype {arr.dtype} != {policy}")
        if arr.ndim != len(axes):
            bad.append(f"ec.{fname}: rank {arr.ndim} != {axes}")
    assert not bad, "\n".join(bad)


def test_runtime_state_arrays_honor_state_contracts(prepared):
    bad = []
    for fname, (policy, axes) in D.STATE_CONTRACTS.items():
        arr = np.asarray(getattr(prepared.st0, fname))
        if arr.dtype != _policy(policy):
            bad.append(f"st0.{fname}: dtype {arr.dtype} != {policy}")
        if arr.ndim != len(axes):
            bad.append(f"st0.{fname}: rank {arr.ndim} != {axes}")
    assert not bad, "\n".join(bad)


def test_runtime_kernel_entry_arrays_honor_boundary_contracts(prepared):
    contracts = D.KERNEL_ARG_CONTRACTS["schedule_pods"]
    for name, attr in (("tmpl_ids", "tmpl_ids"), ("forced", "forced")):
        policy, axes = contracts[name]
        arr = np.asarray(getattr(prepared, attr))
        assert arr.dtype == _policy(policy), f"{name}: {arr.dtype} != {policy}"
        assert arr.ndim == len(axes), f"{name}: rank {arr.ndim} != {axes}"
