"""OSL1804 registry-sync regression matrix (detector-awake for the
contract-parity pass): copies of the REAL registry and arena structs are
mutated one axis at a time — policy constant value, dropped/stale registry
entries, an unresolvable policy name — and the rule must fire naming the
exact field; the unmutated copies must stay green."""

import os
import shutil

from opensim_tpu.analysis import lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "opensim_tpu")


def _stage(tmp_path, mutate=None):
    """Copy the real registry and arena structs into tmp preserving the
    path suffixes the rule locates them by."""
    root = os.path.join(str(tmp_path), "staged")
    os.makedirs(os.path.join(root, "encoding"))
    for rel in ("encoding/dtypes.py", "encoding/state.py"):
        shutil.copy(os.path.join(PKG, rel), os.path.join(root, rel))
    if mutate is not None:
        mutate(root)
    return root


def _findings(root, rules=("contract-abi-parity",)):
    return lint_paths([root], rules=list(rules))


def _edit(root, rel, old, new, count=1):
    path = os.path.join(root, rel)
    with open(path) as fh:
        src = fh.read()
    assert old in src, f"mutation anchor {old!r} missing from {rel}"
    with open(path, "w") as fh:
        fh.write(src.replace(old, new, count))


def test_real_sources_are_green(tmp_path):
    assert _findings(_stage(tmp_path)) == []


def test_dropped_contract_entry_fires_naming_the_field(tmp_path):
    root = _stage(tmp_path)
    _edit(root, "encoding/dtypes.py",
          '    "node_domain": ("INT_DTYPE", ("N", "Tk")),\n', "")
    findings = _findings(root)
    assert findings and all(f.code == "OSL1804" for f in findings)
    assert any("`node_domain`" in f.message
               and "no ARENA_CONTRACTS entry" in f.message for f in findings)


def test_stale_contract_entry_fires(tmp_path):
    root = _stage(tmp_path)
    _edit(root, "encoding/dtypes.py",
          '    "node_domain": ("INT_DTYPE", ("N", "Tk")),',
          '    "node_domain": ("INT_DTYPE", ("N", "Tk")),\n'
          '    "ghost_field": ("INT_DTYPE", ("N",)),')
    findings = _findings(root)
    assert findings and all(f.code == "OSL1804" for f in findings)
    assert any("`ghost_field`" in f.message and "names no EncodedCluster"
               in f.message for f in findings)


def test_unresolvable_policy_name_fires(tmp_path):
    root = _stage(tmp_path)
    _edit(root, "encoding/dtypes.py",
          '"node_domain": ("INT_DTYPE", ("N", "Tk")),',
          '"node_domain": ("MYSTERY_DTYPE", ("N", "Tk")),')
    findings = _findings(root)
    assert findings and all(f.code == "OSL1804" for f in findings)
    assert any("MYSTERY_DTYPE" in f.message for f in findings)
