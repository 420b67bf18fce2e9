"""The typed OPENSIM_* env-knob registry (ISSUE 12 satellite): every knob
registered, validators accept their documented defaults, docs/env.md stays
generated, and lint rule OSL1401 sweeps raw reads."""

import os

import pytest

from opensim_tpu.utils import envknobs


def test_every_knob_is_prefixed_and_documented():
    assert envknobs.KNOBS, "registry must not be empty"
    for name, knob in envknobs.KNOBS.items():
        assert name == knob.name
        assert name.startswith("OPENSIM_")
        assert knob.doc.strip(), f"{name} has no doc line"
        assert knob.type in ("int", "float", "flag", "enum", "str", "path", "spec")
        assert knob.on_error in ("default", "raise")


def test_validators_accept_their_documented_defaults():
    """The documented default must parse through the registered validator —
    the drift this registry exists to prevent."""
    for knob in envknobs.KNOBS.values():
        if knob.validator is None or knob.default == "":
            continue
        knob.validator(knob.default)  # must not raise


def test_raw_fails_loudly_on_unregistered_name():
    with pytest.raises(KeyError, match="not registered"):
        envknobs.raw("OPENSIM_NO_SUCH_KNOB")
    with pytest.raises(KeyError, match="not registered"):
        envknobs.is_set("OPENSIM_NO_SUCH_KNOB")


def test_raw_passthrough_and_default(monkeypatch):
    monkeypatch.delenv("OPENSIM_CAPACITY_TOPK", raising=False)
    assert envknobs.raw("OPENSIM_CAPACITY_TOPK") == ""
    assert envknobs.raw("OPENSIM_CAPACITY_TOPK", "10") == "10"
    monkeypatch.setenv("OPENSIM_CAPACITY_TOPK", "7")
    assert envknobs.raw("OPENSIM_CAPACITY_TOPK") == "7"
    assert envknobs.is_set("OPENSIM_CAPACITY_TOPK")


def test_value_parses_and_degrades_per_contract(monkeypatch):
    # "default" knobs warn and fall back on garbage
    monkeypatch.setenv("OPENSIM_FLIGHT_RECORDER_N", "not-a-number")
    assert envknobs.value("OPENSIM_FLIGHT_RECORDER_N") == 64
    monkeypatch.setenv("OPENSIM_FLIGHT_RECORDER_N", "9")
    assert envknobs.value("OPENSIM_FLIGHT_RECORDER_N") == 9
    # "raise" knobs surface the operator typo
    monkeypatch.setenv("OPENSIM_SCAN_UNROLL", "zero")
    with pytest.raises(ValueError):
        envknobs.value("OPENSIM_SCAN_UNROLL")


def test_docs_env_md_is_generated_and_in_sync():
    """docs/env.md is generated from the registry (make docs); a knob added
    without regenerating the docs fails here."""
    path = os.path.join(os.path.dirname(__file__), "..", "docs", "env.md")
    with open(path) as f:
        on_disk = f.read()
    assert on_disk == envknobs.render_markdown(), (
        "docs/env.md is stale; regenerate with `make docs`"
    )


def test_osl1401_flags_raw_reads_and_stays_quiet_on_registry_use():
    from opensim_tpu.analysis import lint_source

    bad = (
        "import os\n"
        'a = os.environ.get("OPENSIM_TRACE", "1")\n'
        'b = os.environ["OPENSIM_FAULTS"]\n'
        'c = os.getenv("OPENSIM_FASTPATH")\n'
        'd = "OPENSIM_JIT_CACHE" in os.environ\n'
    )
    findings = lint_source(bad, path="opensim_tpu/somewhere.py", rules=["env-registry"])
    assert len(findings) == 4
    assert all(f.code == "OSL1401" for f in findings)

    good = (
        "import os\n"
        "from opensim_tpu.utils import envknobs\n"
        'a = envknobs.raw("OPENSIM_TRACE", "1")\n'
        # writes are legal: the CLI arms knobs for downstream code
        'os.environ["OPENSIM_DISABLE_FASTPATH"] = "1"\n'
        # non-OPENSIM reads are out of scope
        'j = os.environ.get("JAX_PLATFORMS", "cpu")\n'
    )
    assert lint_source(good, path="opensim_tpu/somewhere.py", rules=["env-registry"]) == []
    # the registry module itself is the sanctioned read path
    assert (
        lint_source(bad, path="opensim_tpu/utils/envknobs.py", rules=["env-registry"])
        == []
    )


def test_call_site_literal_defaults_match_the_registry():
    """``envknobs.raw(NAME, default)`` callers keep site-local defaults for
    unset-vs-empty semantics; this sweep gates them against the registered
    default so docs/env.md can never document one value while a call site
    runs another (the drift the registry exists to prevent)."""
    import re

    pkg = os.path.join(os.path.dirname(__file__), "..", "opensim_tpu")
    pattern = re.compile(r'envknobs\.raw\(\s*"(OPENSIM_\w+)"\s*,\s*"([^"]*)"\s*\)')
    checked = 0
    for root, _dirs, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py") or fname == "envknobs.py":
                continue
            src = open(os.path.join(root, fname)).read()
            for name, literal in pattern.findall(src):
                assert name in envknobs.KNOBS, f"{fname}: unregistered {name}"
                assert literal == envknobs.KNOBS[name].default, (
                    f"{fname}: raw({name!r}, {literal!r}) disagrees with the "
                    f"registered default {envknobs.KNOBS[name].default!r}"
                )
                checked += 1
    assert checked >= 5  # the sweep found real call sites


def test_osl1401_suppression():
    from opensim_tpu.analysis import lint_source

    src = (
        "import os\n"
        'a = os.environ.get("OPENSIM_TRACE")  # opensim-lint: disable=env-registry\n'
    )
    assert lint_source(src, path="opensim_tpu/x.py", rules=["env-registry"]) == []
