"""Memory observatory + compile telemetry + phase profiles (ISSUE 12)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from opensim_tpu.engine import prepcache
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx
from opensim_tpu.obs import footprint
from opensim_tpu.server import rest


def _cluster(nodes=6, bound=12):
    rt = ResourceTypes()
    for i in range(nodes):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "16", "64Gi"))
    for i in range(bound):
        rt.pods.append(
            fx.make_fake_pod(f"b{i:02d}", "500m", "1Gi", fx.with_node_name(f"n{i % nodes}"))
        )
    return rt


def _payload(name="web", replicas=3):
    return {"deployments": [fx.make_fake_deployment(name, replicas, "250m", "512Mi").raw]}


# ---------------------------------------------------------------------------
# arena accounting
# ---------------------------------------------------------------------------


def test_entry_footprint_attributes_arena_fields_by_policy_dtype():
    server = rest.SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    cache = footprint.prepcache_footprint(server.prep_cache, include_fields=True)
    assert cache["entries"], "deploy must populate the cache"
    entry = cache["entries"][0]
    assert entry["bytes"] > 0
    # every field carries bytes/dtype/shape, and the dtype classes are the
    # encoder policy vocabulary (a foreign dtype would land in "other")
    assert "alloc" in entry["fields"]
    assert entry["fields"]["alloc"]["dtype"] == "float32"
    assert set(entry["dtypes"]) <= {"float32", "int32", "int64", "bool", "other"}
    assert "off_policy_fields" not in entry  # the policy holds repo-wide
    assert sum(entry["dtypes"].values()) == entry["bytes"]


def test_cache_totals_reconcile_with_entry_sums_and_dedup_shared_leaves():
    """The ISSUE 12 acceptance criterion: totals == Σ per-entry unique
    bytes, with delta entries' shared base leaves counted exactly once."""
    server = rest.SimonServer(base_cluster=_cluster())
    for k in range(3):
        assert server.deploy_apps(_payload(f"app-{k}"))[0] == 200
    cache = footprint.prepcache_footprint(server.prep_cache)
    assert len(cache["entries"]) >= 2
    assert cache["total_bytes"] == sum(e["unique_bytes"] for e in cache["entries"])
    # derived entries alias the base's unchanged arenas: dedup must bite
    assert cache["shared_bytes"] > 0
    assert sum(cache["dtypes"].values()) == cache["total_bytes"]


def test_twin_delta_entry_reports_lineage_and_drop_density():
    server = rest.SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    base_key = next(
        e.key for e in server.prep_cache.entries_snapshot() if e.key.endswith("|base")
    )
    base = server.prep_cache.get(base_key)
    with base.lock:
        base.restore()
        derived = prepcache.twin_pod_delta(
            base, base_key + "|churn",
            [fx.make_fake_pod("new-pod", "250m", "512Mi")],
            {("default", "b00"), ("default", "b01")},
        )
    assert derived is not None
    fp = footprint.entry_footprint(derived)
    assert fp["lineage_depth"] == 1
    assert fp["drop_density"] > 0
    assert fp["pods"] == len(derived.prep.ordered)


def test_compaction_counter_bumps_on_density_refusal():
    rt = _cluster(nodes=4, bound=80)
    server = rest.SimonServer(base_cluster=rt)
    assert server.deploy_apps(_payload())[0] == 200
    base_key = next(
        e.key for e in server.prep_cache.entries_snapshot() if e.key.endswith("|base")
    )
    base = server.prep_cache.get(base_key)
    before = prepcache.compactions_total()
    removed = {("default", f"b{i:02d}") for i in range(70)}  # > the 64-row floor
    with base.lock:
        base.restore()
        refused = prepcache.twin_pod_delta(base, base_key + "|x", [], removed)
    assert refused is None
    assert prepcache.compactions_total() == before + 1


def test_process_memory_and_observatory_watermark():
    proc = footprint.process_memory()
    assert proc["rss_bytes"] > 0
    assert proc["rss_peak_bytes"] >= proc["rss_bytes"]
    obs = footprint.MemoryObservatory()
    first = obs.sample_process()
    again = obs.sample_process()
    assert again["rss_peak_bytes"] >= first["rss_peak_bytes"]  # monotone peak


def test_memory_rows_parity_with_cluster_report(tmp_path):
    """simon top --mem parity: the report JSON's memory rows ARE the rows
    the text renderer prints (byte-equal, like every report table)."""
    from opensim_tpu.obs.capacity import format_top
    from opensim_tpu.obs.footprint import memory_rows

    server = rest.SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    report = server.cluster_report(probe_headroom=False, include_memory=True)
    rows = report["memory"]["rows"]
    assert rows[0] == ["Memory", "Value"]
    assert rows == memory_rows(report["memory"]["summary"])
    rendered = format_top(report)
    for row in rows:
        for cell in row:
            assert cell in rendered
    # without ?mem=1 the block is absent and the renderer skips it
    bare = server.cluster_report(probe_headroom=False)
    assert "memory" not in bare
    assert "process RSS" not in format_top(bare)


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------


def test_observed_jit_call_records_compiles_with_cause_attribution():
    import jax
    import jax.numpy as jnp

    from opensim_tpu.obs import profile

    watch = profile.CompileWatch()
    orig = profile.COMPILES
    profile.COMPILES = watch
    try:
        fitted = jax.jit(lambda x, k=2: x * k, static_argnames=("k",))
        profile.observed_jit_call("toy", fitted, (jnp.ones((4,)),), {"k": 2})
        profile.observed_jit_call("toy", fitted, (jnp.ones((4,)),), {"k": 2})  # warm
        profile.observed_jit_call("toy", fitted, (jnp.ones((8,)),), {"k": 2})  # shape
        profile.observed_jit_call(
            "toy", fitted, (jnp.ones((8,), jnp.int32),), {"k": 2}
        )  # dtype
        profile.observed_jit_call("toy", fitted, (jnp.ones((8,), jnp.int32),), {"k": 3})  # static
        snap = watch.snapshot()["boundaries"]["toy"]
        assert snap["compiles"] == 4  # the warm call recorded nothing
        assert snap["causes"] == {"first": 1, "shape": 1, "dtype": 1, "static": 1}
        assert snap["distinct_signatures"] == 4
        assert snap["seconds"] > 0
    finally:
        profile.COMPILES = orig


def test_schedule_pods_boundary_is_instrumented():
    """An XLA-path simulate must show up at the schedule_pods boundary."""
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.obs import profile

    rt = _cluster(nodes=3, bound=0)
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("solo", "100m", "128Mi"))
    before = (
        profile.COMPILES.snapshot()["boundaries"]
        .get("schedule_pods", {})
        .get("compiles", 0)
    )
    res = simulate(rt, [AppResource("a", app)])
    assert not res.unscheduled_pods
    after = (
        profile.COMPILES.snapshot()["boundaries"]
        .get("schedule_pods", {})
        .get("compiles", 0)
    )
    # at least one compile OR the signature was already warm from an
    # earlier test in this process — the boundary must exist either way
    assert "schedule_pods" in profile.COMPILES.snapshot()["boundaries"] or after > before


def test_jitcache_stats_counts_files(tmp_path, monkeypatch):
    from opensim_tpu.utils import jitcache

    cache_dir = tmp_path / "jit"
    cache_dir.mkdir()
    (cache_dir / "a.bin").write_bytes(b"x" * 100)
    (cache_dir / "b.bin").write_bytes(b"y" * 50)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    monkeypatch.delenv("OPENSIM_JIT_CACHE", raising=False)
    stats = jitcache.cache_stats()
    assert stats == {"dir": str(cache_dir), "files": 2, "bytes": 150}


# ---------------------------------------------------------------------------
# phase profiles
# ---------------------------------------------------------------------------


def test_phase_profile_folds_exclusive_time_and_quantiles():
    from opensim_tpu.obs import trace as tracing
    from opensim_tpu.obs.profile import PhaseProfile

    prof = PhaseProfile()
    for _ in range(4):
        tr = tracing.TraceContext("deploy-apps")
        with tracing.trace_scope(tr):
            with tr.span("prepare"):
                with tr.span("encode"):
                    time.sleep(0.002)
                time.sleep(0.001)
        tr.finish()
        prof.observe_trace(tr)
    snap = prof.snapshot()
    assert snap["traces"] == 4
    prepare = snap["spans"]["prepare"]
    encode = snap["spans"]["encode"]
    assert prepare["count"] == 4 and encode["count"] == 4
    # exclusive time subtracts the encode child from prepare
    assert prepare["exclusive_seconds"] < prepare["seconds"]
    assert prepare["seconds"] >= encode["seconds"]
    assert prepare["p99_s"] >= prepare["p50_s"] >= 0
    assert "deploy-apps" in snap["endpoints"]


def test_debug_endpoints_and_cli_render(tmp_path):
    """GET /api/debug/memory + /api/debug/profile over real HTTP, and the
    simon mem / simon profile CLIs against them."""
    from http.server import ThreadingHTTPServer

    from opensim_tpu.cli.main import build_parser, run_mem, run_profile

    server = rest.SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), rest.make_handler(server))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{url}/api/debug/memory") as resp:
            mem = json.load(resp)
        assert mem["prepcache"]["total_bytes"] > 0
        assert mem["process"]["rss_bytes"] > 0
        assert "fields" in mem["prepcache"]["entries"][0]
        with urllib.request.urlopen(f"{url}/api/debug/memory?fields=0") as resp:
            lean = json.load(resp)
        assert "fields" not in lean["prepcache"]["entries"][0]
        with urllib.request.urlopen(f"{url}/api/debug/profile") as resp:
            prof = json.load(resp)
        assert prof["phases"]["traces"] >= 1
        assert "backend" in prof["compiles"]

        parser = build_parser()
        import contextlib
        import io

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run_mem(parser.parse_args(["mem", "--url", url]))
        assert rc == 0
        text = out.getvalue()
        assert "prep cache:" in text and "process: RSS" in text
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run_profile(parser.parse_args(["profile", "--url", url, "--json"]))
        assert rc == 0
        assert json.loads(out.getvalue())["phases"]["traces"] >= 1
    finally:
        httpd.shutdown()
        server.close()


def test_mem_ticker_env_knob(monkeypatch):
    monkeypatch.setenv("OPENSIM_MEM_TICKER_S", "0")
    obs = footprint.MemoryObservatory()
    obs.start_ticker()
    assert obs._ticker is None  # 0 disables
    monkeypatch.setenv("OPENSIM_MEM_TICKER_S", "not-a-number")
    assert footprint.mem_ticker_s() == 10.0  # degrade-with-warning contract
