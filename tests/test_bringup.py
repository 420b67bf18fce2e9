"""Chip bring-up contract (ISSUE 21), checked on the CPU: nothing in the
program may make a CPU run look like a chip run.

- the compile cache is placeable from outside and fixed otherwise;
- ``--backend tpu`` decides in process and exits 1 off-TPU;
- ``bench.py`` rows name their device, and the run fails without a TPU unless
  the CPU was asked for by name;
- the megakernel never interprets without being asked;
- bench children inherit the parent's platform unchanged;
- multi-process serving modes refuse a TPU backend (one process per chip).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scrubbed_env(**extra):
    # conftest's choices (cache dir, 8 virtual devices) are not the child's
    drop = ("JAX_COMPILATION_CACHE_DIR", "OPENSIM_JIT_CACHE", "XLA_FLAGS")
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **extra)
    return env


# ---------------------------------------------------------------------------
# compile cache resolver
# ---------------------------------------------------------------------------


def test_cache_dir_placed_from_outside_is_the_only_one_touched(tmp_path, monkeypatch):
    import jax

    from opensim_tpu.utils import jitcache

    placed = tmp_path / "placed"
    default = tmp_path / "would-be-default"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.delenv("OPENSIM_JIT_CACHE", raising=False)
    monkeypatch.setattr(jitcache, "DEFAULT_DIR", str(default))
    before = jax.config.jax_compilation_cache_dir
    assert jitcache.maybe_enable(default=True) == str(placed)
    assert jitcache.cache_dir() == str(placed)
    assert not default.exists(), "a second cache directory was created"
    assert jax.config.jax_compilation_cache_dir == before, "a directory was set in code"


def test_cache_off_switch_keeps_its_meaning(monkeypatch):
    from opensim_tpu.utils import jitcache

    monkeypatch.setenv("OPENSIM_JIT_CACHE", "0")
    assert jitcache.maybe_enable(default=True) is None
    assert jitcache.cache_stats() is None


def test_cache_dir_unset_is_fixed_in_checkout_across_processes(tmp_path):
    code = "from opensim_tpu.utils import jitcache; print(jitcache.cache_dir())"
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], env=_scrubbed_env(), cwd=cwd,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        for cwd in (REPO, str(tmp_path))
    }
    assert seen == {os.path.join(REPO, ".jit_cache")}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jit_cache/" in f.read().split()


def test_second_process_reports_a_persistent_cache_hit(tmp_path):
    """What chip_smoke.py prints per phase: a child compiling a shape an
    earlier child compiled says so, and the entries land in the placed dir."""
    code = (
        # the order cli.main() uses: cache on, compile telemetry listening, then work
        "from opensim_tpu.utils.jitcache import maybe_enable; maybe_enable(default=True)\n"
        "from opensim_tpu.obs.profile import COMPILES\n"
        "from opensim_tpu.cli.main import device_line\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: (x * 3 + 1).sum())(jnp.ones((64, 64))).block_until_ready()\n"
        "print(device_line())\n"
    )
    cache = tmp_path / "cache"
    lines = [
        subprocess.run(
            [sys.executable, "-c", code], cwd=str(tmp_path),
            env=_scrubbed_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
            capture_output=True, text=True, check=True,
        ).stdout.strip().splitlines()[-1]
        for _ in range(2)
    ]
    assert "platform=cpu" in lines[0] and f"cache_dir={cache}" in lines[0]
    assert "cache_hits=0" in lines[0] and "cache_misses=0" not in lines[0]
    assert "cache_hits=0" not in lines[1], lines[1]
    assert any(cache.iterdir())
    assert not (tmp_path / ".jit_cache").exists()


# ---------------------------------------------------------------------------
# --backend tpu decides in process
# ---------------------------------------------------------------------------


def test_backend_tpu_on_cpu_exits_1_without_spawning(monkeypatch, capsys):
    from opensim_tpu.cli import main as cli

    def no_children(*a, **k):
        raise AssertionError("--backend tpu spawned a child process")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(subprocess, "run", no_children)
    monkeypatch.delenv("OPENSIM_REQUIRE_TPU", raising=False)
    with pytest.raises(SystemExit) as exc:
        cli.main(["apply", "--backend", "tpu", "-f", os.path.join(REPO, "example/simon-config.yaml")])
    assert exc.value.code == 1
    assert "JAX selected 'cpu'" in capsys.readouterr().err
    assert "OPENSIM_REQUIRE_TPU" not in os.environ


def test_backend_tpu_refuses_the_interpreter(monkeypatch, capsys):
    from opensim_tpu.cli import main as cli

    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    with pytest.raises(SystemExit) as exc:
        cli._select_backend("tpu")
    assert exc.value.code == 1
    assert "interpret" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# bench.py: rows name the device; no TPU and no explicit CPU request = failure
# ---------------------------------------------------------------------------


def _bench(env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--pods", "40", "--nodes", "4", "--no-warmup"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )


def test_bench_rows_carry_the_device():
    proc = _bench(_scrubbed_env(OPENSIM_JIT_CACHE="0"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (row["platform"], row["device_kind"], row["device_count"]) == ("cpu", "cpu", 1)
    assert "backend" not in row and "backend_note" not in row


@pytest.mark.skipif(
    os.environ.get("OPENSIM_TEST_BACKEND") == "tpu", reason="needs a machine without a TPU"
)
def test_bench_fails_without_a_tpu_unless_cpu_was_asked_for():
    env = _scrubbed_env(OPENSIM_JIT_CACHE="0")
    del env["JAX_PLATFORMS"]
    proc = _bench(env)
    assert proc.returncode != 0
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "no TPU found" in row["error"] and row["stage"] == "device"
    assert "value" not in row


# ---------------------------------------------------------------------------
# the megakernel never interprets without being asked
# ---------------------------------------------------------------------------


def test_fastpath_off_tpu_raises_instead_of_interpreting(monkeypatch):
    from opensim_tpu.engine import fastpath

    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    # raised before any input is marshalled: nothing about `prep` is read
    with pytest.raises(RuntimeError, match="compiles only for a TPU backend"):
        fastpath.schedule(None, [], [], [])
    with pytest.raises(RuntimeError, match="compiles only for a TPU backend"):
        fastpath.sweep(None, None, None, None)


# ---------------------------------------------------------------------------
# bench children inherit the platform
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("platform", ["tpu", None])
def test_boot_server_passes_the_platform_through(monkeypatch, tmp_path, platform):
    from opensim_tpu.server import loadgen

    seen = {}

    class _Exited:
        returncode = 1

        def __init__(self, cmd, env=None, **kw):
            seen["env"] = env

        def poll(self):
            return 1

    monkeypatch.setattr(subprocess, "Popen", _Exited)
    if platform is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platform)
    with pytest.raises(RuntimeError, match="server exited at boot"):
        loadgen._boot_server(str(tmp_path / "kc"), 1, admission=True, batch_max=2)
    assert seen["env"].get("JAX_PLATFORMS") == platform


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------


def test_multiprocess_serving_refuses_a_tpu_backend(monkeypatch, capsys):
    import jax

    from opensim_tpu.server import pool, rest

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("OPENSIM_FLEET_ATTACH", raising=False)
    assert rest.serve(kubeconfig="kc", port=0, workers=2) == 1
    assert rest.serve(kubeconfig="kc", port=0, journal="journal", standby=True) == 1
    out = capsys.readouterr().out
    assert out.count("a TPU chip belongs to one process at a time") == 2
    if "fork" in __import__("multiprocessing").get_all_start_methods():
        with pytest.raises(pool.OneProcessPerChip, match="one process at a time"):
            pool.WorkerPool(workers=1, mode="process")
    # and nothing changes off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert pool.one_process_per_chip("--workers 2") is None
