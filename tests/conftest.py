"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths run without TPU hardware.

The CPU platform is selected through jax.config (not only JAX_PLATFORMS) so
a shell that exports another platform still gets the virtual mesh;
OPENSIM_TEST_BACKEND=tpu opts out for the compiled-Mosaic parity run."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from opensim_tpu.utils import jitcache  # noqa: E402

# same rule as utils/jitcache.py: a cache placed from outside wins, else the
# fixed in-checkout directory (subprocess tests inherit the choice)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", jitcache.cache_dir())

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# OPENSIM_TEST_BACKEND=tpu opts out of the CPU forcing so the fastpath /
# kernel-parity tests can run through compiled Mosaic on real hardware.
if os.environ.get("OPENSIM_TEST_BACKEND") != "tpu":
    jax.config.update("jax_platforms", "cpu")
    # servers and benches the tests spawn inherit the platform (nothing forces
    # it on them any more), so they must inherit this one
    os.environ["JAX_PLATFORMS"] = "cpu"
