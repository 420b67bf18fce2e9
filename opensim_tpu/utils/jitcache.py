"""JAX persistent compilation cache wiring.

The headline 50k/5k plan spends its cold start compiling the scan/
megakernel pipelines; the persistent cache amortizes that across processes
(CI runs, repeated `simon apply` invocations, server restarts, the phases of
``chip_smoke.py``).

One resolver, :func:`cache_dir`:

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX itself reads it; this module sets
  no directory in code and creates none, so a cache placed from outside (a
  CI volume, the chip machine's own setting) is the only one touched;
- unset → the fixed, git-ignored ``.jit_cache/`` at the checkout root. The
  path is part of nothing random (no pid, time or temp name): two processes
  started from the same checkout resolve the same directory, so the second
  one hits what the first one compiled.

``OPENSIM_JIT_CACHE=0`` turns the cache off; any other value (or unset)
leaves the caller's default in effect. ``bench.py``, the CLI and the test
conftest default it on.
"""

from __future__ import annotations

import os
from typing import Optional

from . import envknobs

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jit_cache",
)


def cache_dir() -> str:
    """The one persistent-cache directory this process may use."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def cache_stats() -> Optional[dict]:
    """Footprint of the persistent compilation cache directory, or None
    when disabled. O(entries) directory scan — called from debug/metrics
    reads, never the serving hot path."""
    if envknobs.raw("OPENSIM_JIT_CACHE") == "0":
        return None
    d = cache_dir()
    files = total = 0
    try:
        with os.scandir(d) as it:
            for entry in it:
                try:
                    if entry.is_file():
                        files += 1
                        total += entry.stat().st_size
                except OSError:
                    continue  # entry raced away mid-scan
    except OSError:
        return None
    return {"dir": d, "files": files, "bytes": total}


def maybe_enable(default: bool = False) -> Optional[str]:
    """Enable the persistent compilation cache unless opted out.

    Returns the cache directory in effect, or None when disabled. `default`
    is the behavior with OPENSIM_JIT_CACHE unset: benches/CLIs that always
    benefit from a warm cache pass True."""
    raw = envknobs.raw("OPENSIM_JIT_CACHE")
    if raw == "0" or (not raw and not default):
        return None
    import jax

    # cache every compilation, not only the slow ones: the scan pipeline
    # recompiles per (P, N, feature) signature and each one matters
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # JAX reads the variable itself; nothing set in code
    try:
        os.makedirs(DEFAULT_DIR, exist_ok=True)
    except OSError as e:
        # an unwritable cache dir degrades to cold compiles, it must never
        # fail the caller — but silently eating it hid real misconfiguration
        import logging

        logging.getLogger("opensim_tpu").warning(
            "persistent jit cache disabled: cannot create %s (%s)", DEFAULT_DIR, e
        )
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
