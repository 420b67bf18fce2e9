"""`python -m pytest benchmarks/tests -q` from the root of the repo, on the
CPU. These tests are the benchmark's own and no part of tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
