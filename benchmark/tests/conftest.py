"""Tests of the benchmark's own yardstick; run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 suite (``tests/``)."""
import os
import sys

# four virtual CPU devices, settled before jax starts: the throw-away
# four-chip cell of test_broken_path needs them, the cells use the first
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
