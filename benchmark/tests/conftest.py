"""The benchmark's own tests: run by hand with ``python3 -m pytest
benchmark/tests`` from the repo's root (the tier-1 command collects
``tests/`` only, and this PR may add no file there).  Everything here runs
on the CPU, the dp cell on four virtual devices."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
