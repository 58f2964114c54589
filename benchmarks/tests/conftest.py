"""Run by hand: ``python -m pytest benchmarks/tests -q`` (tier-1 collects
``tests/`` only). Everything here runs on the CPU: four virtual devices
for the four-chip rehearsal, no persistent compile cache."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
