"""The benchmark's own tests, run with ``pytest bench/tests`` (the repository's
tier-1 run, ``testpaths = tests``, does not collect them).  They run on the
CPU with four virtual devices, so a 2x2 fabric can be built."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
