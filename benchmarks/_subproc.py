"""Shared fake-multi-device subprocess harness for HLO measurements.

Collective-count benchmarks need ``--xla_force_host_platform_device_count``
set *before* jax initializes, so each measurement runs a snippet in a fresh
subprocess and parses the JSON it prints on its last stdout line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap


def run_hlo_subprocess(snippet: str, n_devices: int, *,
                       timeout: int = 900) -> dict:
    """Run ``snippet`` under an ``n_devices`` fake-device fabric; return the
    JSON object the snippet prints as its final line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"    # a CPU fake fabric: never the chip
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"collective-count subprocess failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])
