"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see 1 device.

Multi-device behaviour is tested via subprocess helpers (see
tests/test_distributed.py) so the main process keeps a single CpuDevice.
"""

import os
import subprocess
import sys
import textwrap

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess with N fake XLA host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"    # a CPU fake fabric: never the chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"subprocess failed:\nSTDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_with_devices


@pytest.fixture(autouse=True)
def _obs_reset():
    """Observability state is process-global; keep tests isolated."""
    yield
    from repro.obs import metrics, trace

    metrics.reset()
    trace.reset()
    trace.disable()
