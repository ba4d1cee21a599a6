"""Paper Figs. 7-8: strong scaling of solve time vs core count.

The paper shows Joule (Xeon cluster) scaling from 75 ms/iter (1024 cores) to
~6 ms/iter (16k cores) on a 600^3 mesh, vs 28.1 us on the CS-1, and a smaller
370^3 mesh that stops scaling beyond 8k cores.

Here: (a) measured CPU strong scaling over fake-device fabrics (1->8
devices, fixed problem) exercising the real halo/AllReduce code path;
(b) the TPU roofline model's scaling curve for the paper meshes at
{64, 128, 256, 512} chips (memory term scales with per-chip volume; the
AllReduce latency floor does not).
"""

import os
import subprocess
import sys


def _measure(n_devices: int, shape=(32, 32, 32), iters: int = 30) -> float:
    """Per-iteration seconds on an n-device CPU fabric (subprocess)."""
    code = f"""
import time, jax, jax.numpy as jnp
from repro.core import bicgstab, precision, stencil
from repro.launch.mesh import make_mesh_for_devices
shape = {shape!r}
cf = stencil.convection_diffusion(shape)
b = stencil.rhs_for_solution(cf, jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32))
mesh = make_mesh_for_devices({n_devices})
solve = jax.jit(lambda c, bb: bicgstab.solve_distributed(
    mesh, c, bb, tol=1e-30, maxiter={iters}, policy=precision.F32))
res = solve(cf, b); jax.block_until_ready(res.x)
t0 = time.time(); res = solve(cf, b); jax.block_until_ready(res.x)
print((time.time() - t0) / {iters})
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"    # a CPU fake fabric: never the chip
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return float(out.stdout.strip().splitlines()[-1])


def run(fast: bool = False) -> list[str]:
    rows = []
    # (a) measured: fixed 32^3-ish problem across 1/2/4/8 CPU devices.
    for n in (1, 2, 4, 8):
        dt = _measure(n)
        rows.append(f"strong_scaling,cpu_{n}dev_us_per_iter,{dt * 1e6:.0f}")
    # (b) roofline model across chip counts for the paper meshes
    from repro.core.perfmodel import iteration_time_model
    for mesh_name, mshape in (("600cube", (608, 608, 608)),
                              ("370cube", (384, 384, 370)),
                              ("cs1_paper", (608, 608, 1536))):
        for chips in (64, 128, 256, 512):
            t = iteration_time_model(mshape, chips)
            rows.append(f"strong_scaling,tpu_model_{mesh_name}_{chips}chips_us,"
                        f"{t['t_iter_s'] * 1e6:.1f}")
    rows.append("strong_scaling,joule_600cube_16k_cores_us,6000")
    rows.append("strong_scaling,cs1_measured_us,28.1")
    return rows
