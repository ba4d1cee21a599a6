"""Generalized Pallas stencil kernel (kernels/stencil_nd) tests: every spec
against the jnp oracle, chunking equivalence, and the distributed drop-in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stencil
from repro.kernels.stencil_nd import stencil_apply, stencil_nd_ref


def _tol(dtype):
    return (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
            else dict(rtol=2e-5, atol=2e-5))


@pytest.mark.parametrize("specname", ["star7", "star13", "star25", "box27"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(specname, dtype):
    spec = stencil.get_spec(specname)
    shape = (6, 7, 8)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape,
                                     dtype=dtype, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32).astype(dtype)
    u_k = stencil_apply(cf, v, spec=spec)
    u_r = stencil_nd_ref(v, [cf.diags[n] for n in spec.names], spec.offsets)
    np.testing.assert_allclose(np.asarray(u_k, np.float32),
                               np.asarray(u_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("specname", ["star25", "box27"])
def test_kernel_matches_core_apply(specname):
    """The kernel must agree with the solver's own oracle (core.stencil)."""
    spec = stencil.get_spec(specname)
    shape = (5, 6, 16)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(2), shape, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    u_k = stencil_apply(cf, v, spec=spec)
    u_c = stencil.apply_ref(cf, v)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_c),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("specname", ["star13", "box27"])
def test_zc_chunking_equivalence(specname):
    """Different VMEM chunkings must give identical results (r-deep windows).
    A split Z axis is cut at multiples of the 128-lane tile."""
    from repro.kernels.stencil_nd.kernel import stencil_nd_pallas
    spec = stencil.get_spec(specname)
    shape = (4, 16, 256)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(4), shape, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
    vp = jnp.pad(v, spec.radius)
    cl = [cf.diags[n] for n in spec.names]
    outs = [stencil_nd_pallas(vp, cl, spec.offsets, radius=spec.radius, zc=zc)
            for zc in (256, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=0, atol=0)


def test_stencil7_alias_is_generic_kernel():
    """kernels.stencil7 must be a one-file deprecation shim re-exporting the
    r=1 star specialization of stencil_nd (satellite: the old package's
    kernel/ops/ref bodies are gone)."""
    from repro.kernels import stencil7, stencil_nd
    assert stencil7.__file__.endswith("stencil7.py")   # module, not package
    for name in ("stencil7_apply", "stencil7_ref", "stencil7_pallas",
                 "pallas_local_apply", "ORDER", "default_tile", "VMEM_BUDGET_BYTES"):
        assert hasattr(stencil7, name), name          # legacy surface intact
    shape = (4, 4, 8)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(6), shape)
    v = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    u7 = stencil7.stencil7_apply(cf, v)
    und = stencil_nd.stencil_apply(cf, v, spec=stencil.STAR7)
    np.testing.assert_allclose(np.asarray(u7), np.asarray(und), rtol=0, atol=0)
    u_ref = stencil7.stencil7_ref(v, [cf.diags[n] for n in stencil7.ORDER])
    np.testing.assert_allclose(np.asarray(u7), np.asarray(u_ref),
                               rtol=1e-5, atol=1e-5)


def test_pick_zc_budget_scales_with_radius():
    from repro.kernels.stencil_nd.ops import default_tile
    # same block: a deeper/wider stencil must not pick a LARGER tile
    t1 = default_tile((64, 64, 256), 4, radius=1, n_coeffs=6, budget=2 ** 22)
    t4 = default_tile((64, 64, 256), 4, radius=4, n_coeffs=24, budget=2 ** 22)
    assert t4[0] * t4[1] * t4[2] <= t1[0] * t1[1] * t1[2]
    assert t4[2] in (256, 128) and 64 % t4[0] == 0 and 64 % t4[1] == 0


@pytest.mark.parametrize("specname", ["star13", "box27"])
@pytest.mark.slow
def test_pallas_local_apply_in_distributed_solver(subproc, specname):
    """solve_distributed with the generic kernel as the SpMV
    (``backend="pallas"``, whose apply is ``pallas_local_apply``), on a
    depth-2 (star13) and corner-carrying (box27) halo."""
    subproc(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)
        spec = stencil.get_spec({specname!r})
        shape = (8, 8, 8)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, spec=spec)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        res = bicgstab.solve_distributed(
            mesh, cf, b, tol=1e-8, maxiter=300, policy=precision.F32,
            backend="pallas", interpret=True)
        assert bool(res.converged), res
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                   rtol=2e-4, atol=2e-4)
        print('OK')
    """, n_devices=4)
