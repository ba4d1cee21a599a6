"""Preconditioner tests: Chebyshev cuts Poisson iterations >=30%, Jacobi
does real work on raw variable-diagonal operators, and everything still
converges to the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bicgstab, precision, stencil
from repro.core.operator import make_operator
from repro.core.precond import (
    PrecondConfig, build_precond, gershgorin_bounds, get_precond_config,
)


def test_config_validation():
    with pytest.raises(ValueError, match="unknown preconditioner"):
        PrecondConfig(name="ilu")
    with pytest.raises(ValueError, match="degree"):
        PrecondConfig(name="chebyshev", degree=0)
    assert get_precond_config(None).name == "none"
    assert get_precond_config("jacobi").name == "jacobi"
    cfg = get_precond_config(PrecondConfig(name="chebyshev"), degree=5)
    assert cfg.degree == 5


def test_gershgorin_bounds_enclose_spectrum():
    cf = stencil.poisson((5, 5, 5))
    lo, hi = gershgorin_bounds(cf)
    w = np.linalg.eigvalsh(stencil.to_dense(cf))
    assert float(lo) <= w.min() + 1e-6
    assert float(hi) >= w.max() - 1e-6


def test_chebyshev_approximates_inverse():
    """Higher degree => M^-1 v closer to A^-1 v (on the bounded spectrum)."""
    cf = stencil.poisson((5, 5, 5))
    v = jax.random.normal(jax.random.PRNGKey(0), (5, 5, 5), jnp.float32)
    A = stencil.to_dense(cf)
    z_true = np.linalg.solve(A, np.asarray(v, np.float64).ravel())
    op = make_operator("reference", cf, policy=precision.F32)
    errs = []
    for degree in (1, 3, 6):
        M = build_precond(
            PrecondConfig(name="chebyshev", degree=degree, lmin_floor=0.01), op)
        z = np.asarray(M.apply(v), np.float64).ravel()
        errs.append(np.linalg.norm(z - z_true) / np.linalg.norm(z_true))
    assert errs[2] < errs[1] < errs[0]


def test_chebyshev_cuts_poisson_iterations_30pct():
    """The acceptance lever at test scale: right-Chebyshev BiCGStab on
    Poisson star7 in >=30% fewer iterations, same solution."""
    shape = (24, 24, 16)
    cf = stencil.poisson(shape)
    x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    base = bicgstab.solve_ref(cf, b, tol=1e-6, maxiter=500)
    cheb = bicgstab.solve_ref(cf, b, tol=1e-6, maxiter=500,
                              precond=PrecondConfig(name="chebyshev", degree=3))
    assert bool(base.converged) and bool(cheb.converged)
    assert int(cheb.iterations) <= 0.7 * int(base.iterations), (
        int(base.iterations), int(cheb.iterations))
    np.testing.assert_allclose(np.asarray(cheb.x), np.asarray(x_true),
                               rtol=5e-3, atol=5e-3)


def test_jacobi_identity_on_normalized_family():
    """The paper's operators are pre-normalized: Jacobi must be a no-op."""
    cf = stencil.poisson((6, 6, 6))
    b = stencil.rhs_for_solution(
        cf, jax.random.normal(jax.random.PRNGKey(1), (6, 6, 6), jnp.float32))
    plain = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=200)
    jac = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=200, precond="jacobi")
    assert int(plain.iterations) == int(jac.iterations)
    np.testing.assert_allclose(np.asarray(plain.x), np.asarray(jac.x),
                               rtol=1e-6, atol=1e-6)


def test_raw_heterogeneous_matches_dense_oracle():
    cf = stencil.heterogeneous_poisson(jax.random.PRNGKey(3), (5, 5, 4))
    assert cf.diag is not None
    v = jax.random.normal(jax.random.PRNGKey(4), (5, 5, 4), jnp.float32)
    A = stencil.to_dense(cf)
    u = A @ np.asarray(v, np.float64).ravel()
    np.testing.assert_allclose(np.asarray(stencil.apply_ref(cf, v)).ravel(),
                               u, rtol=1e-4, atol=1e-4)
    unit, diag = cf.normalized()
    assert unit.diag is None
    np.testing.assert_allclose(
        np.asarray(stencil.apply_ref(unit, v)).ravel(),
        (A / np.asarray(diag, np.float64).ravel()[:, None]
         @ np.asarray(v, np.float64).ravel()),
        rtol=1e-4, atol=1e-4)


def test_jacobi_warm_start_is_translated_to_hat_space():
    """A warm start near the solution must help a right-Jacobi solve exactly
    as it helps the unpreconditioned one: wrap_right's hat-space iterate is
    ``x_hat = D x``, so solvers must hand it ``D x0``, not ``x0`` (the
    legacy-path bug that made SIMPLE's truncated inner solves stall)."""
    shape = (10, 10, 8)
    cf = stencil.heterogeneous_poisson(jax.random.PRNGKey(3), shape)
    x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    near = x_true + 1e-4 * jnp.ones_like(x_true)
    cold = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=3000, precond="jacobi")
    warm = bicgstab.solve_ref(cf, b, x0=near, tol=1e-8, maxiter=3000,
                              precond="jacobi")
    assert bool(warm.converged)
    assert int(warm.iterations) < int(cold.iterations), (
        int(cold.iterations), int(warm.iterations))
    np.testing.assert_allclose(np.asarray(warm.x), np.asarray(x_true),
                               rtol=5e-3, atol=5e-3)


def test_jacobi_cuts_heterogeneous_iterations():
    shape = (12, 12, 8)
    cf = stencil.heterogeneous_poisson(jax.random.PRNGKey(3), shape,
                                       contrast=2.0)
    x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    base = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=3000)
    jac = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=3000, precond="jacobi")
    assert bool(base.converged) and bool(jac.converged)
    assert int(jac.iterations) <= 0.7 * int(base.iterations), (
        int(base.iterations), int(jac.iterations))
    np.testing.assert_allclose(np.asarray(jac.x), np.asarray(x_true),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("specname", ["star7", "star25", "box27"])
def test_preconditioned_solve_across_family(specname):
    """Chebyshev-preconditioned BiCGStab agrees with the dense oracle for
    every stencil shape."""
    shape = (6, 8, 6) if specname != "star25" else (8, 9, 8)
    spec = stencil.get_spec(specname)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, spec=spec)
    x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    res = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=500,
                             precond=PrecondConfig(name="chebyshev", degree=2))
    assert bool(res.converged), specname
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_distributed_preconditioned_solve(subproc):
    """Preconditioned BiCGStab inside shard_map (bounds reduced over the
    fabric with pmax) matches the manufactured solution, on both the SPMD
    and Pallas-fused backends."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bicgstab, precision, stencil
        from repro.core.precond import PrecondConfig
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (16, 16, 8)
        cf = stencil.poisson(shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        base = bicgstab.solve_distributed(mesh, cf, b, tol=1e-6, maxiter=500,
                                          policy=precision.F32)
        for backend in ("spmd", "pallas"):
            res = bicgstab.solve_distributed(
                mesh, cf, b, tol=1e-6, maxiter=500, policy=precision.F32,
                backend=backend,
                precond=PrecondConfig(name="chebyshev", degree=3))
            assert bool(res.converged), backend
            assert int(res.iterations) < int(base.iterations), backend
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                       rtol=5e-3, atol=5e-3)
        print('OK')
    """)
