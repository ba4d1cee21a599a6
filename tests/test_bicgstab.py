"""BiCGStab / CG solver behaviour tests (single-device oracle paths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import bicgstab, precision, stencil


def _problem(shape, seed=0, kind="random"):
    k = jax.random.PRNGKey(seed)
    if kind == "random":
        cf = stencil.random_nonsymmetric(k, shape)
    elif kind == "poisson":
        cf = stencil.poisson(shape)
    else:
        cf = stencil.convection_diffusion(shape)
    x_true = jax.random.normal(jax.random.PRNGKey(seed + 1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    return cf, x_true, b


@pytest.mark.parametrize("kind", ["random", "poisson", "convdiff"])
def test_converges_to_true_solution(kind):
    cf, x_true, b = _problem((6, 6, 6), kind=kind)
    res = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=400)
    assert bool(res.converged)
    assert not bool(res.breakdown)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true), rtol=2e-4, atol=2e-4)


def test_matches_numpy_solve():
    cf, _, b = _problem((4, 4, 4), seed=7)
    res = bicgstab.solve_ref(cf, b, tol=1e-10, maxiter=400)
    A = stencil.to_dense(cf)
    x_np = np.linalg.solve(A, np.asarray(b, np.float64).ravel()).reshape(b.shape)
    np.testing.assert_allclose(np.asarray(res.x), x_np, rtol=1e-4, atol=1e-4)


def test_true_residual_decreases():
    cf, _, b = _problem((6, 6, 6))
    res = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=400)
    r = np.asarray(b) - np.asarray(stencil.apply_ref(cf, res.x))
    assert np.linalg.norm(r) / np.linalg.norm(np.asarray(b)) < 1e-6


def test_zero_rhs_converges_immediately():
    cf, _, _ = _problem((4, 4, 4))
    res = bicgstab.solve_ref(cf, jnp.zeros((4, 4, 4), jnp.float32), tol=1e-8)
    assert bool(res.converged)
    assert int(res.iterations) == 0
    assert np.abs(np.asarray(res.x)).max() == 0.0


def test_warm_start_reduces_iterations():
    cf, x_true, b = _problem((6, 6, 6))
    cold = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=400)
    warm = bicgstab.solve_ref(
        cf, b, x0=x_true + 1e-4 * jnp.ones_like(x_true), tol=1e-8, maxiter=400
    )
    assert int(warm.iterations) < int(cold.iterations)


def test_history_mode_matches_loop_mode():
    cf, _, b = _problem((5, 5, 5))
    loop = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=60)
    hist = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=60, record_history=True)
    assert bool(hist.converged)
    np.testing.assert_allclose(np.asarray(loop.x), np.asarray(hist.x), rtol=1e-5, atol=1e-6)
    h = np.asarray(hist.history)
    # history is monotone-ish at the tail and frozen after convergence
    assert h[-1] <= 1e-8


def test_mixed_precision_true_residual_plateaus():
    """Paper Fig. 9: the 16-bit recurrence keeps 'converging' but the TRUE
    residual plateaus near 16-bit machine precision."""
    cf, _, b = _problem((8, 8, 8), kind="convdiff")
    res = bicgstab.solve_ref(
        cf, b.astype(jnp.bfloat16), tol=1e-12, maxiter=200, policy=precision.MIXED
    )
    r = np.asarray(b, np.float64) - np.asarray(
        stencil.apply_ref(cf.astype(jnp.float32), res.x.astype(jnp.float32)), np.float64
    )
    true_rel = np.linalg.norm(r) / np.linalg.norm(np.asarray(b, np.float64))
    # bf16 has ~8 mantissa bits => plateau well above f32 but solve is usable
    assert 1e-7 < true_rel < 5e-2


def test_iterative_refinement_recovers_f32_accuracy():
    cf, x_true, b = _problem((6, 6, 6), kind="convdiff")
    x, rels = bicgstab.solve_refined(
        cf, b, outer_iters=4, inner_maxiter=60, inner_policy=precision.MIXED
    )
    rels = np.asarray(rels)
    assert rels[-1] < 1e-5          # recovered past the bf16 plateau
    assert (np.diff(np.log10(rels + 1e-30)) < 0).all()  # monotone improvement
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_true), rtol=1e-3, atol=1e-3)


def test_cg_on_spd_poisson():
    cf, x_true, b = _problem((6, 6, 6), kind="poisson")
    res = bicgstab.solve_ref(cf, b, tol=1e-8, maxiter=400, solver="cg")
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true), rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(3, 7), seed=st.integers(0, 2**30),
    dominance=st.floats(1.1, 3.0),
)
def test_property_solver_beats_tolerance(n, seed, dominance):
    """For any diagonally-dominant stencil system, the solver's exit residual
    honors the requested tolerance (system invariant)."""
    shape = (n, n, n)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(seed), shape, dominance=dominance)
    x_true = jax.random.normal(jax.random.PRNGKey(seed + 1), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    res = bicgstab.solve_ref(cf, b, tol=1e-6, maxiter=500)
    assert bool(res.converged)
    r = np.asarray(b) - np.asarray(stencil.apply_ref(cf, res.x))
    assert np.linalg.norm(r) <= 5e-5 * max(np.linalg.norm(np.asarray(b)), 1e-30)
