"""Batched (many-RHS) solver-stack tests: the block-Krylov batch axis.

The contract under test, layer by layer:

* ``B=1`` batched is **bitwise identical** to the unbatched path (the
  acceptance bar — same ops, broadcast leading axis of extent 1), for
  every backend and both comm schedules.
* Per-RHS solves in a batch behave independently: exact per-RHS
  iteration counts, independent convergence/breakdown masks, and a
  converged RHS freezes at its exit state while the rest keep iterating.
* The fused batched reductions produce per-RHS scalars bitwise equal to
  running each RHS through the unbatched kernels.
* The collective schedule is batch-invariant: one body AllReduce per
  pipelined iteration whether B is 1 or 4 (HLO-asserted, slow tier).

A note on B>1 vs per-RHS-solo comparisons: the *eager* batched step is
bitwise per-RHS (``local_partial`` unrolls per-RHS dots in unbatched
accumulation order), and the generic loops stay bitwise through
``lax.while_loop`` too.  The pipelined BiCGStab body, with its 12
shared-operand dots, gets fused differently by XLA for the (B, ...) vs
(...) graphs (FMA/fusion rounding), so its B>1 trajectory is asserted
allclose rather than bitwise — B=1 vs unbatched stays exact.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bicgstab, precision, stencil
from repro.core.halo import FabricAxes
from repro.core.solvers.common import convergence_test

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPE = (8, 8, 6)


def _run_snippet(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout


def _problem(B=None, seed=1, shape=SHAPE):
    cf = stencil.poisson(shape)
    xshape = shape if B is None else (B,) + shape
    x_true = jax.random.normal(jax.random.PRNGKey(seed), xshape, jnp.float32)
    return cf, stencil.rhs_for_solution(cf, x_true), x_true


# ---------------------------------------------------------------------------
# Layer 1: the reference apply
# ---------------------------------------------------------------------------

def test_apply_ref_batched_bitwise():
    cf, b, _ = _problem(B=3)
    u = stencil.apply_ref(cf, b)
    assert u.shape == b.shape
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(u[i]),
                                      np.asarray(stencil.apply_ref(cf, b[i])))


def test_local_apply_batched_matches_ref():
    """The halo layer's padded apply on a degenerate fabric, batched."""
    from repro.core.halo import local_apply

    cf, b, _ = _problem(B=2)
    u = local_apply(cf, b, FabricAxes())
    np.testing.assert_array_equal(np.asarray(u),
                                  np.asarray(stencil.apply_ref(cf, b)))


# ---------------------------------------------------------------------------
# Layer 5: solver semantics (reference backend, eager)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver", ["bicgstab", "cg", "pipelined_cg",
                                    "pipelined_bicgstab"])
def test_b1_batched_bitwise_identical_to_unbatched(solver):
    """The acceptance bar: a (1, ...) solve IS the unbatched solve."""
    cf, b, _ = _problem()
    kw = dict(tol=1e-5, maxiter=60, policy=precision.F32, solver=solver)
    ru = bicgstab.solve_ref(cf, b, **kw)
    rb = bicgstab.solve_ref(cf, b[None], **kw)
    assert rb.x.shape == (1,) + SHAPE
    np.testing.assert_array_equal(np.asarray(rb.x[0]), np.asarray(ru.x))
    assert int(rb.iterations[0]) == int(ru.iterations)
    assert bool(rb.converged[0]) == bool(ru.converged)
    np.testing.assert_array_equal(np.asarray(rb.rel_residual[0]),
                                  np.asarray(ru.rel_residual))


@pytest.mark.parametrize("solver", ["bicgstab", "cg", "pipelined_cg"])
def test_batched_matches_per_rhs_solo_bitwise(solver):
    """Each RHS of a B=3 block solve reproduces its solo solve exactly —
    iterations, x, and residual (the per-RHS freeze keeps a converged RHS
    untouched while the others iterate on)."""
    cf, b, _ = _problem(B=3)
    kw = dict(tol=1e-5, maxiter=80, policy=precision.F32, solver=solver)
    rb = bicgstab.solve_ref(cf, b, **kw)
    for i in range(3):
        ri = bicgstab.solve_ref(cf, b[i], **kw)
        assert int(rb.iterations[i]) == int(ri.iterations)
        np.testing.assert_array_equal(np.asarray(rb.x[i]), np.asarray(ri.x))
        np.testing.assert_array_equal(np.asarray(rb.rel_residual[i]),
                                      np.asarray(ri.rel_residual))
    # RHS are genuinely different problems: counts must not be all equal
    # by construction (guards against an accidental lock-step loop)
    assert rb.iterations.shape == (3,)


def test_pipelined_bicgstab_batched_tracks_solo():
    """B>1 pipelined BiCGStab: XLA fuses the batched while-body with
    different rounding (see module docstring), so solo agreement is
    allclose; iteration counts must still match exactly."""
    cf, b, x_true = _problem(B=2)
    kw = dict(tol=1e-5, maxiter=80, policy=precision.F32,
              solver="pipelined_bicgstab")
    rb = bicgstab.solve_ref(cf, b, **kw)
    for i in range(2):
        ri = bicgstab.solve_ref(cf, b[i], **kw)
        assert int(rb.iterations[i]) == int(ri.iterations)
        np.testing.assert_allclose(np.asarray(rb.x[i]), np.asarray(ri.x),
                                   rtol=1e-4, atol=1e-4)
    assert bool(rb.converged.all())
    np.testing.assert_allclose(np.asarray(rb.x), np.asarray(x_true),
                               rtol=2e-3, atol=2e-3)


def test_converged_rhs_freezes_while_others_iterate():
    """A zero RHS converges at iteration 0 (x stays zero, counter stays 0)
    while the live RHS runs its full solo trajectory next to it."""
    cf, b1, _ = _problem()
    b = jnp.stack([jnp.zeros_like(b1), b1])
    kw = dict(tol=1e-5, maxiter=80, policy=precision.F32, solver="bicgstab")
    rb = bicgstab.solve_ref(cf, b, **kw)
    assert int(rb.iterations[0]) == 0 and bool(rb.converged[0])
    assert not np.any(np.asarray(rb.x[0]))
    ri = bicgstab.solve_ref(cf, b1, **kw)
    assert int(rb.iterations[1]) == int(ri.iterations)
    np.testing.assert_array_equal(np.asarray(rb.x[1]), np.asarray(ri.x))


def test_batched_history_shape_and_freeze():
    cf, b, _ = _problem(B=2)
    maxiter = 30
    rb = bicgstab.solve_ref(cf, b, tol=1e-5, maxiter=maxiter,
                            policy=precision.F32, record_history=True)
    h = np.asarray(rb.history)
    assert h.shape == (maxiter, 2)
    # after an RHS converges its history freezes at the exit residual
    for i in range(2):
        k = int(rb.iterations[i])
        assert np.all(h[k:, i] == h[k, i])


def test_batched_breakdown_mask_is_per_rhs():
    """A singular operator row drives breakdown for the RHS that excites
    it; batched next to a healthy Poisson solve both flags stay honest."""
    cf, b1, _ = _problem()
    kw = dict(tol=1e-12, maxiter=5, policy=precision.F32, solver="bicgstab")
    b = jnp.stack([b1, 2.0 * b1])
    rb = bicgstab.solve_ref(cf, b, **kw)
    assert rb.breakdown.shape == (2,) and rb.converged.shape == (2,)
    assert not bool(rb.breakdown.any())


# ---------------------------------------------------------------------------
# Layer 3/4: the fused-kernel backend, degenerate fabric (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["blocking", "overlap"])
@pytest.mark.parametrize("backend", ["spmd", "pallas"])
def test_b1_bitwise_backends_degenerate_fabric(backend, schedule):
    """B=1 == unbatched for the distributed backends on the 1x1 fabric
    (the full multi-device check is the slow subprocess test below)."""
    cf, b, _ = _problem()
    kw = dict(tol=1e-5, maxiter=40, policy=precision.F32,
              backend=backend, schedule=schedule)
    ru = bicgstab.solve_ref(cf, b, **kw)
    rb = bicgstab.solve_ref(cf, b[None], **kw)
    np.testing.assert_array_equal(np.asarray(rb.x[0]), np.asarray(ru.x))
    assert int(rb.iterations[0]) == int(ru.iterations)


def test_pallas_backend_batched_matches_solo():
    cf, b, _ = _problem(B=2)
    kw = dict(tol=1e-5, maxiter=40, policy=precision.F32, backend="pallas")
    rb = bicgstab.solve_ref(cf, b, **kw)
    for i in range(2):
        ri = bicgstab.solve_ref(cf, b[i], **kw)
        assert int(rb.iterations[i]) == int(ri.iterations)
        np.testing.assert_array_equal(np.asarray(rb.x[i]), np.asarray(ri.x))


def test_fused_iter_batched_ops_bitwise():
    """Every fused_iter wrapper: batched rows == per-RHS unbatched rows,
    vectors and scalar partials alike."""
    from repro.kernels.fused_iter.ops import (
        dot_mixed, update_p, update_q_dots, update_xr_dots)

    B, n = 3, int(np.prod(SHAPE))
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r, s, y, x, p, r0 = [jax.random.normal(k, (B,) + SHAPE, jnp.float32)
                         for k in ks]
    alpha = jnp.linspace(0.5, 1.5, B)
    omega = jnp.linspace(0.2, 0.8, B)
    beta = jnp.linspace(-0.3, 0.4, B)

    qb, qyb, yyb = update_q_dots(alpha, r, s, y, interpret=True, batched=True)
    xb, rb, r0rb, rrb = update_xr_dots(alpha, omega, x, p, qb, y, r0,
                                       interpret=True, batched=True)
    pb = update_p(beta, omega, rb, p, s, interpret=True, batched=True)
    db = dot_mixed(r, s, interpret=True, batched=True)
    for i in range(B):
        qi, qyi, yyi = update_q_dots(alpha[i], r[i], s[i], y[i],
                                     interpret=True)
        xi, ri, r0ri, rri = update_xr_dots(alpha[i], omega[i], x[i], p[i],
                                           qi, y[i], r0[i], interpret=True)
        pi = update_p(beta[i], omega[i], ri, p[i], s[i], interpret=True)
        di = dot_mixed(r[i], s[i], interpret=True)
        for got, want in ((qb[i], qi), (qyb[i], qyi), (yyb[i], yyi),
                          (xb[i], xi), (rb[i], ri), (r0rb[i], r0ri),
                          (rrb[i], rri), (pb[i], pi), (db[i], di)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stencil_nd_pallas_batched_bitwise():
    from repro.kernels.stencil_nd.ops import stencil_apply

    spec = stencil.STAR7
    cf, b, _ = _problem(B=2)
    ub = stencil_apply(cf, b, spec=spec, interpret=True)
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(ub[i]),
            np.asarray(stencil_apply(cf, b[i], spec=spec, interpret=True)))


# ---------------------------------------------------------------------------
# Satellites: f64 tolerance regression, deprecation shim
# ---------------------------------------------------------------------------

def test_convergence_test_threshold_dtype():
    """In-process half of the f64 regression: the threshold must be formed
    in bnorm2's dtype, not hard-cast to f32 (satellite bugfix)."""
    conv = convergence_test(1e-3, jnp.float32(4.0))
    assert bool(conv(jnp.float32(3.9e-6)))
    assert not bool(conv(jnp.float32(4.1e-6)))


def test_convergence_test_f64_tiny_tol_subprocess():
    """Under x64, a tolerance far below f32 eps must survive squaring —
    the old f32 hard-cast flushed ``tol*tol`` to 0 and never converged."""
    code = (
        "import jax; jax.config.update('jax_enable_x64', True)\n"
        "import jax.numpy as jnp\n"
        "from repro.core.solvers.common import convergence_test\n"
        "conv = convergence_test(1e-25, jnp.float64(1.0))\n"
        "assert bool(conv(jnp.float64(1e-51))), 'tiny f64 tol flushed'\n"
        "assert not bool(conv(jnp.float64(1e-49)))\n"
        "print('OK')\n"
    )
    assert "OK" in _run_snippet(code)


def test_stencil7_deprecation_warning_fires_once():
    """The shim import warns exactly once per process and keeps the legacy
    names importable."""
    code = (
        "import warnings\n"
        "with warnings.catch_warnings(record=True) as w:\n"
        "    warnings.simplefilter('always')\n"
        "    import repro.kernels.stencil7 as s7\n"
        "    import repro.kernels.stencil7  # second import: cached, no warn\n"
        "hits = [x for x in w if issubclass(x.category, DeprecationWarning)\n"
        "        and 'stencil_nd' in str(x.message)]\n"
        "assert len(hits) == 1, [str(x.message) for x in w]\n"
        "assert callable(s7.stencil7_apply) and callable(s7.stencil7_pallas)\n"
        "print('OK')\n"
    )
    assert "OK" in _run_snippet(code)


# ---------------------------------------------------------------------------
# Multi-device: B=1 bitwise + batch-invariant collectives (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_b1_bitwise_all_backends(subproc):
    """Acceptance: B=1 batched == unbatched on a real 2x4 fabric for both
    distributed backends x both schedules, and B=4 matches the reference
    solve (the reference backend's B=1 identity is tier-1, in-process)."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (8, 8, 6)
        cf = stencil.poisson(shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        kw = dict(tol=1e-5, maxiter=40, policy=precision.F32)
        for backend in ("spmd", "pallas"):
            for schedule in ("blocking", "overlap"):
                ru = bicgstab.solve_distributed(mesh, cf, b, backend=backend,
                                                schedule=schedule, **kw)
                rb = bicgstab.solve_distributed(mesh, cf, b[None],
                                                backend=backend,
                                                schedule=schedule, **kw)
                np.testing.assert_array_equal(np.asarray(rb.x[0]),
                                              np.asarray(ru.x))
                assert int(rb.iterations[0]) == int(ru.iterations), (
                    backend, schedule)
        # a real block solve converges to the manufactured solutions
        xt4 = jax.random.normal(jax.random.PRNGKey(2), (4,) + shape,
                                jnp.float32)
        b4 = stencil.rhs_for_solution(cf, xt4)
        r4 = bicgstab.solve_distributed(mesh, cf, b4, tol=1e-7, maxiter=200,
                                        policy=precision.F32)
        assert bool(r4.converged.all())
        np.testing.assert_allclose(np.asarray(r4.x), np.asarray(xt4),
                                   rtol=2e-4, atol=2e-4)
        print('OK')
    """)


@pytest.mark.slow
def test_batched_collective_count_is_batch_invariant(subproc):
    """Acceptance: a jitted B=4 pipelined_bicgstab solve lowers to exactly
    1 body AllReduce per iteration — the same totals as B=1 — and the
    ppermute count does not grow with B either."""
    subproc("""
        import jax, jax.numpy as jnp
        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)
        shape = (8, 8, 8)
        cf = stencil.poisson(shape)
        for solver, per_iter_want in (("pipelined_bicgstab", 1),
                                      ("bicgstab", 3)):
            counts = {}
            for B in (1, 4):
                b = jnp.ones((B,) + shape, jnp.float32)
                f = lambda c, bb: bicgstab.solve_distributed(
                    mesh, c, bb, tol=0.0, maxiter=8, policy=precision.F32,
                    solver=solver, schedule="overlap")
                text = jax.jit(f).lower(cf, b).as_text()
                counts[B] = (
                    text.count('all_reduce') + text.count('all-reduce'),
                    text.count('collective_permute')
                    + text.count('collective-permute'))
            assert counts[1] == counts[4], (solver, counts)
            # setup folds into one AllReduce; the loop body is emitted once
            assert counts[1][0] - 1 == per_iter_want, (solver, counts)
        print('OK')
    """, n_devices=4)
