"""Conjugate gradients (the symmetric/HPCG-flavored comparison solver),
generic over a LinearOperator and with full SolveResult parity.

Per-iteration reduction schedule (2 sync points vs BiCGStab's 3):

    ap = A p;        <p, ap>              (sync point 1)
    r+ = r - a*ap;   <r+, r+>  (norm)     (sync point 2)

Preconditioned (HPCG's ``CG_ref``), still 2 sync points:

    ap = A p;        <p, ap>                        (sync point 1)
    r+ = r - a*ap;   z = M^-1 r+;   <r+, z>, <r+, r+>   (sync point 2)

Breakdown is flagged when <p, Ap> vanishes (loss of positive-definiteness
— e.g. CG applied to a nonsymmetric stencil) or the rho recurrence
degenerates, mirroring the BiCGStab flags so drivers and tests treat both
solvers uniformly.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.core.precision import Policy, F32
from repro.core.solvers.common import (
    SolveResult, axpy_family, convergence_test, finish, init_counters,
    run_krylov, safe_div,
)
from repro.obs import trace as obs_trace


def cg_loop(
    apply_A: Callable,
    dots: Callable,
    b,
    x0=None,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
) -> SolveResult:
    """The algorithm body; composable inside jit/shard_map. Returns SolveResult."""
    axpy, _ = axpy_family(policy)
    b = b.astype(policy.storage)
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = x0.astype(policy.storage)
        r = axpy(jnp.float32(-1.0), apply_A(x), b)
    bnorm2, rho0 = dots([(b, b), (r, r)], policy)  # one setup sync point
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, p, rho, conv, brk = carry
        ap = apply_A(p)
        (pap,) = dots([(p, ap)], policy)
        alpha, bad1 = safe_div(rho, pap)
        x = axpy(alpha, p, x)
        r = axpy(-alpha, ap, r)
        (rho_new,) = dots([(r, r)], policy)
        beta, bad2 = safe_div(rho_new, rho)
        p = axpy(beta, p, r)
        conv = converged(rho_new)
        return i + 1, x, r, p, rho_new, conv, brk | bad1 | bad2

    conv0 = converged(rho0)
    i0, brk0 = init_counters(conv0)
    init = (i0, x, r, r, rho0, conv0, brk0)
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=hist)


def pcg_loop(
    apply_A: Callable,
    dots: Callable,
    apply_M: Callable,
    b,
    x0=None,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
) -> SolveResult:
    """The textbook preconditioned CG of HPCG's ``CG_ref``: ``z = M^-1 r``,
    ``rho = <r, z>``, ``p = z + beta p``, the iterate in real space.

    ``<r, r>`` for the convergence test rides in the same reduction as
    ``<r, z>``, so an iteration keeps CG's 2 sync points; the price is one
    application of ``M^-1`` to the final residual."""
    axpy, _ = axpy_family(policy)
    b = b.astype(policy.storage)
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = x0.astype(policy.storage)
        r = axpy(jnp.float32(-1.0), apply_A(x), b)
    z = apply_M(r)
    bnorm2, rz0, rr0 = dots([(b, b), (r, z), (r, r)], policy)  # setup sync point
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, p, rz, rr, conv, brk = carry
        ap = apply_A(p)
        (pap,) = dots([(p, ap)], policy)
        alpha, bad1 = safe_div(rz, pap)
        x = axpy(alpha, p, x)
        r = axpy(-alpha, ap, r)
        z = apply_M(r)
        rz_new, rr_new = dots([(r, z), (r, r)], policy)
        beta, bad2 = safe_div(rz_new, rz)
        p = axpy(beta, p, z)
        return i + 1, x, r, p, rz_new, rr_new, converged(rr_new), brk | bad1 | bad2

    conv0 = converged(rr0)
    i0, brk0 = init_counters(conv0)
    init = (i0, x, r, z, rz0, rr0, conv0, brk0)
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=hist)


def cg_solver(
    op,
    b,
    x0=None,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
    precond=None,
) -> SolveResult:
    """Registry entry point: CG over a LinearOperator.

    Without a preconditioner (or with the identity) this is
    :func:`cg_loop`.  With one it is the textbook PCG (:func:`pcg_loop`),
    valid for any symmetric positive definite ``M^-1`` — the V-cycle
    (``"mg"``) as well as the Chebyshev polynomial and Jacobi — and a warm
    start ``x0`` is taken as it is.
    """
    from repro.core.precond import IdentityPrecond

    if precond is None or isinstance(precond, IdentityPrecond):
        return cg_loop(op.apply, op.dots, b, x0, tol=tol, maxiter=maxiter,
                       policy=policy, record_history=record_history)
    return pcg_loop(op.apply, op.dots, obs_trace.scoped("precond")(precond.apply),
                    b, x0, tol=tol, maxiter=maxiter, policy=policy,
                    record_history=record_history)
