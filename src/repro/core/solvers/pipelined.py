"""Pipelined Krylov solvers: one fused AllReduce per iteration.

The generic loops synchronize at every recurrence dependency — BiCGStab 3
times per iteration (fused schedule), CG twice.  On a latency-bound fabric
those blocking reductions dominate (paper §IV-3 measures the CS-1's
AllReduce at 1.5 us *because* the fabric erases them; commodity fabrics
cannot).  The pipelined reformulations here restructure the recurrences so
every inner product of an iteration is formed from vectors already in hand
and reduced in a **single** fused AllReduce:

* :func:`pipelined_cg_loop` — Ghysels & Vanroose's pipelined CG.  The
  iteration's two dots (<r,r>, <w,r>) depend only on the carried vectors,
  not on the matvec ``q = A w``, so the one AllReduce is dependency-free of
  the SpMV and overlaps it outright.  One extra vector recurrence triple
  (z, s, p) trades memory traffic for the hidden latency.

* :func:`pipelined_bicgstab_loop` — single-reduction BiCGStab (the
  Yang-Brent "improved BiCGStab" family).  The alpha-/omega-chained dots
  are expanded through ``q = r - alpha s`` and ``y = z - alpha t`` (with
  ``z = A r``, ``t = A s`` maintained at zero extra SpMVs by the recurrence
  ``s' = z' + beta (s - omega t)``), so all 12 scalar ingredients of one
  iteration reduce in one fused AllReduce — down from 3, overlappable with
  the trailing SpMV pair.  Crucially, the cross-iteration scalars are
  *re-anchored* every reduction: ``rho = <r0, r>`` and the convergence norm
  ``<r, r>`` are fresh dots on the carried residual rather than recurrence
  expansions, so rounding drift cannot accumulate — the trajectory tracks
  classic BiCGStab to rounding level (the expansion survives only inside
  one iteration, for omega and beta).

Both return full :class:`~repro.core.solvers.common.SolveResult` parity
(history / breakdown flags) and run on every operator backend — the
reduction count is asserted from lowered HLO in ``tests/test_solvers.py``.

Two costs are inherent and documented rather than hidden: (1) convergence
is checked on the *carried* residual norm (the new residual's norm is not
known until the next iteration's reduction), so both solvers report one
iteration more than their generic counterparts, and the residual the scan
*records* at iteration k is the lag-1 carried norm.  So that metrics
emission is solver-agnostic, :func:`_align_history` shifts the recorded
history back into the generic solvers' semantics — ``history[k]`` is the
relative residual after iteration k+1 for every registered solver; the
final entry repeats the last *reduced* norm, because the residual after
the very last update is never reduced (that is the lag-1 cost itself).
(2) pipelined CG maintains ``w = A r`` purely by
recurrence, which bounds its attainable accuracy near ``sqrt(eps)`` of the
storage dtype (the classic Ghysels-Vanroose trade-off) — ask it for f32
tolerances of ~1e-5, not 1e-8.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.core.precision import Policy, F32
from repro.core.solvers.common import (
    SolveResult, axpy_family, convergence_test, finish, init_counters,
    run_krylov, safe_div,
)


def _align_history(hist):
    """Shift the lag-1 recorded history into generic-solver semantics.

    The pipelined scans record the *carried* residual: entry k is the norm
    of the residual after only k updates (entry 0 is ``||r0||``), one slot
    behind the generic loops' "residual after iteration k+1".  Dropping the
    leading entry and repeating the final reduced norm restores parity, so
    ``SolveResult.history[k]`` means the same thing for every solver (and
    ``rel_residual == history[iterations - 1]`` on convergence).  Converged
    entries are frozen by ``run_krylov``, so the repeated tail is exact
    there; on a maxiter exit it repeats the last norm the solver ever saw.
    Batched histories (``[maxiter, B]``) shift along the iteration axis.
    """
    if hist is None:
        return None
    return jnp.concatenate([hist[1:], hist[-1:]], axis=0)


def pipelined_bicgstab_loop(
    apply_A: Callable,
    dots: Callable,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
) -> SolveResult:
    """Single-reduction BiCGStab; composable inside jit/shard_map.

    Carried vectors: x, r, p plus the matvec images ``s = A p``,
    ``z = A r``, ``t = A s``.  Per iteration: one fused 12-dot AllReduce,
    2 SpMVs (``z' = A r'`` and ``t' = A s'`` — same count as classic
    BiCGStab), and 9 AXPY-class updates.
    """
    axpy, axpy2 = axpy_family(policy)
    st = policy.storage

    b = b.astype(st)
    if x0 is None:
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        x0 = x0.astype(st)
        r0 = axpy(jnp.float32(-1.0), apply_A(x0), b)

    # p0 = r0, so s0 = A p0 doubles as z0 = A r0 — setup costs 2 SpMVs and
    # ONE fused AllReduce (the generic loops' setup was folded to one too).
    s0 = apply_A(r0)
    t0 = apply_A(s0)
    bnorm2, rho0 = dots([(b, b), (r0, r0)], policy)
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, p, s, z, t, res2, conv, brk = carry
        # the single sync point: every scalar this iteration needs, formed
        # from vectors already in hand and reduced in one fused AllReduce.
        # rho and rr are *fresh* dots on the carried residual (re-anchor),
        # so scalar rounding never accumulates across iterations.
        (rho, rr, r0s, r0z, r0t, rz, sz, rt, st_, zz, zt, tt) = dots(
            [(r0, r), (r, r), (r0, s), (r0, z), (r0, t), (r, z), (s, z),
             (r, t), (s, t), (z, z), (z, t), (t, t)], policy)
        alpha, bad1 = safe_div(rho, r0s)
        # <q,y> and <y,y> via q = r - alpha s, y = z - alpha t
        qy = rz - alpha * (sz + rt) + alpha * alpha * st_
        yy = zz - 2.0 * alpha * zt + alpha * alpha * tt
        omega, bad2 = safe_div(qy, yy)
        # <r0,r'> = (rho - alpha<r0,s>) - omega(<r0,z> - alpha<r0,t>);
        # used only for this iteration's beta — next alpha re-anchors
        rho_new = (rho - alpha * r0s) - omega * (r0z - alpha * r0t)
        beta_frac, bad3 = safe_div(rho_new, rho)
        alpha_frac, bad4 = safe_div(alpha, omega)
        beta = beta_frac * alpha_frac
        # vector recurrences (classic BiCGStab updates + the A-image pair)
        q = axpy(-alpha, s, r)
        y = axpy(-alpha, t, z)
        x = axpy2(alpha, p, omega, q, x)
        r_new = axpy(-omega, y, q)
        p_new = axpy(beta, axpy(-omega, s, p), r_new)
        z_new = apply_A(r_new)
        s_new = axpy(beta, axpy(-omega, t, s), z_new)   # s' = A p' for free
        t_new = apply_A(s_new)
        conv = converged(rr)       # ||r||^2 of the carried (lag-1) residual
        brk = bad1 | bad2 | bad3 | bad4
        return (i + 1, x, r_new, p_new, s_new, z_new, t_new, rr, conv, brk)

    conv0 = converged(rho0)
    i0, brk0 = init_counters(conv0)
    init = (i0, x0, r0, r0, s0, s0, t0, rho0, conv0, brk0)
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=_align_history(hist))


def pipelined_cg_loop(
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
    """Ghysels-Vanroose pipelined CG; composable inside jit/shard_map.

    The fused (<r,r>, <w,r>) reduction shares no dependency with the
    iteration's only SpMV ``q = A w``, so the AllReduce genuinely hides
    under the matvec.  Convergence is checked on the carried gamma = <r,r>
    (one iteration lagged — see the module docstring).
    """
    axpy, _ = axpy_family(policy)
    st = policy.storage

    b = b.astype(st)
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b
    else:
        x = x0.astype(st)
        r = axpy(jnp.float32(-1.0), apply_A(x), b)
    w0 = apply_A(r)
    bnorm2, gamma0 = dots([(b, b), (r, r)], policy)
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, w, p, s, z, gamma_old, alpha_old, res2, conv, brk = carry
        gamma, delta = dots([(r, r), (w, r)], policy)    # the one AllReduce
        q = apply_A(w)                                   # overlapped SpMV
        first = i == 0
        beta_raw, badb = safe_div(gamma, gamma_old)
        beta = jnp.where(first, 0.0, beta_raw)
        corr, badc = safe_div(beta * gamma, alpha_old)
        alpha, bada = safe_div(gamma,
                               delta - jnp.where(first, 0.0, corr))
        z = axpy(beta, z, q)            # z = q + beta z   (= A s)
        s = axpy(beta, s, w)            # s = w + beta s   (= A p)
        p = axpy(beta, p, r)            # p = r + beta p
        x = axpy(alpha, p, x)
        r = axpy(-alpha, s, r)
        w = axpy(-alpha, z, w)          # w = A r by recurrence
        conv = converged(gamma)
        brk = brk | bada | (~first & (badb | badc))
        return i + 1, x, r, w, p, s, z, gamma, alpha, gamma, conv, brk

    zeros = jnp.zeros_like(b)
    conv0 = converged(gamma0)
    i0, brk0 = init_counters(conv0)
    # alpha_old shaped like gamma (per-RHS for batched solves) so the
    # while_loop carry structure is shape-stable
    init = (
        i0, x, r, w0, zeros, zeros, zeros,
        gamma0, jnp.ones_like(gamma0), gamma0,
        conv0, brk0,
    )
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=_align_history(hist))


def _right_preconditioned(loop, *, refuses=()):
    """``loop`` right-preconditioned; a preconditioner named in ``refuses``
    is an error."""
    def solver(op, b, x0=None, *, tol: float = 1e-6, maxiter: int = 200,
               policy: Policy = F32, record_history: bool = False,
               precond=None) -> SolveResult:
        from repro.core.precond import warm_start, wrap_right

        if getattr(precond, "name", None) in refuses:
            raise ValueError(
                f"{loop.__name__} preconditions on the right, which is CG only "
                f"for an M^-1 that commutes with A; {precond.name!r} does not: "
                f"use solver='cg', the textbook PCG")
        wrapped, unwrap = wrap_right(op, precond)
        res = loop(wrapped.apply, wrapped.dots, b, warm_start(precond, x0),
                   tol=tol, maxiter=maxiter, policy=policy,
                   record_history=record_history)
        return unwrap(res)
    return solver


#: Registry entry points (see core/solvers/__init__.py): right-
#: preconditioned like the generic solvers — the collective schedule
#: (1 AllReduce/iter) is untouched by any preconditioner.
pipelined_bicgstab_solver = _right_preconditioned(pipelined_bicgstab_loop)
pipelined_bicgstab_solver.__name__ = "pipelined_bicgstab_solver"
pipelined_cg_solver = _right_preconditioned(pipelined_cg_loop, refuses=("mg",))
pipelined_cg_solver.__name__ = "pipelined_cg_solver"
