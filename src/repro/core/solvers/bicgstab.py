"""Distributed BiCGStab (paper Alg. 1, §IV), generic over a LinearOperator.

The loop body is written once against the operator protocol and runs in
three modes that share every line of algorithm logic:

* reference: ``op.apply`` = dense-shift oracle, ``op.dots`` = local dots;
* SPMD:      ``op.apply`` = halo-exchange local apply, ``op.dots`` = psum
  over the fabric — the whole loop lives inside one ``shard_map`` so the
  collective schedule (this paper's subject) is exactly what we write;
* Pallas:    when the operator carries :class:`~repro.core.operator.FusedOps`
  the step switches to the fused-kernel dataflow — SpMV kernels plus fused
  update+dot passes producing *local partials*, reduced with
  ``op.reduce_partials`` so one iteration is exactly 3 AllReduces.

Reduction schedule per iteration (paper counts 4 dot products):

    s = A p;                <r0, s>                      (sync point 1)
    y = A q;                <q, y>, <y, y>               (sync point 2)
    r+ = q - w y;           <r0, r+>, <r+, r+>           (sync point 3)

The dots of sync points 1 and 2 pair the SpMV's output with its input or
with ``r0``: they come from the operator's SpMV-with-dots
(``LinearOperator.apply_dots``), whose partials ride in the SpMV's own
pass where the backend has such a kernel.

With fused reductions each sync point is one AllReduce => 3/iter; the
paper-faithful separate schedule is one blocking AllReduce per dot => 5/iter
(incl. the convergence norm).
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

from repro.core.precision import Policy, F32
from repro.core.solvers.common import (
    SolveResult, apply_then_dots, axpy_family, bcast_scalar, convergence_test,
    finish, init_counters, run_krylov, safe_div,
)
from repro.obs import trace as obs_trace


def bicgstab_loop(
    apply_A: Callable,
    dots: Callable,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
    axpy=None,
    axpy2=None,
    apply_dots=None,
):
    """The generic algorithm body; composable inside jit/shard_map.

    ``apply_A`` and ``dots`` are bare callables (the pre-operator surface,
    kept because ``solve_refined`` and external callers compose it freely);
    :func:`bicgstab_solver` adapts a LinearOperator onto it, passing its
    ``apply_dots`` for the SpMVs of sync points 1 and 2 (default: the SpMV,
    then ``dots``).
    """
    default_axpy, default_axpy2 = axpy_family(policy)
    axpy = axpy or default_axpy
    axpy2 = axpy2 or default_axpy2
    apply_dots = apply_dots or apply_then_dots(apply_A, dots, policy)

    b = b.astype(policy.storage)
    if x0 is None:
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        x0 = x0.astype(policy.storage)
        r0 = axpy(jnp.float32(-1.0), apply_A(x0), b)

    bnorm2, rho0 = dots([(b, b), (r0, r0)], policy)  # one setup sync point
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, p, rho, res2, conv, brk = carry
        s, (r0s,) = apply_dots(p, r0, ("w",))
        alpha, bad1 = safe_div(rho, r0s)
        q = axpy(-alpha, s, r)
        y, (qy, yy) = apply_dots(q, None, ("v", "u"))
        omega, bad2 = safe_div(qy, yy)
        x = axpy2(alpha, p, omega, q, x)
        r_new = axpy(-omega, y, q)
        rho_new, res2_new = dots([(r0, r_new), (r_new, r_new)], policy)
        beta_frac, bad3 = safe_div(rho_new, rho)
        alpha_frac, bad4 = safe_div(alpha, omega)
        beta = beta_frac * alpha_frac
        p = axpy(beta, axpy(-omega, s, p), r_new)
        conv = converged(res2_new)
        brk = bad1 | bad2 | bad3 | bad4
        return i + 1, x, r_new, p, rho_new, res2_new, conv, brk

    conv0 = converged(rho0)
    i0, brk0 = init_counters(conv0)
    init = (i0, x0, r0, r0, rho0, rho0, conv0, brk0)
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=hist)


def bicgstab_fused_loop(
    op,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
):
    """BiCGStab through the operator's fused Pallas passes (op.fused).

    Per iteration: 2 halo-exchange SpMV kernels, the fused update+dot
    kernels of ``kernels/fused_iter`` (each emitting f32 *local* partials
    alongside its vector output), and exactly three ``op.reduce_partials``
    AllReduces — the end-to-end wiring of the fused schedule into the
    distributed loop.

    ``update_q_dots`` recomputes ``q = r - alpha*s`` inside the kernel pass
    that forms the <q,y>/<y,y> partials: the SpMV needs q *before* y exists,
    so q is first formed inline as the SpMV input (identical arithmetic,
    bitwise-equal result) and the kernel then fuses the recompute with both
    dot partials in a single sweep instead of re-reading q from memory.
    """
    f = op.fused
    assert f is not None, "operator has no fused kernel ops (use bicgstab_loop)"
    st = policy.storage

    b = b.astype(st)
    if x0 is None:
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        x0 = x0.astype(st)
        r0 = (b.astype(policy.compute)
              - op.apply(x0).astype(policy.compute)).astype(st)

    bnorm2, rho0 = op.reduce_partials(
        [f.dot_partial(b, b), f.dot_partial(r0, r0)])  # one setup AllReduce
    converged = convergence_test(tol, bnorm2)

    def step(carry):
        i, x, r, p, rho, res2, conv, brk = carry
        s = op.apply(p)
        (r0s,) = op.reduce_partials([f.dot_partial(r0, s)])     # AllReduce 1
        alpha, bad1 = safe_div(rho, r0s)
        # SpMV input (kernel-identical); bcast aligns a per-RHS [B] alpha
        with obs_trace.scope("update"):
            q_in = r - bcast_scalar(alpha.astype(st), s) * s
        y = op.apply(q_in)
        q, qy, yy = f.update_q_dots(alpha, r, s, y)
        qy, yy = op.reduce_partials([qy, yy])                   # AllReduce 2
        omega, bad2 = safe_div(qy, yy)
        x, r_new, r0r, rr = f.update_xr_dots(alpha, omega, x, p, q, y, r0)
        rho_new, res2_new = op.reduce_partials([r0r, rr])       # AllReduce 3
        beta_frac, bad3 = safe_div(rho_new, rho)
        alpha_frac, bad4 = safe_div(alpha, omega)
        p = f.update_p(beta_frac * alpha_frac, omega, r_new, p, s)
        conv = converged(res2_new)
        brk = bad1 | bad2 | bad3 | bad4
        return i + 1, x, r_new, p, rho_new, res2_new, conv, brk

    conv0 = converged(rho0)
    i0, brk0 = init_counters(conv0)
    init = (i0, x0, r0, r0, rho0, rho0, conv0, brk0)
    final, hist = run_krylov(step, init, maxiter=maxiter, bnorm2=bnorm2,
                             record_history=record_history)
    return finish(final, bnorm2, history=hist)


def bicgstab_solver(
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
    """Registry entry point: BiCGStab over a LinearOperator.

    Right preconditioning (``A M^-1 y = b``, ``x = M^-1 y``) wraps the
    operator's apply and unwraps the returned iterate; residuals and the
    collective schedule are untouched.  Dispatches to the fused-kernel step
    when the operator provides one.
    """
    from repro.core.precond import warm_start, wrap_right

    wrapped, unwrap = wrap_right(op, precond)
    x0 = warm_start(precond, x0)
    if wrapped.fused is not None:
        res = bicgstab_fused_loop(
            wrapped, b, x0, tol=tol, maxiter=maxiter, policy=policy,
            record_history=record_history)
    else:
        res = bicgstab_loop(
            wrapped.apply, wrapped.dots, b, x0, tol=tol, maxiter=maxiter,
            policy=policy, record_history=record_history,
            apply_dots=wrapped.apply_dots)
    return unwrap(res)
