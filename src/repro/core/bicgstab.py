"""Solver drivers: wire mesh + operator backend + preconditioner + solver.

This module is the glue layer (and the historical import surface — the
algorithm bodies moved to ``core/solvers/``, the SpMV backends to
``core/operator.py``, preconditioning to ``core/precond.py``):

* :func:`solve_ref`          — single-address-space solve (oracle);
* :func:`solve_distributed`  — the paper's run: the whole Krylov iteration
  inside one ``shard_map``, any registered solver x backend x precond;
* :func:`make_iteration_fn`  — one SPMD iteration (the unit the paper
  measures and the dry-run lowers);
* :func:`solve_refined`      — bf16 inner solves + f32 iterative refinement;
* :func:`solve_ref_fused`    — single-block BiCGStab through the fused
  stencil7 dot-epilogue kernels (the per-chip reference schedule).

Legacy names (``bicgstab_loop``, ``cg_loop``, ``SolveResult``, ...) are
re-exported so existing callers and tests keep working.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import SCHEDULES, get_schedule  # noqa: F401
from repro.core.halo import FabricAxes
from repro.core.operator import BACKENDS, make_operator  # noqa: F401
from repro.core.precision import Policy, F32, MIXED
from repro.core.precond import PrecondConfig, build_precond, get_precond_config
from repro.core.solvers import SOLVERS, get_solver  # noqa: F401
from repro.core.solvers.bicgstab import bicgstab_fused_loop, bicgstab_loop  # noqa: F401
from repro.core.solvers.cg import cg_loop  # noqa: F401
from repro.core.solvers.common import (  # noqa: F401
    EPS as _EPS,
    SolveResult,
    axpy_family as _axpys,
    local_dots as _local_dots,
    safe_div as _safe_div,
)
from repro.core.stencil import StencilCoeffs, apply_ref
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Reference (single address space) entry point
# ---------------------------------------------------------------------------

def solve_ref(
    coeffs: StencilCoeffs,
    b: jax.Array,
    x0: jax.Array | None = None,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = F32,
    record_history: bool = False,
    solver: str = "bicgstab",
    backend: str = "reference",
    precond: str | PrecondConfig | None = None,
    schedule: str | None = None,
) -> SolveResult:
    """Single-device oracle solve (used by tests and small examples).

    ``backend="pallas"`` runs the same solve through the fused kernels on a
    1x1 fabric (all collectives degenerate) — the single-block fused path.
    ``schedule`` picks the comm schedule for the distributed backends
    (degenerate here, but the apply structure is exercised).
    """
    op = make_operator(backend, coeffs, policy=policy, schedule=schedule)
    M = build_precond(get_precond_config(precond), op)
    return get_solver(solver)(
        op, b, x0, tol=tol, maxiter=maxiter, policy=policy,
        record_history=record_history, precond=M)


def cg_ref(coeffs: StencilCoeffs, b, **kw):
    """CG oracle (kept for the historical call sites)."""
    return solve_ref(coeffs, b, solver="cg",
                     **{k: v for k, v in kw.items() if k != "x0"})


def solve_ref_fused(
    coeffs: StencilCoeffs,
    b: jax.Array,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    interpret: bool | None = None,
):
    """BiCGStab evaluated entirely through the fused Pallas schedule
    (EXPERIMENTS §Perf stencil v3): SpMV+dot epilogues and fused
    update+dot passes — 31 words/meshpoint/iteration instead of 42.

    Single-block (per-chip) reference; the distributed solver composes the
    same vector kernels via ``backend="pallas"``.  Python loop (not
    lax.while) because pallas_call is re-traced per call in interpret mode.
    """
    from repro.kernels import resolve_interpret
    from repro.kernels.fused_iter import update_p, update_xr_dots
    from repro.kernels.stencil_nd.fused import stencil7_dot, stencil7_two_dots

    interpret = resolve_interpret(interpret)
    x = jnp.zeros_like(b)
    r = b
    p = b
    r0 = b
    bnorm2 = float(jnp.vdot(b.astype(jnp.float32), b.astype(jnp.float32)))
    rho = jnp.float32(bnorm2)
    n_iter = 0
    rel = 1.0
    for n_iter in range(1, maxiter + 1):
        s, r0s = stencil7_dot(coeffs, p, r0, interpret=interpret)   # pass 1
        alpha = rho / r0s
        q = r - alpha.astype(r.dtype) * s                            # pass 2
        y, qy, yy = stencil7_two_dots(coeffs, q, interpret=interpret)  # pass 3
        omega = qy / yy
        x, r, rho_new, rr = update_xr_dots(alpha, omega, x, p, q, y, r0,
                                           interpret=interpret)      # pass 4
        beta = (alpha / omega) * (rho_new / rho)
        p = update_p(beta, omega, r, p, s, interpret=interpret)      # pass 5
        rho = rho_new
        rel = float(jnp.sqrt(rr / bnorm2))
        if rel < tol:
            break
    return SolveResult(x, jnp.int32(n_iter), jnp.float32(rel),
                       jnp.bool_(rel < tol), jnp.bool_(False))


# ---------------------------------------------------------------------------
# Distributed (shard_map) entry point — the paper's implementation
# ---------------------------------------------------------------------------

def solve_distributed(
    mesh,
    coeffs: StencilCoeffs,
    b: jax.Array,
    x0: jax.Array | None = None,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    policy: Policy = MIXED,
    fused_reductions: bool = True,
    overlap_halo: bool | None = None,
    schedule: str | None = None,
    record_history: bool = False,
    solver: str = "bicgstab",
    backend: str = "spmd",
    precond: str | PrecondConfig | None = None,
    interpret: bool | None = None,
    apply_impl: Callable | None = None,
) -> SolveResult:
    """A Krylov solve with the entire iteration inside one ``shard_map``.

    The fabric sees exactly the paper's traffic: one bidirectional face
    exchange per mesh axis per SpMV and 3 (fused) or 5 (paper-faithful
    separate) scalar AllReduces per BiCGStab iteration — 1 with the
    pipelined solvers (``solver="pipelined_bicgstab"/"pipelined_cg"``).
    With ``backend="pallas"`` the local work additionally runs as the fused
    stencil + vector-update Pallas kernels.

    ``schedule`` ("blocking" | "overlap", ``core.comm.SCHEDULES``) picks
    the halo schedule — ``overlap`` issues the ppermutes first and hides
    them under the interior apply, bit-identical to ``blocking``.  The
    legacy ``overlap_halo`` boolean spells the same choice and loses ties.

    ``precond`` ("none" | "jacobi" | "chebyshev" | "mg" | a PrecondConfig)
    applies on the right (CG: as the textbook PCG), local work only, so
    the collective schedule is unchanged; "mg" runs on one device only.
    ``apply_impl`` is the legacy hook swapping the local SpMV for a custom
    kernel.

    Block (many-RHS) solves: pass ``b`` with a leading batch axis
    ``(B,) + coeffs.shape``.  The batch axis is replicated (each shard owns
    its block of every RHS), halo slabs of all B RHS ride each ppermute
    message, every sync point reduces the stacked ``[k, B]`` partials in
    one AllReduce, and the returned SolveResult carries per-RHS ``[B]``
    iteration counts / flags / residuals.  The collective count per
    iteration is independent of B.
    """
    sched = get_schedule(schedule if schedule is not None else overlap_halo)
    fabric = FabricAxes.from_mesh(mesh)
    if backend == "reference" and mesh.devices.size > 1:
        # the reference backend has no halo exchange and local-only dots:
        # inside shard_map each shard would silently solve an unrelated
        # zero-Dirichlet sub-problem
        raise ValueError(
            "backend='reference' is single-address-space only; use "
            "backend='spmd' or 'pallas' on a multi-device mesh "
            "(or solve_ref on the undistributed arrays)")
    nb = b.ndim - coeffs.ndim       # leading batch (many-RHS) axes
    spec = fabric.spec(coeffs.ndim, n_batch=nb)
    cf_spec = fabric.spec(coeffs.ndim)
    cf = coeffs.astype(policy.storage)
    pconf = get_precond_config(precond)
    if pconf.name == "mg" and mesh.devices.size > 1:
        # HPCG's V-cycle sweeps one process's block; split over chips, each
        # sweep would need its own halo exchange: a different smoother
        raise ValueError("precond='mg' runs on one device only; "
                         f"the mesh has {mesh.devices.size}")
    solver_fn = get_solver(solver)

    def solve_fn(cf_local, b_local, x0_local):
        op = make_operator(
            backend, cf_local, fabric, policy=policy,
            schedule=sched, fused_reductions=fused_reductions,
            interpret=interpret)
        if apply_impl is not None:
            op = op.with_apply(obs_trace.scoped("spmv")(lambda v: apply_impl(
                op.coeffs, v, fabric, policy=policy, overlap=sched.overlap_halo)))
        M = build_precond(pconf, op)
        return solver_fn(op, b_local, x0_local, tol=tol, maxiter=maxiter,
                         policy=policy, record_history=record_history,
                         precond=M)

    scalar = P()
    out_specs = SolveResult(
        x=spec, iterations=scalar, rel_residual=scalar,
        converged=scalar, breakdown=scalar,
        history=(scalar if record_history else None),
    )
    if x0 is None:
        x0 = jnp.zeros_like(b)
    mapped = jax.shard_map(
        solve_fn, mesh=mesh,
        in_specs=(cf_spec, spec, spec),
        out_specs=out_specs,
        # Pallas applies produce ShapeDtypeStructs without vma metadata;
        # out_specs above are explicit, so the vma checker adds nothing here.
        check_vma=False,
    )
    return mapped(cf, b, x0)


def make_iteration_fn(
    mesh,
    *,
    policy: Policy = MIXED,
    fused_reductions: bool = True,
    overlap_halo: bool | None = None,
    schedule: str | None = None,
    backend: str = "spmd",
    interpret: bool | None = None,
    apply_impl: Callable | None = None,
):
    """One BiCGStab iteration as a standalone SPMD function.

    This is the unit the paper measures (28.1 us/iter on the CS-1) and the
    unit the dry-run lowers for the roofline: 2 halo-exchange SpMVs, 6 AXPYs,
    4 inner products, 3 (fused) or 5 (separate) AllReduce points.  With
    ``backend="pallas"`` the body is the fused-kernel dataflow, so lowering
    it shows the 3-AllReduce schedule of the wired fused iteration.

    Signature: (coeffs, x, r, p, r0, rho) -> (x, r, p, rho, res2).
    """
    from repro.core.solvers.common import safe_div

    sched = get_schedule(schedule if schedule is not None else overlap_halo)
    fabric = FabricAxes.from_mesh(mesh)
    if backend == "reference" and mesh.devices.size > 1:
        raise ValueError(
            "backend='reference' is single-address-space only; use "
            "backend='spmd' or 'pallas' on a multi-device mesh")

    def iteration(cf, x, r, p, r0, rho):
        op = make_operator(
            backend, cf, fabric, policy=policy,
            schedule=sched, fused_reductions=fused_reductions,
            interpret=interpret)
        if apply_impl is not None:
            op = op.with_apply(obs_trace.scoped("spmv")(lambda v: apply_impl(
                op.coeffs, v, fabric, policy=policy, overlap=sched.overlap_halo)))
        axpy, axpy2 = _axpys(policy)
        if op.fused is not None:
            f = op.fused
            st = policy.storage
            s = op.apply(p)
            (r0s,) = op.reduce_partials([f.dot_partial(r0, s)])
            alpha, _ = safe_div(rho, r0s)
            with obs_trace.scope("update"):
                q_in = r - alpha.astype(st) * s
            y = op.apply(q_in)
            q, qy, yy = f.update_q_dots(alpha, r, s, y)
            qy, yy = op.reduce_partials([qy, yy])
            omega, _ = safe_div(qy, yy)
            x, r_new, r0r, rr = f.update_xr_dots(alpha, omega, x, p, q, y, r0)
            rho_new, res2 = op.reduce_partials([r0r, rr])
            beta_frac, _ = safe_div(rho_new, rho)
            alpha_frac, _ = safe_div(alpha, omega)
            p = f.update_p(beta_frac * alpha_frac, omega, r_new, p, s)
            return x, r_new, p, rho_new, res2
        s, (r0s,) = op.apply_dots(p, r0, ("w",))
        alpha, _ = safe_div(rho, r0s)
        q = axpy(-alpha, s, r)
        y, (qy, yy) = op.apply_dots(q, None, ("v", "u"))
        omega, _ = safe_div(qy, yy)
        x = axpy2(alpha, p, omega, q, x)
        r_new = axpy(-omega, y, q)
        rho_new, res2 = op.dots([(r0, r_new), (r_new, r_new)], policy)
        beta_frac, _ = safe_div(rho_new, rho)
        alpha_frac, _ = safe_div(alpha, omega)
        p = axpy(beta_frac * alpha_frac, axpy(-omega, s, p), r_new)
        return x, r_new, p, rho_new, res2

    spec = fabric.spec(3)
    scalar = P()
    return jax.shard_map(
        iteration, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, scalar),
        out_specs=(spec, spec, spec, scalar, scalar),
        check_vma=False,   # see solve_distributed: Pallas applies
    )


# ---------------------------------------------------------------------------
# Iterative refinement (beyond paper — §VI-B discussion made concrete)
# ---------------------------------------------------------------------------

def solve_refined(
    coeffs: StencilCoeffs,
    b: jax.Array,
    *,
    mesh=None,
    outer_iters: int = 4,
    inner_maxiter: int = 60,
    inner_tol: float = 1e-3,
    inner_policy: Policy = MIXED,
    tol: float = 1e-6,
):
    """fp32-accurate solutions from a bf16 inner solver.

    The paper observes the mixed-precision residual plateaus near machine-eps
    (Fig. 9) and points at iterative refinement [Carson-Higham] as the fix.
    We implement it: residuals and the solution accumulate in f32; each
    correction solve runs entirely in the 16-bit policy.
    """
    cf32 = coeffs.astype(jnp.float32)

    def inner(rhs):
        if mesh is None:
            return solve_ref(coeffs, rhs, tol=inner_tol, maxiter=inner_maxiter,
                             policy=inner_policy)
        return solve_distributed(mesh, coeffs, rhs, tol=inner_tol,
                                 maxiter=inner_maxiter, policy=inner_policy)

    if mesh is None:
        apply32 = functools.partial(apply_ref, cf32, policy=F32)
    else:
        from repro.core.halo import global_apply
        apply32 = functools.partial(global_apply, mesh, cf32, policy=F32)

    x = jnp.zeros_like(b, dtype=jnp.float32)
    bnorm = jnp.linalg.norm(b.astype(jnp.float32))
    rels = []
    for _ in range(outer_iters):
        r = b.astype(jnp.float32) - apply32(x)
        rels.append(jnp.linalg.norm(r) / jnp.maximum(bnorm, _EPS))
        d = inner(r.astype(inner_policy.storage))
        x = x + d.x.astype(jnp.float32)
    r = b.astype(jnp.float32) - apply32(x)
    rels.append(jnp.linalg.norm(r) / jnp.maximum(bnorm, _EPS))
    return x, jnp.stack(rels)
