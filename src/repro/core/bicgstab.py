"""Solver drivers: wire mesh + operator backend + preconditioner + solver.

The algorithm bodies live in ``core/solvers/``, the SpMV backends in
``core/operator.py``, preconditioning in ``core/precond.py``; this module
wires them:

* :func:`solve_ref`          — single-device solve (oracle);
* :func:`solve_distributed`  — the paper's run: the whole Krylov iteration
  inside one ``shard_map``, any registered solver x backend x precond;
* :func:`make_iteration_fn`  — one SPMD BiCGStab iteration (the unit the
  paper measures and the dry run lowers): the solver's own step;
* :func:`solve_refined`      — bf16 inner solves + f32 iterative refinement.

The two distributed drivers build their operator through one helper
(:func:`_local_operator`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import get_schedule
from repro.core.halo import FabricAxes
from repro.core.operator import make_operator
from repro.core.precision import Policy, F32, MIXED
from repro.core.precond import PrecondConfig, build_precond, get_precond_config
from repro.core.solvers import bicgstab as bicgstab_solvers, get_solver
from repro.core.solvers.common import EPS, SolveResult
from repro.core.stencil import StencilCoeffs, apply_ref


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


# ---------------------------------------------------------------------------
# Distributed (shard_map) entry points — the paper's implementation
# ---------------------------------------------------------------------------

def _local_operator(mesh, *, backend: str, policy: Policy, schedule,
                    fused_reductions: bool, interpret: bool | None):
    """``(fabric, build)``: the mesh's fabric and ``build(cf_local)``, which
    makes the backend's operator over a local coefficient shard inside
    ``shard_map`` under the resolved halo schedule (a name or
    :class:`~repro.core.comm.CommSchedule`; None is ``overlap``)."""
    sched = get_schedule(schedule)
    fabric = FabricAxes.from_mesh(mesh)
    if backend == "reference" and mesh.devices.size > 1:
        # the reference backend has no halo exchange and local-only dots:
        # inside shard_map each shard would silently solve an unrelated
        # zero-Dirichlet sub-problem
        raise ValueError(
            "backend='reference' is single-address-space only; use "
            "backend='spmd' or 'pallas' on a multi-device mesh "
            "(or solve_ref on the undistributed arrays)")

    def build(cf_local):
        return make_operator(
            backend, cf_local, fabric, policy=policy,
            schedule=sched, fused_reductions=fused_reductions,
            interpret=interpret)

    return fabric, build


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
    schedule=None,
    record_history: bool = False,
    solver: str = "bicgstab",
    backend: str = "spmd",
    precond: str | PrecondConfig | None = None,
    interpret: bool | None = None,
) -> SolveResult:
    """A Krylov solve with the entire iteration inside one ``shard_map``.

    The fabric sees exactly the paper's traffic: one bidirectional face
    exchange per mesh axis per SpMV and 3 (fused) or 5 (paper-faithful
    separate) scalar AllReduces per BiCGStab iteration — 1 with the
    pipelined solvers (``solver="pipelined_bicgstab"/"pipelined_cg"``).
    With ``backend="pallas"`` the local work additionally runs as the fused
    stencil + vector-update Pallas kernels.

    ``schedule`` ("blocking" | "overlap" or a ``CommSchedule``,
    ``core.comm.SCHEDULES``; default overlap) picks the halo schedule —
    ``overlap`` issues the ppermutes first and hides them under the
    interior apply, bit-identical to ``blocking``.

    ``precond`` ("none" | "jacobi" | "chebyshev" | "mg" | a PrecondConfig)
    applies on the right (CG: as the textbook PCG), local work only, so
    the collective schedule is unchanged; "mg" runs on one device only.

    Block (many-RHS) solves: pass ``b`` with a leading batch axis
    ``(B,) + coeffs.shape``.  The batch axis is replicated (each shard owns
    its block of every RHS), halo slabs of all B RHS ride each ppermute
    message, every sync point reduces the stacked ``[k, B]`` partials in
    one AllReduce, and the returned SolveResult carries per-RHS ``[B]``
    iteration counts / flags / residuals.  The collective count per
    iteration is independent of B.
    """
    fabric, build = _local_operator(
        mesh, backend=backend, policy=policy, schedule=schedule,
        fused_reductions=fused_reductions, interpret=interpret)
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
        op = build(cf_local)
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
    schedule=None,
    backend: str = "spmd",
    interpret: bool | None = None,
):
    """One BiCGStab iteration as a standalone SPMD function.

    This is the unit the paper measures (28.1 us/iter on the CS-1) and the
    unit the dry-run lowers for the roofline: 2 halo-exchange SpMVs, 6 AXPYs,
    4 inner products, 3 (fused) or 5 (separate) AllReduce points.  The body
    is the solver's own step (``core.solvers.bicgstab.dataflow``): with
    ``backend="pallas"`` the fused-kernel dataflow, so lowering it shows the
    3-AllReduce schedule of the wired fused iteration.

    Signature: (coeffs, x, r, p, r0, rho) -> (x, r, p, rho, res2).
    """
    fabric, build = _local_operator(
        mesh, backend=backend, policy=policy, schedule=schedule,
        fused_reductions=fused_reductions, interpret=interpret)

    def iteration(cf, x, r, p, r0, rho):
        op = build(cf)
        _, step = bicgstab_solvers.dataflow(op)
        x, r, p, rho, res2, _ = step(op, policy, x, r, p, r0, rho)
        return x, r, p, rho, res2

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
        rels.append(jnp.linalg.norm(r) / jnp.maximum(bnorm, EPS))
        d = inner(r.astype(inner_policy.storage))
        x = x + d.x.astype(jnp.float32)
    r = b.astype(jnp.float32) - apply32(x)
    rels.append(jnp.linalg.norm(r) / jnp.maximum(bnorm, EPS))
    return x, jnp.stack(rels)
