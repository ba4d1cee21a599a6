"""Preconditioners (beyond-paper: the iteration-count lever the WSE
follow-on work identifies — Woo et al., Jacquelin et al.).

Three families, all *local* operations so the per-iteration collective
schedule of the solve is unchanged:

* :class:`JacobiPrecond` — ``M^-1 = D^-1`` from the stencil's stored main
  diagonal.  The paper's operators are pre-normalized (unit diagonal — the
  paper itself applies Jacobi by construction, "we only store six other
  diagonals"), so Jacobi is the identity for them; it does real work for
  *raw* operators that carry a variable diagonal
  (``stencil.heterogeneous_poisson``).  Zero setup, zero extra SpMVs.

* :class:`ChebyshevPrecond` — a degree-d Chebyshev polynomial approximation
  of ``A^-1`` on a spectral interval ``[lmin, lmax]`` (the classic
  Chebyshev semi-iteration with zero initial guess, the hypre/AMG smoother
  recurrence).  Costs d-1 extra SpMVs per application — local halo
  exchanges only, **no extra AllReduces** — and repays them by clustering
  the spectrum, cutting the outer (AllReduce-bearing) iteration count.
  Bounds default to fabric-reduced Gershgorin estimates with a relative
  floor on ``lmin``.

* :class:`~repro.core.multigrid.MGPrecond` (``"mg"``) — HPCG's multigrid
  V-cycle: 4 levels, one symmetric 8-colour Gauss–Seidel sweep before and
  after each coarse correction, injection both ways (``core/multigrid``).
  Symmetric positive definite but not a polynomial in ``A``: CG applies it
  as the textbook PCG (``core/solvers/cg``), BiCGStab on the right.  One
  device only.

Preconditioners are built *inside* the shard_map body (they close over
local coefficient shards and the operator's local apply); the static
choices (name, degree, floor, explicit bounds) travel in a
:class:`PrecondConfig` resolved by the driver.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

from repro.core.multigrid import MGPrecond, build_levels
from repro.core.operator import LinearOperator
from repro.core.solvers.common import SolveResult
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class PrecondConfig:
    """Static (trace-time) preconditioner choices.

    ``lmin``/``lmax`` override the Gershgorin estimate when given;
    ``lmin_floor`` keeps the Chebyshev interval away from a zero Gershgorin
    lower bound (the weakly-dominant Poisson case) — eigenvalues below the
    floor are left to the outer Krylov solver as isolated outliers.
    """

    name: str = "none"
    degree: int = 3
    lmin: float | None = None
    lmax: float | None = None
    lmin_floor: float = 0.05

    def __post_init__(self):
        if self.name not in PRECONDS:
            raise ValueError(f"unknown preconditioner {self.name!r}; "
                             f"have {sorted(PRECONDS)}")
        if self.degree < 1:
            raise ValueError(f"chebyshev degree must be >= 1, got {self.degree}")


def get_precond_config(name_or_config, **overrides) -> PrecondConfig:
    """Normalize a CLI string / None / config into a PrecondConfig."""
    if name_or_config is None:
        name_or_config = "none"
    if isinstance(name_or_config, PrecondConfig):
        return (dataclasses.replace(name_or_config, **overrides)
                if overrides else name_or_config)
    return PrecondConfig(name=name_or_config, **overrides)


# ---------------------------------------------------------------------------
# The preconditioners
# ---------------------------------------------------------------------------

class IdentityPrecond:
    name = "none"

    def apply(self, v):
        return v


@dataclasses.dataclass(frozen=True)
class JacobiPrecond:
    """Right diagonal scaling: ``M^-1 v = v / diag``."""

    inv_diag: jnp.ndarray
    storage: jnp.dtype
    compute: jnp.dtype
    name: str = "jacobi"

    def apply(self, v):
        return (v.astype(self.compute)
                * self.inv_diag.astype(self.compute)).astype(self.storage)

    def apply_inv(self, v):
        """``M v`` — exact inverse of :meth:`apply`, used to translate warm
        starts into hat space (see :func:`warm_start`)."""
        return (v.astype(self.compute)
                / self.inv_diag.astype(self.compute)).astype(self.storage)


@dataclasses.dataclass(frozen=True)
class ChebyshevPrecond:
    """``M^-1 v ~= A^-1 v`` via the degree-d Chebyshev semi-iteration.

    Standard three-term recurrence for solving ``A z = v`` from ``z0 = 0``
    with the spectrum enclosed in ``[lmin, lmax]`` (d=1 degenerates to
    ``v / theta``, the scaled-identity smoother).  All work is SpMVs and
    AXPYs — halo exchanges, no reductions.
    """

    apply_A: Callable
    degree: int
    lmin: jnp.ndarray
    lmax: jnp.ndarray
    storage: jnp.dtype
    compute: jnp.dtype
    name: str = "chebyshev"

    def apply(self, v):
        c, st = self.compute, self.storage
        theta = jnp.float32((self.lmax + self.lmin) / 2)
        delta = jnp.float32((self.lmax - self.lmin) / 2)
        sigma1 = theta / delta
        rho = 1.0 / sigma1
        r = v.astype(c)
        d = r * (1.0 / theta).astype(c)
        z = d
        for _ in range(1, self.degree):
            r = r - self.apply_A(d.astype(st)).astype(c)
            rho_new = 1.0 / (2.0 * sigma1 - rho)
            d = (rho_new * rho).astype(c) * d + (2.0 * rho_new / delta).astype(c) * r
            z = z + d
            rho = rho_new
        return z.astype(st)


PRECONDS = ("none", "jacobi", "chebyshev", "mg")


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def gershgorin_bounds(coeffs):
    """Local Gershgorin disc bounds (min over rows of d - R, max of d + R).

    Traceable (pure jnp) so the distributed path can reduce the local
    extrema over the fabric with the operator's ``reduce_max``.
    """
    s = None
    for cf in coeffs.diags.values():
        a = jnp.abs(cf.astype(jnp.float32))
        s = a if s is None else s + a
    d = (coeffs.diag.astype(jnp.float32) if coeffs.diag is not None
         else jnp.ones_like(s))
    return jnp.min(d - s), jnp.max(d + s)


def build_precond(config: PrecondConfig, op: LinearOperator):
    """Instantiate a preconditioner against an operator (inside shard_map)."""
    if config.name == "none":
        return IdentityPrecond()
    pol = op.policy
    if config.name == "jacobi":
        if op.coeffs.diag is None:
            return IdentityPrecond()  # the family is already unit-diagonal
        return JacobiPrecond(inv_diag=1.0 / op.coeffs.diag.astype(jnp.float32),
                             storage=pol.storage, compute=pol.compute)
    if config.name == "mg":
        return MGPrecond(build_levels(op))
    # chebyshev
    if config.lmin is not None and config.lmax is not None:
        lmin = jnp.float32(config.lmin)
        lmax = jnp.float32(config.lmax)
    else:
        lo, hi = gershgorin_bounds(op.coeffs)
        lmax = op.reduce_max(hi) if config.lmax is None else jnp.float32(config.lmax)
        if config.lmin is None:
            lmin = -op.reduce_max(-lo)
            lmin = jnp.maximum(lmin, config.lmin_floor * lmax)
        else:
            lmin = jnp.float32(config.lmin)
    return ChebyshevPrecond(apply_A=op.apply, degree=config.degree,
                            lmin=lmin, lmax=lmax,
                            storage=pol.storage, compute=pol.compute)


def warm_start(precond, x0):
    """Translate a real-space warm start into hat space: ``x0_hat = M x0``.

    Solvers hand ``x0`` to the hat system ``A M^-1``, whose iterate is
    ``x_hat = M x``; a preconditioner with an exact ``apply_inv`` therefore
    maps the guess so the initial residual is ``b - A x0``, exactly the
    unpreconditioned start (truncated inner solves — e.g. SIMPLE's 5-iter
    momentum solves — rely on this, or every solve restarts from ``M^-1
    x0`` instead of ``x0``).  Preconditioners without an inverse (Chebyshev)
    use the guess as-is: any hat-space start is valid, just not warm.
    """
    if x0 is None or precond is None:
        return x0
    apply_inv = getattr(precond, "apply_inv", None)
    return x0 if apply_inv is None else apply_inv(x0)


def wrap_right(op: LinearOperator, precond):
    """Right-precondition an operator: returns ``(wrapped_op, unwrap)``.

    ``wrapped_op.apply(v) = A(M^-1 v)`` (the hat system — residuals,
    convergence test and collective schedule are identical to the
    unpreconditioned solve); ``unwrap`` maps a hat-space SolveResult back,
    ``x = M^-1 x_hat``.  A warm start ``x0`` is interpreted in hat space;
    solvers translate real-space guesses with :func:`warm_start`.
    """
    if precond is None or isinstance(precond, IdentityPrecond):
        return op, lambda res: res

    apply_m = obs_trace.scoped("precond")(precond.apply)
    wrapped = op.with_apply(lambda v: op.apply(apply_m(v)))

    def unwrap(res: SolveResult) -> SolveResult:
        return dataclasses.replace(res, x=precond.apply(res.x))

    return wrapped, unwrap
