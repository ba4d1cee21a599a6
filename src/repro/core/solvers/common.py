"""Shared solver plumbing: SolveResult, safe division, the AXPY family, and
the while-loop / history-scan scaffolding every Krylov loop reuses.

Everything here composes inside jit and ``shard_map`` — carries are pytrees
of arrays, control flow is ``lax.while_loop`` (or ``lax.scan`` when a
residual history is recorded).

Batched (many-RHS) solves: every helper is vectorized over an optional
leading batch axis ``B``.  A batched solve carries per-RHS scalars —
``alpha``/``rho``/``res2`` become ``[B]`` arrays, the convergence and
breakdown flags ``[B]`` bools, the iteration counter an ``int32[B]`` — and
:func:`run_krylov` freezes each converged (or broken-down) RHS at its exit
state while the rest keep iterating, so per-RHS iteration counts are exact.
The ``B=1`` batched path is arithmetic-identical (bitwise) to the unbatched
path: the same ops run with a broadcast leading axis of extent 1.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.precision import Policy
from repro.obs import trace as obs_trace


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["x", "iterations", "rel_residual", "converged", "breakdown", "history"],
    meta_fields=[],
)
@dataclasses.dataclass
class SolveResult:
    """Uniform solver output (BiCGStab and CG alike — drivers and tests
    treat every registered solver identically)."""

    x: jax.Array
    iterations: jax.Array          # int32 (int32[B] for a batched solve)
    rel_residual: jax.Array        # f32, recurrence residual at exit ([B])
    converged: jax.Array           # bool ([B]): independent per-RHS masks
    breakdown: jax.Array           # bool ([B]): a recurrence denom vanished
    history: jax.Array | None = None  # f32[maxiter(, B)] rel residuals


EPS = 1e-30


def convergence_test(tol: float, bnorm2):
    """The uniform relative-residual predicate: ``res2 <= tol^2 * ||b||^2``.

    Every Krylov loop (generic and pipelined alike) tests its squared
    recurrence residual against the same threshold; sharing the closure
    keeps the convergence semantics identical across the registry instead
    of each loop re-deriving ``tol*tol*bnorm2`` inline.

    The threshold is computed in ``bnorm2``'s dtype: an f64 solve with a
    tolerance below f32 eps must not have ``tol*tol`` rounded (or flushed
    to zero) in float32.  ``bnorm2`` may be batched ([B]); the predicate
    is then elementwise per RHS.
    """
    t = jnp.asarray(tol, dtype=jnp.asarray(bnorm2).dtype)
    thresh = t * t * bnorm2

    def converged(res2):
        return res2 <= thresh

    return converged


def safe_div(num, den):
    """num/den plus a breakdown flag when the denominator vanished.

    Elementwise, so batched ([B]) numerators/denominators get independent
    per-RHS breakdown flags.
    """
    ok = jnp.abs(den) > EPS
    return jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0), ~ok


def bcast_scalar(a, x):
    """A per-RHS scalar (``[B]`` or 0-d) aligned against ``x`` for broadcast.

    Unbatched scalars pass through untouched; a ``[B]`` scalar against a
    ``(B, ...)`` vector gains trailing singleton axes so ``a * x`` scales
    each RHS by its own coefficient.
    """
    a = jnp.asarray(a)
    if a.ndim == 0 or a.ndim >= jnp.ndim(x):
        return a
    return a.reshape(a.shape + (1,) * (jnp.ndim(x) - a.ndim))


def axpy_family(policy: Policy):
    """AXPY family in compute precision (paper Table I: 6 HP AXPYs/iter),
    in the ``update`` scope."""
    c = policy.compute

    @obs_trace.scoped("update")
    def axpy(a, x, y):  # y + a*x
        ac = bcast_scalar(jnp.asarray(a).astype(c), x)
        return (y.astype(c) + ac * x.astype(c)).astype(policy.storage)

    @obs_trace.scoped("update")
    def axpy2(a, x, b, y, z):  # z + a*x + b*y
        ac = bcast_scalar(jnp.asarray(a).astype(c), x)
        bc = bcast_scalar(jnp.asarray(b).astype(c), y)
        return (
            z.astype(c) + ac * x.astype(c) + bc * y.astype(c)
        ).astype(policy.storage)

    return axpy, axpy2


def local_partial(a, b, policy: Policy, *, mesh_ndim: int | None = None):
    """One FMAC-style local inner-product partial, batch-aware.

    With ``mesh_ndim`` given, operands whose rank exceeds it carry a
    leading batch axis: each RHS slice gets its own ``policy.dot`` (the
    exact unbatched accumulation order, so ``B=1`` is bitwise identical)
    and the partial becomes a ``[B]`` row.
    """
    nb = 0 if mesh_ndim is None else jnp.ndim(a) - mesh_ndim
    if nb <= 0:
        return policy.dot(a, b)
    return jnp.stack([policy.dot(a[i], b[i]) for i in range(a.shape[0])])


def local_dots(pairs, policy: Policy, *, mesh_ndim: int | None = None):
    """Single-address-space reduction: stack of FMAC-style inner products.

    Batched operands (rank above ``mesh_ndim``) produce ``[B]`` rows, so
    the stack of one sync point is a single ``[k, B]`` array — the shape
    the distributed backends push through one fused AllReduce.
    """
    return jnp.stack(
        [local_partial(a, b, policy, mesh_ndim=mesh_ndim) for a, b in pairs])


def init_counters(conv0):
    """(iteration counter, breakdown flag) shaped like the convergence mask.

    Unbatched loops get the classic ``(int32 0, bool False)`` scalars; a
    batched loop (``conv0`` is ``bool[B]``) gets per-RHS counters/flags so
    :func:`run_krylov` can freeze each RHS independently.
    """
    conv0 = jnp.asarray(conv0)
    if conv0.ndim == 0:
        return jnp.int32(0), jnp.bool_(False)
    return jnp.zeros(conv0.shape, jnp.int32), jnp.zeros(conv0.shape, bool)


def _freeze_select(mask, new, old):
    """Per-leaf ``where(mask, new, old)`` with the mask broadcast from the
    leading (batch) axis — so a ``bool[B]`` mask selects whole RHS slices
    of ``(B, ...)`` leaves and elements of ``[B]`` scalar leaves alike."""
    m = mask
    if jnp.ndim(new) > jnp.ndim(mask):
        m = mask.reshape(mask.shape + (1,) * (jnp.ndim(new) - jnp.ndim(mask)))
    return jnp.where(m, new, old)


def run_krylov(step, init, *, maxiter: int, bnorm2, record_history: bool):
    """Drive a Krylov ``step`` to convergence.

    ``step(carry) -> carry`` advances one iteration; the carry contract is
    ``(i, x, *state, res2, conv, brk)`` — position 0 the iteration counter,
    the last three the squared residual, convergence and breakdown flags.

    Batched solves carry per-RHS flags (``bool[B]``): every iteration the
    step result is merged back per RHS, so a converged (or broken-down)
    RHS freezes at its exit state — its counter stops, its ``x``/residual
    stay put — while the still-active RHS keep iterating.  The loop exits
    only when no RHS remains active.

    Returns the final carry plus (optionally) the f32[maxiter(, B)]
    relative residual history: ``record_history=True`` switches the
    ``while_loop`` for a fixed-length ``scan`` whose inactive iterations
    freeze the carry.
    """
    batched = jnp.ndim(init[-2]) > 0

    if record_history:
        def scan_body(carry, _):
            active = ~(carry[-2] | carry[-1])
            new = step(carry)
            carry = jax.tree.map(
                functools.partial(_freeze_select, active), new, carry)
            rel = jnp.sqrt(carry[-3] / jnp.maximum(bnorm2, EPS))
            return carry, rel

        final, hist = jax.lax.scan(scan_body, init, None, length=maxiter)
        return final, hist

    if batched:
        def masked_step(carry):
            active = ~(carry[-2] | carry[-1])
            return jax.tree.map(
                functools.partial(_freeze_select, active), step(carry), carry)

        def cond(carry):
            i, *_rest, conv, brk = carry
            return jnp.any((i < maxiter) & ~conv & ~brk)

        return jax.lax.while_loop(cond, masked_step, init), None

    def cond(carry):
        i, *_rest, conv, brk = carry
        return (i < maxiter) & ~conv & ~brk

    return jax.lax.while_loop(cond, step, init), None


def finish(carry, bnorm2, history=None) -> SolveResult:
    """Assemble a SolveResult from a run_krylov final carry."""
    i, x, *_rest, res2, conv, brk = carry
    rel = jnp.sqrt(res2 / jnp.maximum(bnorm2, EPS))
    return SolveResult(x, i, rel, conv, brk, history=history)


def emit_solve_metrics(result: SolveResult, *, wall_s: float | None = None,
                       **labels):
    """Per-solve observability emission (iterations, per-RHS convergence,
    residual history) into the :mod:`repro.obs.metrics` registry.

    Safe to call anywhere: under jit/shard_map the result's fields are
    tracers and this silently no-ops — the drivers call it again on the
    concrete result, which is where the numbers actually land.  History
    semantics are solver-agnostic (see ``core/solvers/pipelined``:
    the pipelined loops realign their lag-1 recorded history), so
    ``history[k]`` is always the relative residual after iteration k+1.
    """
    from repro.obs import metrics as obs_metrics

    return obs_metrics.record_solve(result, wall_s=wall_s, **labels)
