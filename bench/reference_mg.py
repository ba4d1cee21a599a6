"""The plain reference of HPCG's solve: preconditioned CG (``CG_ref``) with
the multigrid V-cycle (``ComputeMG_ref``) and symmetric Gauss–Seidel
(``ComputeSYMGS_ref``), in float32 jax.numpy on global arrays.  It
imports nothing of the program under test.

``fields`` maps a neighbour offset to its coefficient field, with a unit
diagonal, as in ``bench/reference.py``.  Departures from HPCG's reference:

* float32 throughout, where HPCG computes in float64;
* the Gauss–Seidel sweep visits the points in 8-colour order, the colour
  being the parities of the (x, y, z) index; the forward sweep takes
  (1, 1, 1) first and the coarse points' colour (0, 0, 0) last, the
  backward sweep the reverse (HPCG's reference sweeps in row order and
  lets an optimised code reorder the sweep so);
* the matrix is HPCG's divided by its diagonal 26 (unit diagonal,
  off-diagonals -1/26): a scalar that changes neither the CG nor the
  Gauss–Seidel iterates;
* ``<r, r>`` is reduced with ``<r, z>`` after the V-cycle, so the last
  iteration applies the V-cycle once more than HPCG's loop.

Each colour is swept the textbook way, ``x_c <- (r - (A - I) x)_c`` on
the rows of that colour, one whole SpMV a colour.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import reference

F32 = jnp.float32
#: HPCG's constants
LEVELS = 4
#: the colours, as (x, y, z) parities, in the order of the forward sweep
FORWARD = tuple((a, b, c) for a in (1, 0) for b in (1, 0) for c in (1, 0))


def mask(shape, colour) -> jax.Array:
    """True at the points of ``colour`` (three parities, traced or not)."""
    hit = jnp.ones(shape, bool)
    for axis in range(3):
        hit = hit & (lax.broadcasted_iota(jnp.int32, shape, axis) % 2 == colour[axis])
    return hit


def sweep(fields: dict, r: jax.Array, x: jax.Array, order) -> jax.Array:
    """One Gauss–Seidel sweep of ``A x = r``, the colours in ``order``."""
    colours = jnp.asarray(order, jnp.int32)

    def colour(k, x):
        off_diagonal = reference.apply(fields, x) - x
        return jnp.where(mask(x.shape, colours[k]), r - off_diagonal, x)

    return lax.fori_loop(0, len(order), colour, x)


def symgs(fields: dict, r: jax.Array, x: jax.Array) -> jax.Array:
    """``ComputeSYMGS``: the forward sweep, then the backward one."""
    return sweep(fields, r, sweep(fields, r, x, FORWARD), FORWARD[::-1])


def even(a: jax.Array) -> jax.Array:
    return a[::2, ::2, ::2]


def vcycle(fields: dict, r: jax.Array, levels: int = LEVELS) -> jax.Array:
    """``ComputeMG``: ``z = M^-1 r`` from a zero start."""
    x = symgs(fields, r, jnp.zeros_like(r))
    if levels > 1:
        rc = even(r - reference.apply(fields, x))
        xc = vcycle({o: even(f) for o, f in fields.items()}, rc, levels - 1)
        x = symgs(fields, r, x.at[::2, ::2, ::2].add(xc))
    return x


def _highest(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapper


@_highest
@functools.partial(jax.jit, static_argnames=("levels",))
def apply_vcycle(fields: dict, r: jax.Array, levels: int = LEVELS) -> jax.Array:
    """One V-cycle, float32."""
    fields = {o: f.astype(F32) for o, f in fields.items()}
    return vcycle(fields, r.astype(F32), levels)


@_highest
@functools.partial(jax.jit, static_argnames=("order",))
def apply_sweep(fields: dict, r: jax.Array, x: jax.Array, order) -> jax.Array:
    """One sweep in colour ``order``, float32."""
    fields = {o: f.astype(F32) for o, f in fields.items()}
    return sweep(fields, r.astype(F32), x.astype(F32), order)


@_highest
@functools.partial(jax.jit, static_argnames=("tol", "maxiter", "levels"))
def pcg(fields: dict, b: jax.Array, *, tol: float, maxiter: int,
        levels: int = LEVELS) -> tuple:
    """``CG_ref`` from x0 = 0, preconditioned by the V-cycle, to the
    relative recurrence residual ``tol``.  Returns ``(x, iterations,
    history)``, ``history[k]`` the relative residual after iteration
    ``k + 1`` (frozen after the last)."""
    fields = {o: f.astype(F32) for o, f in fields.items()}
    b = b.astype(F32)

    def dot(u, v):
        return jnp.sum(u * v)

    bnorm2 = dot(b, b)
    z = vcycle(fields, b, levels)
    init = (0, jnp.zeros_like(b), b, z, dot(b, z), bnorm2,
            jnp.zeros(maxiter, F32))

    def cond(c):
        i, *_, rr, _ = c
        return (i < maxiter) & (rr > tol * tol * bnorm2)

    def body(c):
        i, x, r, p, rz, _, hist = c
        ap = reference.apply(fields, p)
        alpha = rz / dot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = vcycle(fields, r, levels)
        rz_new, rr = dot(r, z), dot(r, r)
        p = z + (rz_new / rz) * p
        return i + 1, x, r, p, rz_new, rr, hist.at[i].set(jnp.sqrt(rr / bnorm2))

    i, x, *_, hist = lax.while_loop(cond, body, init)
    last = hist[jnp.maximum(i - 1, 0)]
    hist = jnp.where(jnp.arange(maxiter) < i, hist, last)
    return x, i, hist
