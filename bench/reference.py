"""The plain reference: a stencil operator with a unit diagonal, the relative
residual of an answer, and a textbook BiCGStab, in jax.numpy on global
arrays.  It imports nothing of the program under test.

``fields`` maps a neighbour offset (dx, dy, dz) to its coefficient field:
row p of ``A v`` is ``v[p] + sum_o fields[o][p] * v[p + o]``, with a
neighbour beyond the mesh edge contributing zero.  On a multi-chip mesh
the arrays stay sharded and XLA's partitioner moves the neighbours'
planes; nothing here exchanges halos by hand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def shift(v: jax.Array, off) -> jax.Array:
    """``out[p] = v[p + off]``, zero where ``p + off`` leaves the array."""
    for axis, o in enumerate(off):
        if o == 0:
            continue
        n = v.shape[axis]
        pad = [(0, 0)] * v.ndim
        pad[axis] = (0, o) if o > 0 else (-o, 0)
        start = o if o > 0 else 0
        v = lax.slice_in_dim(jnp.pad(v, pad), start, start + n, axis=axis)
    return v


def apply(fields: dict, v: jax.Array, dtype=F32) -> jax.Array:
    """``A v`` with every product and sum in ``dtype``."""
    u = v.astype(dtype)
    for off, cf in fields.items():
        u = u + cf.astype(dtype) * shift(v, off).astype(dtype)
    return u


def _chunk(n: int, limit: int = 32) -> int:
    return max(c for c in range(1, min(n, limit) + 1) if n % c == 0)


def _seam_rows(shape, z0, full, seams, radius):
    """True at the rows of a z-slab that read a neighbour across a seam:
    within ``radius`` of a multiple of ``block`` on a seam's axis, the
    mesh's own edges left out."""
    near = jnp.zeros(shape, bool)
    for axis, block in seams:
        i = lax.broadcasted_iota(jnp.int32, shape, axis) + (z0 if axis == 2 else 0)
        j = i % block
        near = near | (((j < radius) | (j >= block - radius))
                       & (i >= radius) & (i < full[axis] - radius))
    return near


@functools.partial(jax.jit, static_argnames=("seams",))
def residual_norms(fields: dict, x: jax.Array, b: jax.Array, seams=()) -> jax.Array:
    """``[||b - A x||^2, ||b||^2]`` in float32, then the same two sums over
    the rows next to the ``seams`` (``(axis, block)``: the mesh is cut into
    blocks of that many planes along the axis), over slabs of z-planes so
    that no full-size float32 temporary is made."""
    radius = max(abs(o) for off in fields for o in off)
    nz = x.shape[2]
    cz = _chunk(nz)
    xz = jnp.pad(x, [(0, 0), (0, 0), (radius, radius)])

    def slab(k, acc):
        z0 = k * cz
        window = lax.dynamic_slice_in_dim(xz, z0, cz + 2 * radius, axis=2).astype(F32)
        u = lax.slice_in_dim(window, radius, radius + cz, axis=2)
        for off, cf in fields.items():
            c = lax.dynamic_slice_in_dim(cf, z0, cz, axis=2).astype(F32)
            w = lax.slice_in_dim(shift(window, (off[0], off[1], 0)),
                                 radius + off[2], radius + off[2] + cz, axis=2)
            u = u + c * w
        bb = lax.dynamic_slice_in_dim(b, z0, cz, axis=2).astype(F32)
        r = bb - u
        seam = _seam_rows(r.shape, z0, x.shape, seams, radius)
        rs, bs = jnp.where(seam, r, 0.0), jnp.where(seam, bb, 0.0)
        return acc + jnp.stack([jnp.sum(r * r), jnp.sum(bb * bb),
                                jnp.sum(rs * rs), jnp.sum(bs * bs)])

    return lax.fori_loop(0, nz // cz, slab, jnp.zeros(4, F32))


def true_residual(fields: dict, x: jax.Array, b: jax.Array, seams=()) -> tuple:
    """``||b - A x|| / ||b||``, computed in float32 from the stored arrays,
    and the same over the rows next to the seams (None without seams)."""
    rr, bb, rs, bs = np.asarray(residual_norms(fields, x, b, seams), np.float64)
    return float(np.sqrt(rr / bb)), (float(np.sqrt(rs / bs)) if seams else None)


def comb(shape, spacing: int, shift: jax.Array, dtype) -> jax.Array:
    """1 at the points whose every coordinate is ``shift`` plus a multiple of
    ``spacing``, else 0.  With ``spacing`` over twice the stencil's radius
    no row of ``A c`` meets two of them: it holds the unit diagonal, one
    stored coefficient times 1, or 0, exact in every precision."""
    hit = jnp.ones(shape, bool)
    for axis in range(len(shape)):
        i = lax.broadcasted_iota(jnp.int32, shape, axis)
        hit = hit & ((i - shift[axis]) % spacing == 0)
    return hit.astype(dtype)


def operator_gap(fields: dict, c: jax.Array, u: jax.Array) -> float:
    """``||u - A c|| / ||A c - c||``: an SpMV's answer ``u`` on the comb ``c``
    against the reference's, over the reference's off-diagonal part."""
    gap = float(residual_norms(fields, c, u)[0])
    off = float(residual_norms(fields, c, c)[0])
    return float(np.sqrt(gap / off))


@functools.partial(jax.jit, static_argnames=("tol", "maxiter", "storage", "compute"))
def bicgstab(fields: dict, b: jax.Array, *, tol: float, maxiter: int,
             storage, compute) -> tuple:
    """Textbook BiCGStab from x0 = 0: vectors and coefficients stored in
    ``storage``, products and sums in ``compute``, inner products and
    scalars in float32.  An iteration whose scalars come out zero or not
    finite (a breakdown) is discarded and the loop stops there.  Returns
    ``(x, iterations)``."""
    cf = {o: c.astype(storage) for o, c in fields.items()}
    b = b.astype(storage)

    def mat(v):
        return apply(cf, v, compute).astype(storage)

    def dot(u, v):
        return jnp.sum(u.astype(F32) * v.astype(F32))

    def axpy(a, u, v):  # v + a u
        return (v.astype(compute) + a.astype(compute) * u.astype(compute)).astype(storage)

    bnorm2 = dot(b, b)
    init = (0, jnp.zeros_like(b), b, b, bnorm2, bnorm2, False)

    def cond(c):
        i, _, _, _, _, res2, broken = c
        return (i < maxiter) & (res2 > tol * tol * bnorm2) & ~broken

    def body(c):
        i, x, r, p, rho, res2, _ = c
        s = mat(p)
        alpha = rho / dot(b, s)
        q = axpy(-alpha, s, r)
        y = mat(q)
        omega = dot(q, y) / dot(y, y)
        x_new = axpy(omega, q, axpy(alpha, p, x))
        r_new = axpy(-omega, y, q)
        rho_new = dot(b, r_new)
        res2_new = dot(r_new, r_new)
        beta = (rho_new / rho) * (alpha / omega)
        p_new = axpy(beta, axpy(-omega, s, p), r_new)
        ok = (jnp.isfinite(jnp.stack([alpha, omega, beta, res2_new])).all()
              & (omega != 0) & (rho_new != 0))
        keep = lambda new, old: jnp.where(ok, new, old)
        return (i + ok.astype(int), keep(x_new, x), keep(r_new, r), keep(p_new, p),
                keep(rho_new, rho), keep(res2_new, res2), ~ok)

    i, x, *_ = lax.while_loop(cond, body, init)
    return x, i
