"""jit'd wrappers: flatten the mesh block to (rows, 128), pad, dispatch, and
reshape back.  Zero padding is exact for every fused op (pads contribute 0 to
dots and are sliced off the vector outputs).

``batched=True`` flattens each RHS of a ``(B, mesh...)`` operand to its own
(rows, 128) plane and returns per-RHS ``[B]`` scalars for the dot partials
(the solver stacks one sync point's partials into a single ``[k, B]``
AllReduce); an unbatched operand is the B = 1 case of the same kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

LANES = 128
DEFAULT_BM = 512


def _to_rows(a: jax.Array, n_batch: int = 0):
    """``a`` as (B, rows, 128) — one zero-padded row plane per RHS (B = 1
    without a batch axis) — and the row block ``bm`` of the sweep."""
    B = a.shape[0] if n_batch else 1
    n = a.size // B
    rows = -(-n // LANES)
    bm = min(DEFAULT_BM, rows)
    rows_pad = -(-rows // bm) * bm
    flat = jnp.pad(a.reshape(B, -1), ((0, 0), (0, rows_pad * LANES - n)))
    return flat.reshape(B, rows_pad, LANES), bm


def _like(flat: jax.Array, a: jax.Array, n_batch: int = 0):
    B = a.shape[0] if n_batch else 1
    return flat.reshape(B, -1)[:, : a.size // B].reshape(a.shape)


def _scalar(x, n_batch: int):
    """Per-RHS (B,) scalars -> ``[B]`` when batched, else a 0-d scalar."""
    x = x.reshape(-1)
    return x if n_batch else x[0]


@functools.partial(jax.jit, static_argnames=("interpret", "batched"))
def update_q_dots(alpha, r, s, y, *, interpret: bool | None = None,
                  batched: bool = False):
    from repro.kernels.fused_iter.kernel import update_q_dots_pallas
    nb = 1 if batched else 0
    r2, bm = _to_rows(r, nb)
    s2, _ = _to_rows(s, nb)
    y2, _ = _to_rows(y, nb)
    q2, qy, yy = update_q_dots_pallas(jnp.asarray(alpha), r2, s2, y2, bm=bm,
                                      interpret=resolve_interpret(interpret))
    return _like(q2, r, nb), _scalar(qy, nb), _scalar(yy, nb)


@functools.partial(jax.jit, static_argnames=("interpret", "batched"))
def update_xr_dots(alpha, omega, x, p, q, y, r0, *,
                   interpret: bool | None = None, batched: bool = False):
    from repro.kernels.fused_iter.kernel import update_xr_dots_pallas
    nb = 1 if batched else 0
    arrs = [_to_rows(a, nb)[0] for a in (x, p, q, y, r0)]
    bm = _to_rows(x, nb)[1]
    xo, ro, r0r, rr = update_xr_dots_pallas(
        jnp.asarray(alpha), jnp.asarray(omega), *arrs, bm=bm,
        interpret=resolve_interpret(interpret))
    return (_like(xo, x, nb), _like(ro, x, nb),
            _scalar(r0r, nb), _scalar(rr, nb))


@functools.partial(jax.jit, static_argnames=("interpret", "batched"))
def update_p(beta, omega, r, p, s, *, interpret: bool | None = None,
             batched: bool = False):
    from repro.kernels.fused_iter.kernel import update_p_pallas
    nb = 1 if batched else 0
    r2, bm = _to_rows(r, nb)
    p2, _ = _to_rows(p, nb)
    s2, _ = _to_rows(s, nb)
    po = update_p_pallas(jnp.asarray(beta), jnp.asarray(omega), r2, p2, s2,
                         bm=bm, interpret=resolve_interpret(interpret))
    return _like(po, r, nb)


@functools.partial(jax.jit, static_argnames=("interpret", "batched"))
def dot_mixed(a, b, *, interpret: bool | None = None, batched: bool = False):
    from repro.kernels.fused_iter.kernel import dot_mixed_pallas
    nb = 1 if batched else 0
    a2, bm = _to_rows(a, nb)
    b2, _ = _to_rows(b, nb)
    out = dot_mixed_pallas(a2, b2, bm=bm,
                           interpret=resolve_interpret(interpret))
    return _scalar(out, nb)
