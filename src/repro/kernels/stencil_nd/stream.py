"""Plane-streaming star-stencil SpMV: every plane of the iterate is read
from HBM once per apply.

``u = A v`` on an unpadded local block with zero-Dirichlet faces — what
``core.halo.interior_apply`` computes — for any star spec (offsets on the
axes, any radius r) and for the radius-1 box (27 points).  The grid walks the x planes in order, r steps ahead
of the output: step ``t`` receives plane ``t`` of the iterate through a
plane BlockSpec (the pipeline fetches plane ``t + 1`` meanwhile) and
widens it to f32 into a VMEM ring of ``2r + 1`` slots, then computes
output plane ``i = t - r`` from the ring's planes ``i - r .. i + r``.
Coefficient and output planes go through plane BlockSpecs indexed by
``i``.  Slots of planes outside the block hold zeros, so an x term past a
face multiplies zero, as the jnp shift does.

Each slot keeps :data:`ROW_BORDER` zero rows above and below the plane
and zero lanes past ``Z`` (its lane extent is rounded up past ``Z + r``).
A y term is a static row slice of an aligned row chunk of the centre
slot, a z term a lane roll of that chunk: the zero rows and lanes are the
faces, so no mask is needed.  A box's corner term (more than one nonzero
offset) is a row slice of the ``dx`` slot's aligned chunk, rolled in z.

Terms accumulate in f32 in the canonical order
(``StencilCoeffs.ordered_items``, the diagonal first) and round once to the
output dtype: the arithmetic of ``interior_apply`` with f32 compute.

The dot epilogue (``dots``) forms, beside ``u``, f32 partial inner
products of the stored output with itself, with the input (its plane is
in the ring) or with one more operand ``w`` (one more plane BlockSpec):
the products of the widened stored values, summed over the rows of each
output plane into an ``(X, len(dots), Z)`` block that the caller sums.
An inner product then needs no pass of its own over a vector the SpMV
already holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import name_offset
from repro.kernels import resolve_interpret
from repro.kernels.stencil_nd.kernel import LANES, VMEM_LIMIT_BYTES
from repro.obs import metrics as obs_metrics

#: zero rows above and below each ring slot's plane (a multiple of
#: the f32 sublane tile, so row chunks of the scratch load aligned).
ROW_BORDER = 8
#: f32 vregs one row chunk of the centre plane may span (16, 32 and 64
#: ran alike on a TPU v5e at 608^3 and 504x504x352).
CHUNK_VREGS = 32


def chunk_rows(Y: int, Zp: int, vregs: int = CHUNK_VREGS) -> int:
    """Rows per inner step: a multiple of 16 (a whole bf16 tile, so the
    ring and coefficient loads stay aligned) whose f32 chunk spans at most
    ``vregs`` vregs; at least 16, at most ``Y``."""
    lane_vregs = Zp // LANES
    rows = max(16, (vregs // lane_vregs) * 8 // 16 * 16)
    return min(rows, Y)


def row_chunks(Y: int, rows: int, body) -> None:
    """``body(q, m)`` over the rows ``[0, Y)`` of a plane, ``m = rows`` at
    a time (``q`` a multiple of ``rows``) in a loop, then the tail."""
    n, tail = divmod(Y, rows)

    def step(k, carry):
        body(pl.multiple_of(k * rows, rows), rows)
        return carry

    if n:
        jax.lax.fori_loop(0, n, step, 0)
    if tail:
        body(n * rows, tail)


def _kernel(v_ref, *refs, n_cf, has_diag, offsets, radius, shape, slots,
            rows, dots=(), has_w=False, ring_rows=0):
    cf_refs = refs[:n_cf]
    refs = refs[n_cf:]
    w_ref = refs[0] if has_w else None
    if dots:
        u_ref, d_ref, ring = refs[has_w:]
    else:
        u_ref, ring = refs
    r, B = radius, ROW_BORDER
    X, Y, Z = shape
    t = pl.program_id(0)
    f32 = jnp.float32

    def slot(j):
        return jax.lax.rem(j + slots, slots)      # j >= -r

    def chunks(body):
        row_chunks(Y, rows, body)

    @pl.when(t == 0)
    def _zero_ring():                              # planes -r..-1, borders
        ring[...] = jnp.zeros(ring.shape, f32)

    st = slot(t)

    @pl.when(t < X)
    def _arrive():                                 # plane t, widened
        def widen(q, m):
            ring[st, pl.ds(B + q, m), pl.ds(0, Z)] = (
                v_ref[pl.ds(q, m), :].astype(f32))
        chunks(widen)

    @pl.when(t >= X)
    def _past_face():                              # plane t is outside
        ring[st] = jnp.zeros(ring.shape[1:], f32)

    i = t - r                                      # the output plane

    @pl.when(t >= r)
    def _apply():
        sc = slot(i)
        if dots:
            d_ref[...] = jnp.zeros(d_ref.shape, f32)

        def apply(q, m):
            win = ring[sc, pl.ds(q, m + 2 * B), :]  # rows q-B .. q+m+B
            mid = win[B:B + m]
            planes = {0: win}                       # dx -> its row window

            def term(off):
                dx, dy, dz = off
                if sum(o != 0 for o in off) > 1:    # a corner of a box
                    if dx not in planes:
                        planes[dx] = ring[slot(i + dx), pl.ds(q, m + 2 * B), :]
                    rows = planes[dx][B + dy:B + dy + m]
                    if dz:
                        rows = pltpu.roll(rows, (-dz) % rows.shape[1], 1)
                    return rows[:, :Z]
                if dx:
                    return ring[slot(i + dx), pl.ds(B + q, m), :][:, :Z]
                if dy:
                    return win[B + dy:B + dy + m, :Z]
                if dz:
                    return pltpu.roll(mid, (-dz) % mid.shape[1], 1)[:, :Z]
                return mid[:, :Z]

            u = term((0, 0, 0))
            fields = cf_refs
            if has_diag:
                u = cf_refs[0][pl.ds(q, m), :].astype(f32) * u
                fields = cf_refs[1:]
            for off, cf_ref in zip(offsets, fields):
                u = u + cf_ref[pl.ds(q, m), :].astype(f32) * term(off)
            u_ref[pl.ds(q, m), :] = u.astype(u_ref.dtype)
            if dots:
                epilogue(q, m, u.astype(u_ref.dtype).astype(f32), mid[:, :Z])

        def epilogue(q, m, us, vs):
            if ring_rows:                       # rows a later patch rewrites
                row = jax.lax.broadcasted_iota(jnp.int32, us.shape, 0) + q
                us = jnp.where((row >= ring_rows) & (row < Y - ring_rows),
                               us, 0.0)
            for j, which in enumerate(dots):
                x = {"u": lambda: us, "v": lambda: vs,
                     "w": lambda: w_ref[pl.ds(q, m), :].astype(f32)}[which]()
                d_ref[j:j + 1, :] += jnp.sum(x * us, axis=0, keepdims=True)

        chunks(apply)


def spmv_stream(v: jax.Array, fields: list[jax.Array],
                offsets: tuple[tuple[int, int, int], ...], *,
                diag: jax.Array | None = None, out_dtype=None,
                interpret: bool = False, dots: tuple[str, ...] = (),
                w: jax.Array | None = None, ring_rows: int = 0):
    """``u = A v`` on an unpadded ``(X, Y, Z)`` block, zero-Dirichlet faces.

    ``fields[k]`` multiplies ``v`` shifted by ``offsets[k]`` (star offsets,
    one nonzero axis each, or radius-1 box offsets), in the order given; ``diag`` (None: unit)
    multiplies the centre first.  One kernel named ``spmv_stream``.

    With ``dots`` (each of ``"u"``, ``"v"``, ``"w"``) it returns ``(u,
    partials)``: ``partials[x, j, z]`` is the f32 sum over the rows ``y``
    of ``dots[j]``'s operand times the stored ``u``, both widened to f32,
    at ``(x, y, z)``.  ``ring_rows`` leaves that many rows at either face
    of ``y`` out of the sums.
    """
    obs_metrics.counter("kernels.stencil_stream.traced_calls").inc()
    if ("w" in dots) != (w is not None):
        raise ValueError("spmv_stream takes w exactly when dots names it")
    return _spmv_stream(v, list(fields), tuple(map(tuple, offsets)),
                        diag=diag, out_dtype=jnp.dtype(out_dtype or v.dtype),
                        interpret=interpret, dots=tuple(dots), w=w,
                        ring_rows=ring_rows)


# jitted so that the SpMVs of one solve share one trace and one lowering
# (one for each epilogue)
@functools.partial(jax.jit, static_argnames=("offsets", "out_dtype",
                                             "interpret", "dots",
                                             "ring_rows"))
def _spmv_stream(v, fields, offsets, *, diag, out_dtype, interpret, dots, w,
                 ring_rows):
    X, Y, Z = v.shape
    for off in offsets:
        if sum(o != 0 for o in off) > 1 and max(map(abs, off)) > 1:
            raise ValueError(f"spmv_stream takes star offsets and box offsets "
                             f"of radius 1, got {off}")
    r = max(max(abs(o) for o in off) for off in offsets)
    if r > ROW_BORDER:
        raise ValueError(f"radius {r} exceeds the scratch border {ROW_BORDER}")
    zp = -(-(Z + r) // LANES) * LANES     # zero lanes past Z absorb z rolls
    slots = 2 * r + 1
    rows = chunk_rows(Y, zp)
    cfs = ([diag] if diag is not None else []) + list(fields)
    lead = pl.BlockSpec((None, Y, Z), lambda t: (jnp.minimum(t, X - 1), 0, 0))
    plane = pl.BlockSpec((None, Y, Z), lambda t: (jnp.maximum(t - r, 0), 0, 0))
    kernel = functools.partial(
        _kernel, n_cf=len(cfs), has_diag=diag is not None,
        offsets=offsets, radius=r, shape=(X, Y, Z), slots=slots,
        rows=rows)
    u_shape = jax.ShapeDtypeStruct((X, Y, Z), out_dtype)
    out_specs, out_shape, ins = plane, u_shape, list(cfs)
    if dots:
        kernel = functools.partial(kernel, dots=dots, has_w=w is not None,
                                   ring_rows=ring_rows)
        ins += [w] if w is not None else []
        out_specs = [plane, pl.BlockSpec(
            (None, len(dots), Z), lambda t: (jnp.maximum(t - r, 0), 0, 0))]
        out_shape = [u_shape,
                     jax.ShapeDtypeStruct((X, len(dots), Z), jnp.float32)]
    return pl.pallas_call(
        kernel, grid=(X + r,),
        in_specs=[lead] + [plane] * len(ins),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((slots, Y + 2 * ROW_BORDER, zp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="spmv_stream",
    )(v, *ins)


def stream_interior_apply(coeffs, v: jax.Array, *, policy,
                          **epilogue):
    """``core.halo.interior_apply`` through :func:`spmv_stream`: the
    stored diagonals in canonical order, the result in storage dtype.
    ``epilogue``: :func:`spmv_stream`'s ``dots``, ``w`` and
    ``ring_rows``."""
    items = coeffs.ordered_items()
    return spmv_stream(v, [cf for _, cf in items],
                       tuple(name_offset(n, coeffs.ndim) for n, _ in items),
                       diag=coeffs.diag, out_dtype=policy.storage,
                       interpret=resolve_interpret(None), **epilogue)


def stream_interior_partials(coeffs, v: jax.Array, w: jax.Array | None,
                             which: tuple[str, ...], *, policy,
                             ring: tuple[int, int, int] = (0, 0, 0)):
    """:func:`stream_interior_apply` with the dot epilogue: ``(u,
    partials)``, ``partials[j]`` the f32 local ``<x, u>`` for ``x`` the
    ``which[j]`` of ``u``, ``v`` and ``w``, over the points farther than
    ``ring[a]`` from either face of each axis ``a``."""
    u, parts = stream_interior_apply(coeffs, v, policy=policy,
                                     dots=tuple(which), w=w,
                                     ring_rows=ring[1])
    (X, _, Z), (rx, _, rz) = v.shape, ring
    return u, list(jnp.sum(parts[rx:X - rx, :, rz:Z - rz], axis=(0, 2)))
