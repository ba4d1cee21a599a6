"""The residual of a radius-1 stencil at the coarse points only: the
restriction of HPCG's multigrid V-cycle (``core/multigrid``).

    rc[i, j, k] = r[2i, 2j, 2k] - sum_o F[i, j, k; o] x[2i + o0, 2j + o1, 2k + o2]

on an unpadded ``(X, Y, Z)`` block of even extents with zero-Dirichlet
faces and a unit diagonal, ``F`` the coefficient fields at the coarse
points: the next level's fields, which are the fine ones injected there.
It is ``(r - A x)[0::2, 0::2, 0::2]`` without forming ``A x`` at the
other seven points in eight.

The grid walks the coarse planes ``i``.  Step ``i`` receives the iterate's
planes ``2i`` and ``2i + 1`` (one BlockSpec of two planes), ``r``'s plane
``2i`` and plane ``i`` of every field and of the answer; plane ``2i - 1``
is the previous step's ``2i + 1``, kept in VMEM, so each plane of the
iterate moves from HBM once.

The points are split by the parities of their (y, z) index in VMEM:
Mosaic's strided loads take every other row of a plane (from a scratch
whose lane extent is 128), and a lane gather with a select and a roll
splits each pair of 128-lane groups into their even and odd lanes.  Each
plane of the iterate is split once into four ``(Y / 2, Z / 2)`` colour
planes with zero rows above and below and zero lanes past ``Z / 2``; a
term is then a row slice of one of them, rolled one lane for ``z - 1``,
the zero rows and lanes the faces.

Terms accumulate in f32 in the order given (the unit diagonal first), as
``stream.spmv_stream`` sums them, round to ``apply_dtype`` and are taken
from ``r`` in ``compute`` dtype: the arithmetic of ``r - A x`` with the
stream SpMV, at the coarse points.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stencil_nd.kernel import LANES, VMEM_LIMIT_BYTES
from repro.kernels.stencil_nd.stream import ROW_BORDER, chunk_rows, row_chunks
from repro.obs import metrics as obs_metrics

#: colour-plane slots: plane 2i, then planes 2i - 1 and 2i + 1 by turns
SLOTS = 3
#: f32 vregs one row chunk of a coarse plane may span
CHUNK_VREGS = 8


def _split_lanes(a, b):
    """The even and odd lanes of the 256 lanes ``[a | b]``, as two
    128-lane values: ``[a_even | b_even]`` and ``[a_odd | b_odd]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    half = LANES // 2
    low = lane < half
    evens_first = jnp.where(low, 2 * lane, 2 * (lane - half) + 1)
    odds_first = jnp.where(low, 2 * lane + 1, 2 * (lane - half))
    pa = jnp.take_along_axis(a, evens_first, axis=1)   # [a_even | a_odd]
    pb = jnp.take_along_axis(b, odds_first, axis=1)    # [b_odd | b_even]
    even = jnp.where(low, pa, pb)
    odd = pltpu.roll(jnp.where(low, pb, pa), half, 1)
    return even, odd


def _kernel(x_ref, r_ref, *refs, n_cf, offsets, shape, rows, compute,
            apply_dtype):
    X, Y, Z = shape
    Yc, Zc = Y // 2, Z // 2
    B = ROW_BORDER
    f32 = jnp.float32
    cf_refs = refs[:n_cf]
    out_ref, raw, col, rev = refs[n_cf:]
    groups = raw.shape[0]                          # 128-lane groups of a plane
    i = pl.program_id(0)

    def chunks(body):
        row_chunks(Yc, rows, body)

    @pl.when(i == 0)
    def _zero():                                   # plane -1, borders, pad lanes
        raw[...] = jnp.zeros(raw.shape, f32)
        col[...] = jnp.zeros(col.shape, f32)

    def receive(src, *plane):                      # a plane into its lane groups
        for g in range(-(-Z // LANES)):
            w = min(LANES, Z - g * LANES)

            def copy(q, m, g=g, w=w):
                raw[g, pl.ds(q, m), pl.ds(0, w)] = (
                    src[(*plane, pl.ds(q, m), pl.ds(g * LANES, w))].astype(f32))
            row_chunks(Y, 2 * rows, copy)

    def split(parities, store):                    # raw's colours, by row chunk
        def body(q, m):
            for py in parities:
                for h in range(groups // 2):
                    a, b = (raw[2 * h + s, pl.ds(2 * q + py, m, stride=2), :]
                            for s in (0, 1))
                    store(py, h, q, m, *_split_lanes(a, b))
        chunks(body)

    def split_x(plane, slot):
        receive(x_ref, plane)

        def store(py, h, q, m, even, odd):
            for pz, v in ((0, even), (1, odd)):
                col[slot, py, pz, pl.ds(B + q, m), pl.ds(h * LANES, LANES)] = v
        split((0, 1), store)

    odd_slot = 1 + jax.lax.rem(i, 2)               # plane 2i + 1
    prev_slot = 3 - odd_slot                       # plane 2i - 1
    split_x(0, 0)
    split_x(1, odd_slot)
    receive(r_ref)

    def store_r(py, h, q, m, even, odd):
        rev[pl.ds(q, m), pl.ds(h * LANES, LANES)] = even
    split((0,), store_r)

    def residual(q, m):
        slots = {-1: prev_slot, 0: 0, 1: odd_slot}

        def term(off):
            dx, dy, dz = off
            s, py, pz = slots[dx], int(dy != 0), int(dz != 0)
            if dy >= 0:
                v = col[s, py, pz, pl.ds(B + q, m), :]
            else:                                  # row j - 1 of the odd rows
                v = col[s, py, pz, pl.ds(q, m + B), :][B - 1:B - 1 + m]
            if dz < 0:                             # lane k - 1 of the odd lanes
                v = pltpu.roll(v, 1, 1)
            return v[:, :Zc]

        u = term((0, 0, 0))
        for off, cf in zip(offsets, cf_refs):
            u = u + cf[pl.ds(q, m), :].astype(f32) * term(off)
        au = u.astype(apply_dtype).astype(compute)
        rv = rev[pl.ds(q, m), :][:, :Zc].astype(compute)
        out_ref[pl.ds(q, m), :] = (rv - au).astype(out_ref.dtype)

    chunks(residual)


def coarse_residual(r: jax.Array, x: jax.Array, fields: list[jax.Array],
                    offsets: tuple[tuple[int, int, int], ...], *,
                    apply_dtype=None, compute=jnp.float32, out_dtype=None,
                    interpret: bool = False) -> jax.Array:
    """``(r - A x)[0::2, 0::2, 0::2]`` on an unpadded ``(X, Y, Z)`` block
    of even extents, zero-Dirichlet faces and a unit diagonal, without
    ``A x`` at the other points.  ``fields[k]``, of shape ``(X/2, Y/2,
    Z/2)``, multiplies the neighbour at ``offsets[k]`` (radius 1) of each
    coarse point; ``A x`` rounds to ``apply_dtype`` (default ``x``'s) and
    the difference is taken in ``compute``.  One kernel named
    ``restrict``."""
    obs_metrics.counter("kernels.restrict.traced_calls").inc()
    return _coarse_residual(
        r, x, list(fields), tuple(map(tuple, offsets)),
        apply_dtype=jnp.dtype(apply_dtype or x.dtype),
        compute=jnp.dtype(compute), out_dtype=jnp.dtype(out_dtype or r.dtype),
        interpret=interpret)


# jitted so that the restrictions of one level share a trace and a lowering
@functools.partial(jax.jit, static_argnames=("offsets", "apply_dtype",
                                             "compute", "out_dtype",
                                             "interpret"))
def _coarse_residual(r, x, fields, offsets, *, apply_dtype, compute,
                     out_dtype, interpret):
    X, Y, Z = x.shape
    if r.shape != x.shape or any(n % 2 for n in x.shape):
        raise ValueError(f"coarse_residual takes r and x of one shape with "
                         f"even extents; got {r.shape} and {x.shape}")
    if any(max(map(abs, off)) != 1 for off in offsets):
        raise ValueError(f"coarse_residual takes radius-1 offsets, got {offsets}")
    Xc, Yc, Zc = X // 2, Y // 2, Z // 2
    if any(f.shape != (Xc, Yc, Zc) for f in fields):
        raise ValueError(f"coarse_residual takes fields of shape {(Xc, Yc, Zc)}")
    zcp = -(-(Zc + 1) // LANES) * LANES     # zero lanes past Zc absorb z - 1
    rows = chunk_rows(Yc, zcp, CHUNK_VREGS)
    if Yc > 16 and rows % 16:               # a whole bf16 tile a chunk
        rows = rows // 16 * 16
    coarse = pl.BlockSpec((None, Yc, Zc), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _kernel, n_cf=len(fields), offsets=offsets, shape=(X, Y, Z),
        rows=rows, compute=compute, apply_dtype=apply_dtype)
    return pl.pallas_call(
        kernel, grid=(Xc,),
        in_specs=[pl.BlockSpec((2, Y, Z), lambda i: (i, 0, 0)),
                  pl.BlockSpec((None, Y, Z), lambda i: (2 * i, 0, 0))]
                 + [coarse] * len(fields),
        out_specs=coarse,
        out_shape=jax.ShapeDtypeStruct((Xc, Yc, Zc), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2 * zcp // LANES, Y, LANES), jnp.float32),
            pltpu.VMEM((SLOTS, 2, 2, Yc + 2 * ROW_BORDER, zcp), jnp.float32),
            pltpu.VMEM((Yc, zcp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="restrict",
    )(x, r, *fields)
