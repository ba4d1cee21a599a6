"""One multicolour Gauss–Seidel sweep of a radius-1 stencil in one pass over
the planes: the smoother of HPCG's multigrid V-cycle (``core/multigrid``).

The points are split into 8 colours by the parities ``(i & 1, j & 1,
k & 1)`` of their (x, y, z) index.  No two points of one colour are
neighbours in the 27-point box (nor in the 7-point star), so a colour
updates all its points at once:

    x[p] <- r[p] - sum_o A[p, p + o] x[p + o]       (the unit diagonal)

A sweep updates the colours one after the other.  Planes of one x parity
never touch each other, so a sweep that takes the colours of one x parity
first (``first``) is a sweep of the planes in the order

    first = 1 (odd planes first):  1, 0, 3, 2, 5, 4, ...
    first = 0 (even planes first): 0, 2, 1, 4, 3, 6, 5, ...

each plane taking its four (y, z) colours in ``inplane`` order.  Plane
``u`` is updated when its neighbours ``u - 1`` and ``u + 1`` hold what the
colour order says they hold: in both orders a plane of the first parity
sees its neighbours before their update and one of the second parity
after it.  Every plane is updated exactly once, and the answer is the
8-colour sweep's.

The kernel walks that order with the plane ring of ``stream.py``: step
``t`` receives plane ``t`` of the iterate (a plane BlockSpec, the pipeline
fetching plane ``t + 1`` meanwhile) into a VMEM ring of 5 f32 slots, and
updates plane ``u(t - 2)``, whose right-hand side, coefficient and output
planes arrive through BlockSpecs indexed by the same ``u``.  So each plane
of ``r``, of the iterate, of every coefficient field and of the answer
moves between HBM and VMEM once: a sweep moves what one SpMV moves, plus
``r``.

A plane update first gathers ``r`` minus the terms of the two
neighbouring planes into a scratch plane, then runs one pass over the
plane per in-plane colour: the 8 in-plane terms of every point, stored
only at that colour's points.  A y term is a row slice of an aligned row
chunk, a z term a lane roll of it, the slots' zero border rows and lanes
the faces, as in ``stream.py``.  Terms accumulate in f32; the answer
rounds once to the output dtype.
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

#: ring slots: at step t the update of plane u(t - 2) reads planes t - 4 .. t
SLOTS = 5
#: f32 vregs one row chunk may span: a plane update holds a few such values
#: (the rows in flight, the window, a term) beside many coefficient loads
CHUNK_VREGS = 8


def plane_order(s, X: int, first: int):
    """The plane updated at sweep step ``s`` (traced or Python int)."""
    if first:
        return jnp.minimum(jnp.bitwise_xor(s, 1), X - 1)
    return jnp.clip(jnp.bitwise_xor(s - 1, 1) + 1, 0, X - 1)


def _kernel(*refs, has_x, n_cf, offsets, shape, first, inplane, rows):
    X, Y, Z = shape
    B = ROW_BORDER
    f32 = jnp.float32
    if has_x:
        x_ref, r_ref, *refs = refs
    else:
        r_ref, *refs = refs
    cf_refs = dict(zip(offsets, refs[:n_cf]))
    out_ref, ring, acc = refs[n_cf:]
    t = pl.program_id(0)

    def slot(j):
        return jax.lax.rem(j + SLOTS, SLOTS)      # j >= -1

    def chunks(body):
        row_chunks(Y, rows, body)

    @pl.when(t == 0)
    def _zero_ring():                              # plane -1, borders
        ring[...] = jnp.zeros(ring.shape, f32)

    st = slot(t)

    if has_x:
        @pl.when(t < X)
        def _arrive():                             # plane t, widened
            def widen(q, m):
                ring[st, pl.ds(B + q, m), pl.ds(0, Z)] = (
                    x_ref[pl.ds(q, m), :].astype(f32))
            chunks(widen)

    @pl.when((t >= X) if has_x else (t > 0))
    def _zero_slot():                              # outside, or a zero start
        ring[st] = jnp.zeros(ring.shape[1:], f32)

    def shifted(win, dy, dz, m):
        rows_ = win[B + dy:B + dy + m]
        if dz:
            rows_ = pltpu.roll(rows_, (-dz) % rows_.shape[1], 1)
        return rows_[:, :Z]

    @pl.when(t >= 2)
    def _update():
        u = plane_order(t - 2, X, first)
        su = slot(u)

        def gather(q, m):                          # r - the x-neighbour terms
            val = r_ref[pl.ds(q, m), :].astype(f32)
            for dx in (-1, 1):
                win = ring[slot(u + dx), pl.ds(q, m + 2 * B), :]
                for off, cf in cf_refs.items():
                    if off[0] == dx:
                        val = val - (cf[pl.ds(q, m), :].astype(f32)
                                     * shifted(win, off[1], off[2], m))
            acc[pl.ds(q, m), :] = val

        chunks(gather)

        for n, (cy, cz) in enumerate(inplane):
            last = n == len(inplane) - 1

            def colour(q, m, cy=cy, cz=cz, last=last):
                win = ring[su, pl.ds(q, m + 2 * B), :]
                val = acc[pl.ds(q, m), :]
                for off, cf in cf_refs.items():
                    if off[0] == 0:
                        val = val - (cf[pl.ds(q, m), :].astype(f32)
                                     * shifted(win, off[1], off[2], m))
                row = jax.lax.broadcasted_iota(jnp.int32, (m, Z), 0) & 1
                lane = jax.lax.broadcasted_iota(jnp.int32, (m, Z), 1) & 1
                new = jnp.where((row == cy) & (lane == cz), val,
                                win[B:B + m, :Z])
                ring[su, pl.ds(B + q, m), pl.ds(0, Z)] = new
                if last:
                    out_ref[pl.ds(q, m), :] = new.astype(out_ref.dtype)

            chunks(colour)


def symgs_sweep(r: jax.Array, x: jax.Array | None, fields: list[jax.Array],
                offsets: tuple[tuple[int, int, int], ...], *, first: int,
                inplane: tuple[tuple[int, int], ...], out_dtype=None,
                interpret: bool = False) -> jax.Array:
    """One Gauss–Seidel sweep of ``A x = r`` on an unpadded ``(X, Y, Z)``
    block with zero-Dirichlet faces and a unit diagonal, in the colour
    order ``first`` (the x parity taken first) and ``inplane`` (the four
    (y, z) parities, in order).  ``fields[k]`` multiplies the neighbour at
    ``offsets[k]`` (radius 1); ``x`` None is a zero start, read from
    nowhere.  One kernel named ``symgs``."""
    obs_metrics.counter("kernels.symgs.traced_calls").inc()
    return _symgs_sweep(r, x, list(fields), tuple(map(tuple, offsets)),
                        first=int(first), inplane=tuple(map(tuple, inplane)),
                        out_dtype=jnp.dtype(out_dtype or r.dtype),
                        interpret=interpret)


# jitted so that the sweeps of one solve share their traces and lowerings
@functools.partial(jax.jit, static_argnames=("offsets", "first", "inplane",
                                             "out_dtype", "interpret"))
def _symgs_sweep(r, x, fields, offsets, *, first, inplane, out_dtype,
                 interpret):
    X, Y, Z = r.shape
    if any(max(map(abs, off)) != 1 for off in offsets):
        raise ValueError(f"symgs takes radius-1 offsets, got {offsets}")
    if sorted(inplane) != [(0, 0), (0, 1), (1, 0), (1, 1)]:
        raise ValueError(f"inplane must order the four (y, z) parities, got {inplane}")
    zp = -(-(Z + 1) // LANES) * LANES     # zero lanes past Z absorb z rolls
    rows = chunk_rows(Y, zp, CHUNK_VREGS)
    order = lambda t: (plane_order(jnp.maximum(t - 2, 0), X, first), 0, 0)
    lead = pl.BlockSpec((None, Y, Z), lambda t: (jnp.minimum(t, X - 1), 0, 0))
    plane = pl.BlockSpec((None, Y, Z), order)
    kernel = functools.partial(
        _kernel, has_x=x is not None, n_cf=len(fields), offsets=offsets,
        shape=(X, Y, Z), first=first, inplane=inplane, rows=rows)
    ins = ([x] if x is not None else []) + [r] + list(fields)
    return pl.pallas_call(
        kernel, grid=(X + 2,),
        in_specs=([lead] if x is not None else []) + [plane] * (1 + len(fields)),
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((SLOTS, Y + 2 * ROW_BORDER, zp), jnp.float32),
            pltpu.VMEM((Y, Z), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="symgs",
    )(*ins)
