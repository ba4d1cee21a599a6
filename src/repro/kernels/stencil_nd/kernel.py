"""Generalized fused stencil SpMV Pallas kernel — any spec in the family.

One kernel lowers *any* :class:`~repro.core.stencil.StencilSpec` (7, 13,
25 or 27 points) to one fused pass: one read of each coefficient diagonal,
one (halo'd) read of the iterate, one write of the result.

Layout on the TPU: Z is the lane dimension and Y the sublane dimension of
every (X, Y, Z) block.  The fabric-local block is cut into a
``(bxc, byc, zc)`` tile grid (``core/tuning.KernelConfig``); each grid step
DMAs the tile's halo'd window of the padded iterate with element-indexed
BlockSpecs (``pl.Element``), so consecutive steps read overlapping windows
— the in-VMEM analogue of the paper's loopback channel, r planes deep.

Mosaic only accepts windows whose last two extents are whole axes or
multiples of the (sublane, lane) tile, so a tile either spans an axis or
is aligned on it: ``byc`` is the whole Y extent or a multiple of
:data:`SUBLANES`, ``zc`` the whole Z extent or a multiple of :data:`LANES`.
An aligned (split) axis reads a window grown by the aligned halo extent
(:func:`halo_extent`), for which the wrapper zero-pads the iterate a
little further on the high side.  The paper's meshes (608, 370, 1536 in Z)
keep Z whole: no multiple of 128 divides 608 or 370.

Inside a step the tile is swept plane by plane and ``rows`` at a time
(:func:`chunk_rows`): every window read is a static, possibly unaligned,
slice of a row chunk loaded at an aligned offset, and the working value
stays a few vregs wide whatever the tile.  Each output element is the same
canonical-order sum whatever the tiling, so every valid tiling is bitwise
identical.

Tile shapes that do not fit the rules (e.g. the paper's unpadded 600 x 595
tiles, or an unaligned split) are clamped at trace time to the nearest
valid tile with a warning — never left to surface as a Mosaic error.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as obs_metrics

#: lane width of a TPU vreg: a split Z axis is cut in multiples of this.
LANES = 128
#: rows of one bf16 tile (f32 tiles hold 8, so 16 serves both dtypes): a
#: split Y axis is cut in multiples of this.
SUBLANES = 16
#: scoped-VMEM limit passed to every stencil kernel (v5e has 128 MiB of
#: VMEM per core; the compiler's default scoped limit is 16 MiB).
VMEM_LIMIT_BYTES = 100 * 2 ** 20
#: f32 vregs one inner-loop row chunk may span (keeps the unrolled body
#: small: compile time and vreg pressure grow with it).
CHUNK_VREGS = 32

# Count of pallas_call ops traced for the stencil SpMV — the kernel-launch
# accounting behind the fused boundary-ring epilogue's 2 -> 1 claim (each
# traced call is one kernel op in the lowered program).  Tests snapshot it
# around a traced apply; see tests/test_tuning.py.  Mirrored into the
# observability registry as ``kernels.stencil_nd.traced_calls``.
_TRACED_CALLS = 0


def traced_call_count() -> int:
    """Total stencil pallas_call ops traced so far in this process."""
    return _TRACED_CALLS


#: alignment of a split tile extent on each of (X, Y, Z)
TILE_ALIGN = (1, SUBLANES, LANES)


def tile_extents(n: int, align: int) -> list[int]:
    """The extents a tile may take on an axis of length ``n``, descending:
    the whole axis, then the divisors of ``n`` that are multiples of
    ``align`` (the axis's entry of :data:`TILE_ALIGN`)."""
    return [n] + [d for d in range(n - 1, 0, -1)
                  if n % d == 0 and d % align == 0]


def clamp_tile(tile: tuple[int, int, int],
               shape: tuple[int, int, int]) -> tuple[int, int, int]:
    """The tile Mosaic compiles nearest ``tile`` on ``shape``: per axis the
    largest valid extent <= the request, else the whole axis.  A valid
    tile comes back unchanged, so ``clamp_tile(t, s) == t`` is the
    validity test."""
    return tuple(next((e for e in tile_extents(n, a) if e <= t), n)
                 for t, n, a in zip(tile, shape, TILE_ALIGN))


def halo_extent(n: int, tile: int, radius: int, align: int) -> int:
    """How far a tile's window reaches past the tile along one axis: the
    2r halo when the tile spans the axis, else 2r rounded up to ``align``
    (a split window must stay tile-aligned)."""
    if tile == n:
        return 2 * radius
    return -(-2 * radius // align) * align


def chunk_rows(byc: int, zc: int) -> int:
    """Rows per inner-loop step: the largest multiple of :data:`SUBLANES`
    dividing ``byc`` whose f32 chunk spans at most :data:`CHUNK_VREGS`
    vregs (at least one aligned tile); ``byc`` itself when it is not a
    multiple of :data:`SUBLANES` (a short or ragged Y extent is one
    chunk, loaded at the static offset 0)."""
    if byc % SUBLANES:
        return byc
    lane_vregs = -(-zc // LANES)
    best = SUBLANES
    for rows in range(SUBLANES, byc + 1, SUBLANES):
        if byc % rows == 0 and (rows // 8) * lane_vregs <= CHUNK_VREGS:
            best = rows
    return best


def accumulate(vp_ref, cf_refs, offsets, *, radius, p, q, rows, zc,
               accum_dtype):
    """Rows ``[q, q + rows)`` of plane ``p`` of ``u = A v``: the unit
    diagonal plus every offset term, in the spec's canonical order.

    ``vp_ref`` is the step's halo'd window; each x-offset plane is loaded
    once as an aligned row chunk and the y/z shifts are static slices of
    it (unaligned slices of a value lower; unaligned dynamic loads do not).
    """
    r = radius
    planes = {}

    def win(off):
        ox, oy, oz = off
        if ox not in planes:
            planes[ox] = vp_ref[pl.ds(p + r + ox, 1), pl.ds(q, rows + 2 * r), :]
        return planes[ox][:, r + oy:r + oy + rows,
                          r + oz:r + oz + zc].astype(accum_dtype)

    u = win((0, 0, 0))           # unit main diagonal (Jacobi preconditioned)
    for cf_ref, off in zip(cf_refs, offsets):
        u = u + cf_ref[pl.ds(p, 1), pl.ds(q, rows), :].astype(accum_dtype) * win(off)
    return u


def sweep(tile, rows, body):
    """Run ``body(p, q)`` over every (plane, row chunk) of a tile."""
    bxc, byc, _ = tile
    nq = byc // rows

    def step(t, carry):
        if nq == 1:
            body(t, 0)
        else:
            body(t // nq, pl.multiple_of((t % nq) * rows, rows))
        return carry

    jax.lax.fori_loop(0, bxc * nq, step, 0)


def _kernel(vp_ref, *refs, offsets, radius, tile, rows, accum_dtype):
    cf_refs, u_ref = refs[:-1], refs[-1]

    def body(p, q):
        u = accumulate(vp_ref, cf_refs, offsets, radius=radius, p=p, q=q,
                       rows=rows, zc=tile[2], accum_dtype=accum_dtype)
        u_ref[pl.ds(p, 1), pl.ds(q, rows), :] = u.astype(u_ref.dtype)

    sweep(tile, rows, body)


def _valid_tile(block: tuple[int, int] | None, zc: int | None,
                shape: tuple[int, int, int]) -> tuple[int, int, int]:
    """Trace-time tile validation: clamp to the nearest valid tile (a
    warning names both) — see ``core.tuning.validate_config``."""
    from repro.core.tuning import KernelConfig, validate_config

    bx, by, Z = shape
    bxc, byc = block if block is not None else (bx, by)
    cfg = validate_config(KernelConfig(block=(bxc, byc), zc=zc or Z), shape,
                          context=" (stencil_nd_pallas)")
    return cfg.tile


def window_call(kernel, v_padded: jax.Array, tiled: list[jax.Array], *,
                name: str, radius: int, tile: tuple[int, int, int],
                out_shape, out_specs_for, interpret: bool):
    """``pallas_call`` named ``name`` over the tile grid of an r-padded block.

    ``v_padded`` (optionally with a leading batch axis) gets the halo'd
    window spec; each of ``tiled`` (mesh-shaped, shared across the batch)
    the plain tile spec.  ``out_specs_for(nb, tile_spec)`` builds the
    output specs from the tile spec of a batch-``nb`` call.
    """
    global _TRACED_CALLS
    r = radius
    nb = v_padded.ndim - 3
    bx, by, Z = (s - 2 * r for s in v_padded.shape[nb:])
    bxc, byc, zc = tile
    grid = (bx // bxc, by // byc, Z // zc)
    ey = halo_extent(by, byc, r, SUBLANES)
    ez = halo_extent(Z, zc, r, LANES)
    if (ey, ez) != (2 * r, 2 * r):
        v_padded = jnp.pad(v_padded, [(0, 0)] * (nb + 1)
                           + [(0, ey - 2 * r), (0, ez - 2 * r)])
    window = (pl.Element(bxc + 2 * r), pl.Element(byc + ey),
              pl.Element(zc + ez))

    def corner(i, j, k):
        # a whole axis is indexed by a literal 0: Mosaic must see the
        # window start is tile-aligned, and j * 608 is not provably so
        return (i * bxc, j * byc if grid[1] > 1 else 0,
                k * zc if grid[2] > 1 else 0)

    if nb:
        grid = (v_padded.shape[0],) + grid
        vspec = pl.BlockSpec((None,) + window,
                             lambda b, i, j, k: (b,) + corner(i, j, k))
        tspec = pl.BlockSpec((bxc, byc, zc), lambda b, i, j, k: (i, j, k))
        ospec = pl.BlockSpec((None, bxc, byc, zc),
                             lambda b, i, j, k: (b, i, j, k))
    else:
        vspec = pl.BlockSpec(window, corner)
        tspec = ospec = pl.BlockSpec((bxc, byc, zc), lambda i, j, k: (i, j, k))
    _TRACED_CALLS += 1
    obs_metrics.counter("kernels.stencil_nd.traced_calls").inc()
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[vspec] + [tspec] * len(tiled),
        out_specs=out_specs_for(nb, ospec),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(v_padded, *tiled)


def stencil_nd_pallas(v_padded: jax.Array, coeffs: list[jax.Array],
                      offsets: tuple[tuple[int, int, int], ...], *,
                      radius: int, zc: int | None = None,
                      block: tuple[int, int] | None = None,
                      accum_dtype=jnp.float32,
                      interpret: bool = True, name: str = "stencil"):
    """u = A v on one local block, a kernel named ``name``.

    ``v_padded``: (bx+2r, by+2r, Z+2r) iterate with halo (zero-padded for a
    standalone block, fabric-filled by ``core.halo.gather_halo`` inside the
    distributed solver), or ``(B, bx+2r, by+2r, Z+2r)`` for a batch of B
    right-hand sides — the batch folds into the grid's leading dimension
    and every coefficient tile is fetched once per spatial tile regardless
    of B (the coefficient BlockSpec ignores the batch index).
    ``coeffs[i]`` is the (bx, by, Z) diagonal that multiplies the
    ``offsets[i]``-shifted window.

    ``block``/``zc`` tile the grid (default: the whole block, one step).
    """
    r = radius
    nb = v_padded.ndim - 3       # leading batch axis (0 or 1)
    shape = tuple(s - 2 * r for s in v_padded.shape[nb:])
    tile = _valid_tile(block, zc, shape)
    rows = chunk_rows(tile[1], tile[2])
    kernel = functools.partial(
        _kernel, offsets=tuple(offsets), radius=r, tile=tile, rows=rows,
        accum_dtype=accum_dtype)
    return window_call(
        kernel, v_padded, list(coeffs), name=name, radius=r, tile=tile,
        out_shape=jax.ShapeDtypeStruct(v_padded.shape[:nb] + shape,
                                       v_padded.dtype),
        out_specs_for=lambda nb, ospec: ospec, interpret=interpret)


def tile_bytes(tile: tuple[int, int, int], shape: tuple[int, int, int],
               itemsize: int, *, radius: int, n_tiled: int) -> int:
    """Double-buffered VMEM bytes of one grid step: the halo'd window of
    the iterate plus ``n_tiled`` tile-shaped operands (coefficients,
    output, extra inputs), each padded to the (sublane, lane) layout."""
    bxc, byc, zc = tile
    sub = 8 * max(1, 4 // itemsize)

    def plane(rows, lanes):
        return (math.ceil(rows / sub) * sub * math.ceil(lanes / LANES)
                * LANES * itemsize)

    ey = halo_extent(shape[1], byc, radius, SUBLANES)
    ez = halo_extent(shape[2], zc, radius, LANES)
    window = (bxc + 2 * radius) * plane(byc + ey, zc + ez)
    return 2 * (window + n_tiled * bxc * plane(byc, zc))
