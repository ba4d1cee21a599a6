"""Fused kernel epilogues: the boundary-ring fold for the overlap
schedule, and the 7-point SpMV inner-product epilogues (EXPERIMENTS.md
§Perf, stencil v3).

**Boundary-ring epilogue** (:func:`fused_ring_apply`): the overlap
schedule's split form pays one interior kernel launch plus one patch
launch per boundary region; the fused form folds the ring into the
interior kernel's own pass — one launch per overlapped SpMV.  Selection is
per-cell via the tuning cache (``KernelConfig.fuse_ring``), because the
fold is a genuine trade: it removes the extra launches and the ring
re-reads, but the single pass now reads the *exchanged* block, so the
whole kernel depends on the halo collectives instead of only the depth-r
ring — on fabrics where halo latency is fully hidden anyway (the paper's
regime) fusion wins; where the interior must cover the transfers the split
form wins.  The sweep decides.

**Dot epilogues**: two variants used by the BiCGStab iteration:
  * ``stencil7_dot``      : s = A p  and  <r0, s>       (sync point 1 feed)
  * ``stencil7_two_dots`` : y = A q  and  <q, y>, <y, y> (sync point 2 feed)

Fusing the dot into the SpMV's write-out pass removes a full re-read of the
freshly written vector (and of the second operand), cutting the iteration's
per-point traffic from 42 to 31 words (see kernels/fused_iter for the AXPY
fusions).  They share the SpMV kernel's tiling and row sweep; the dots
accumulate in f32 across the sequential grid steps (paper FMAC discipline).

The dot epilogues are the one radius-1-star specialization left in the
package (the ``kernels/stencil7`` shim re-exports them under their
historical home); the ring epilogue is generic over the stencil family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.stencil import STAR7, StencilCoeffs
from repro.kernels import resolve_interpret
from repro.kernels.stencil_nd.kernel import (
    accumulate, chunk_rows, sweep, window_call,
)
from repro.kernels.stencil_nd.ops import default_tile

# kernel argument order (== STAR7.names: xp, xm, yp, ym, zp, zm)
ORDER = STAR7.names


def fused_ring_apply(exchange, cf_list: list[jax.Array], spec, config, *,
                     accum_dtype=jnp.float32,
                     interpret: bool | None = None) -> jax.Array:
    """One-launch overlapped SpMV: interior + boundary ring in one pass.

    Runs the fused stencil kernel once over the *exchanged* r-padded block.
    Bitwise identity with the split interior+ring form follows from the
    kernel's per-element contract: a non-ring cell never reads halo values,
    so its sum is unchanged between the zero-padded and exchanged inputs;
    a ring cell computes exactly the canonical-order sum the split form's
    patch kernel computes from the same exchanged slabs.  Tiling cannot
    break this — each output element is an independent canonical-order
    accumulation, whatever the grid decomposition (asserted bitwise across
    schedules and epilogues in tests/test_tuning.py).

    Launch accounting: this is 1 pallas_call per SpMV where the split form
    traces 1 + (patch launches per split boundary region).
    """
    from repro.kernels.stencil_nd.ops import tile_apply

    assert exchange.radius == spec.radius, (exchange.radius, spec.radius)
    return tile_apply(exchange.padded, cf_list, spec, config,
                      accum_dtype=accum_dtype, interpret=interpret)


def _dot_kernel(vp_ref, w_ref, *refs, tile, rows, accum_dtype, two_dots):
    cf_refs, (u_ref, d1_ref, d2_ref) = refs[:-3], refs[-3:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))
    def _():
        d1_ref[...] = jnp.zeros_like(d1_ref)
        d2_ref[...] = jnp.zeros_like(d2_ref)

    def body(p, q):
        u = accumulate(vp_ref, cf_refs, STAR7.offsets, radius=1, p=p, q=q,
                       rows=rows, zc=tile[2], accum_dtype=accum_dtype)
        u_ref[pl.ds(p, 1), pl.ds(q, rows), :] = u.astype(u_ref.dtype)
        # epilogue: dots against w (= r0 or q) and optionally u itself, in f32
        uf = u.astype(jnp.float32)[0]
        wf = w_ref[p, pl.ds(q, rows), :].astype(jnp.float32)
        d1_ref[...] += jnp.sum(wf * uf).reshape(1, 1)
        if two_dots:
            d2_ref[...] += jnp.sum(uf * uf).reshape(1, 1)

    sweep(tile, rows, body)


def _call(coeffs: StencilCoeffs, v: jax.Array, w: jax.Array, *, two_dots: bool,
          name: str, accum_dtype=jnp.float32, interpret: bool | None = None):
    shape = v.shape
    # tile and row chunk as for the plain SpMV, with w as one more operand
    tile = default_tile(shape, jnp.dtype(v.dtype).itemsize, n_coeffs=7)
    rows = chunk_rows(tile[1], tile[2])
    sspec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    u, d1, d2 = window_call(
        functools.partial(_dot_kernel, tile=tile, rows=rows,
                          accum_dtype=accum_dtype, two_dots=two_dots),
        jnp.pad(v, 1), [w] + [coeffs.diags[n] for n in ORDER],
        name=name, radius=1, tile=tile,
        out_shape=[
            jax.ShapeDtypeStruct(shape, v.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        out_specs_for=lambda nb, ospec: [ospec, sspec, sspec],
        interpret=resolve_interpret(interpret))
    return u, d1[0, 0], d2[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil7_dot(coeffs: StencilCoeffs, p: jax.Array, r0: jax.Array, *,
                 interpret: bool | None = None):
    """s = A p, <r0, s> in one pass. Returns (s, r0s_partial)."""
    s, d1, _ = _call(coeffs, p, r0, two_dots=False, name="stencil7_dot",
                     interpret=interpret)
    return s, d1


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil7_two_dots(coeffs: StencilCoeffs, q: jax.Array, *,
                      interpret: bool | None = None):
    """y = A q, <q, y>, <y, y> in one pass. Returns (y, qy, yy)."""
    y, qy, yy = _call(coeffs, q, q, two_dots=True, name="stencil7_two_dots",
                      interpret=interpret)
    return y, qy, yy
