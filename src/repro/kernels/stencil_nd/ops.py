"""jit'd wrappers for the generalized stencil kernel: tuning-cache lookup,
VMEM budgeting, padding, and the local apply of ``backend="pallas"``
(:func:`pallas_local_apply`) that pairs the kernel with the depth-r halo
exchange.

Every wrapper resolves its tile shapes through the persistent tuning cache
(``core/tuning``): a swept cell transparently gets its winning
``KernelConfig`` (x/y tile, Z split, ring fusion); an
unswept cell falls back to the deterministic pre-tuning default, so an
empty cache reproduces the fixed-shape behaviour bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilCoeffs, StencilSpec
from repro.kernels import resolve_interpret
from repro.kernels.stencil_nd.kernel import (
    LANES, SUBLANES, tile_bytes, tile_extents,
)

#: double-buffered working set a default tile may take: half of a v5e
#: core's 128 MiB of VMEM (the kernels raise their scoped limit to
#: ``kernel.VMEM_LIMIT_BYTES``).
VMEM_BUDGET_BYTES = 64 * 2 ** 20


def default_tile(shape: tuple[int, int, int], itemsize: int, *,
                 radius: int = 1, n_coeffs: int = 6,
                 budget: int = VMEM_BUDGET_BYTES) -> tuple[int, int, int]:
    """The deterministic tile of an untuned cell: Z whole, then Y whole,
    then the deepest x slab whose double-buffered working set (window +
    coefficients + output) fits ``budget``; Y and then Z are split only
    when a single x plane does not fit.  Falls back to the smallest valid
    tile."""
    X, Y, Z = shape
    for zc in tile_extents(Z, LANES):
        for byc in tile_extents(Y, SUBLANES):
            for bxc in tile_extents(X, 1):
                if tile_bytes((bxc, byc, zc), shape, itemsize, radius=radius,
                              n_tiled=n_coeffs + 1) <= budget:
                    return bxc, byc, zc
    return 1, tile_extents(Y, SUBLANES)[-1], tile_extents(Z, LANES)[-1]


def _spec_order(coeffs: StencilCoeffs, spec: StencilSpec):
    """Diagonals in the spec's canonical order (kernel argument contract)."""
    return [coeffs.diags[n] for n in spec.names]


def tile_apply(vp: jax.Array, cf_list: list[jax.Array], spec: StencilSpec,
               config, *, accum_dtype=jnp.float32,
               interpret: bool | None = None) -> jax.Array:
    """One fused kernel pass over an r-padded block under a KernelConfig.

    The composition point between the tuning cache and the kernel: every
    apply path (standalone, blocking, overlap interior, ring patch, fused
    epilogue) funnels through here so a tuned tile applies uniformly.
    Per-element accumulation order is tile-independent (each output element
    is a canonical-order sum over offsets), so any two valid configs give
    bitwise-identical results.
    """
    from repro.kernels.stencil_nd.kernel import stencil_nd_pallas

    return stencil_nd_pallas(
        vp, cf_list, spec.offsets, radius=spec.radius, zc=config.zc,
        block=config.block, accum_dtype=accum_dtype,
        interpret=resolve_interpret(interpret), name=f"stencil_{spec.name}")


def ring_patch_apply(exchange, cf_list: list[jax.Array], spec: StencilSpec,
                     config, u: jax.Array, fabric, *,
                     accum_dtype=jnp.float32,
                     interpret: bool | None = None) -> jax.Array:
    """The split overlap epilogue: re-run the kernel on the exchanged
    depth-r ring slabs and overwrite the ring of ``u`` — one extra kernel
    launch per boundary region (the fused epilogue folds these away).

    The patch re-runs the same Pallas kernel (not a jnp re-derivation,
    whose fusion can differ by an ulp), so overlap stays bit-identical to
    blocking.  Slab tiles are sized per-slab (a tuned full-block tile does
    not fit a depth-r slab): each slab takes the default tile of its own
    shape.  A batched exchange patches every RHS's
    ring in the same per-region launches (the slab kernel grids over the
    batch axis).
    """
    from repro.core import comm, tuning

    r = spec.radius
    pre = (slice(None),) * exchange.n_batch
    for reg in comm.boundary_regions(exchange.shape, fabric, r):
        lo_hi = [(sl.start or 0,
                  exchange.shape[i] if sl.stop is None else sl.stop)
                 for i, sl in enumerate(reg)]
        sub_shape = tuple(hi - lo for lo, hi in lo_hi)
        sub_vp = exchange.padded[pre + tuple(slice(lo, hi + 2 * r)
                                             for lo, hi in lo_hi)]
        sub_cfg = tuning.default_config(spec, exchange.padded.dtype,
                                        sub_shape)
        patch = tile_apply(sub_vp, [c[reg] for c in cf_list], spec, sub_cfg,
                           accum_dtype=accum_dtype, interpret=interpret)
        # the TPU compiler aborts (HloReachabilityMap out of range) when it
        # weighs fusing a kernel's output into this update on a split
        # fabric; the barrier keeps the kernels and the update apart
        u, patch = jax.lax.optimization_barrier((u, patch))
        u = u.at[pre + reg].set(patch)
    return u


@functools.partial(jax.jit, static_argnames=("spec", "accum_dtype", "interpret"))
def stencil_apply(coeffs: StencilCoeffs, v: jax.Array, *,
                  spec: StencilSpec | None = None,
                  accum_dtype=jnp.float32,
                  interpret: bool | None = None) -> jax.Array:
    """u = A v on a local block (zero-Dirichlet at block edges), any spec.

    ``v`` may carry a leading batch axis (``(B, bx, by, Z)``) — the batch
    folds into the kernel grid and the tuning lookup keys on the mesh
    shape alone (a tuned cell's config applies to every batch size).

    Tile shapes come from the tuning cache (trace-time lookup keyed by
    {device x spec x dtype x shape}); without an entry the deterministic
    default (:func:`default_tile`) applies.
    """
    from repro.core import tuning

    assert v.ndim in (3, 4), "the fused kernel is 3D (+ optional batch axis)"
    if coeffs.diag is not None:
        raise NotImplementedError(
            "the fused stencil kernel assumes the family's unit diagonal; "
            "raw operators go through core.operator.pallas_operator, which "
            "adds the diagonal deviation outside the kernel")
    spec = spec or coeffs.spec
    nb = v.ndim - 3
    config, _ = tuning.lookup_config(spec, v.dtype, v.shape)
    vp = jnp.pad(v, [(0, 0)] * nb + [(spec.radius, spec.radius)] * 3)
    return tile_apply(vp, _spec_order(coeffs, spec), spec, config,
                      accum_dtype=accum_dtype, interpret=interpret)


def pallas_local_apply(coeffs, v, fabric, *, policy, schedule=None,
                       interpret: bool | None = None,
                       fuse_ring: bool | None = None):
    """Drop-in for halo.local_apply: depth-r halo exchange + fused kernel,
    under either communication schedule (``core.comm.SCHEDULES``).

    ``blocking``: ``gather_halo`` assembles the (bx+2r, by+2r, Z+2r) block
    (slab ``ppermute`` per split axis, corner-carrying sequential exchange
    for box specs), which is exactly the kernel's input layout — the kernel
    computes the whole product in one fused pass.

    ``overlap`` (default): the exchange is issued first, the kernel runs on
    the *zero-padded* block — the interior apply, which depends on no
    collective — and only the depth-r boundary ring is patched from the
    exchanged block.  The patch epilogue has two forms, chosen per cell by
    the tuning cache (``fuse_ring`` overrides):

    * split (default): re-run the kernel on the exchanged ring slabs —
      one extra launch per boundary region, minimal collective-dependent
      compute;
    * fused: fold the ring into the interior kernel's pass by running the
      one fused kernel over the exchanged block — a single launch per
      SpMV (2+ -> 1), at the price of the whole pass depending on the
      exchange (see ``kernels/stencil_nd/fused.py``).

    Both epilogues and the blocking path are bitwise identical: every form
    accumulates the same canonical-order terms per element.
    """
    from repro.core import comm, tuning
    from repro.kernels.stencil_nd.fused import fused_ring_apply

    if coeffs.diag is not None:
        raise NotImplementedError(
            "the fused stencil kernel assumes the family's unit diagonal; "
            "raw operators go through core.operator.pallas_operator, which "
            "adds the diagonal deviation outside the kernel")
    spec = coeffs.spec
    r = spec.radius
    cf = coeffs.astype(policy.storage)
    vs = v.astype(policy.storage)
    nb = vs.ndim - cf.ndim       # leading batch (many-RHS) axes
    cf_list = _spec_order(cf, spec)
    config, _ = tuning.lookup_config(spec, vs.dtype, vs.shape)
    fuse = config.fuse_ring if fuse_ring is None else bool(fuse_ring)

    def kernel(vp):
        return tile_apply(vp, cf_list, spec, config,
                          accum_dtype=policy.compute, interpret=interpret)

    def patch_ring(exchange, u):
        return ring_patch_apply(exchange, cf_list, spec, config, u, fabric,
                                accum_dtype=policy.compute,
                                interpret=interpret)

    fused_fn = None
    if fuse:
        def fused_fn(exchange):
            return fused_ring_apply(exchange, cf_list, spec, config,
                                    accum_dtype=policy.compute,
                                    interpret=interpret)

    return comm.scheduled_apply(
        cf, vs, fabric, policy=policy,
        schedule=schedule,
        full_fn=kernel,
        interior_fn=lambda vv: kernel(
            jnp.pad(vv, [(0, 0)] * nb + [(r, r)] * cf.ndim)),
        patch_fn=patch_ring,
        fused_fn=fused_fn)
