"""Fused kernel epilogue: the boundary-ring fold for the overlap schedule.

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

The SpMV's inner-product epilogues live with the kernel that runs on the
chip (``kernels/stencil_nd/stream.py``, ``spmv_stream(..., dots=)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
