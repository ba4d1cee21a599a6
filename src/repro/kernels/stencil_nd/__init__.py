from repro.kernels.stencil_nd.ops import (  # noqa: F401
    pallas_local_apply,
    ring_patch_apply,
    stencil_apply,
    tile_apply,
)
from repro.kernels.stencil_nd.ref import stencil_nd_ref  # noqa: F401
