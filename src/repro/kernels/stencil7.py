"""Deprecation shim: the 7-point kernel package, collapsed.

Historically ``kernels/stencil7/`` carried its own fused Pallas kernel
(the paper's Listing 1, TPU-native) plus wrappers and a jnp oracle.  All
of that now lives, shape-parameterized, in :mod:`repro.kernels.stencil_nd`
— this single file re-exports the radius-1 star specialization under the
legacy names so existing callers keep working.  New code should import
from ``kernels/stencil_nd`` directly.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.stencil import STAR7, StencilCoeffs

warnings.warn(
    "repro.kernels.stencil7 is deprecated: the 7-point kernel lives, "
    "shape-parameterized, in repro.kernels.stencil_nd — import from there. "
    "This shim re-exports the legacy names and will be removed.",
    DeprecationWarning, stacklevel=2)
from repro.kernels import stencil_nd
from repro.kernels.stencil_nd.kernel import stencil_nd_pallas
from repro.kernels.stencil_nd.ops import (  # noqa: F401  (re-exported API)
    VMEM_BUDGET_BYTES,
    default_tile,
)
from repro.kernels.stencil_nd.ref import stencil_nd_ref

# the 7-point kernel's argument order (== STAR7.names: xp, xm, yp, ym, zp, zm)
ORDER = STAR7.names


def stencil7_apply(coeffs: StencilCoeffs, v: jax.Array, *,
                   accum_dtype=jnp.float32,
                   interpret: bool | None = None) -> jax.Array:
    """u = A v on a local block (zero-Dirichlet at block edges)."""
    assert v.ndim == 3, "stencil7 kernel is 3D"
    return stencil_nd.stencil_apply(coeffs, v, spec=STAR7,
                                    accum_dtype=accum_dtype,
                                    interpret=interpret)


def stencil7_pallas(v_padded: jax.Array, coeffs: list[jax.Array], *,
                    zc: int, accum_dtype=jnp.float32, interpret: bool = True):
    """v_padded: (bx+2, by+2, Z+2) zero-padded iterate; coeffs: 6 x (bx,by,Z)
    in the order xp, xm, yp, ym, zp, zm (== STAR7.offsets order)."""
    return stencil_nd_pallas(v_padded, coeffs, STAR7.offsets, radius=1,
                             zc=zc, accum_dtype=accum_dtype,
                             interpret=interpret, name="stencil_star7")


def stencil7_ref(v: jax.Array, coeffs: list[jax.Array],
                 accum_dtype=jnp.float32) -> jax.Array:
    """Pure-jnp oracle; coeffs order: xp, xm, yp, ym, zp, zm."""
    return stencil_nd_ref(v, coeffs, STAR7.offsets, accum_dtype=accum_dtype)


def pallas_local_apply(coeffs, v, fabric, *, policy, schedule=None,
                       interpret: bool | None = None):
    """Drop-in for halo.local_apply: halo exchange + fused Pallas SpMV."""
    return stencil_nd.pallas_local_apply(coeffs, v, fabric, policy=policy,
                                         schedule=schedule,
                                         interpret=interpret)
