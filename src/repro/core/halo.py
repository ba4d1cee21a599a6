"""Halo-exchange SpMV on the chip fabric (paper §IV-1, Figs. 3-5),
generalized to depth-r halos for the whole stencil family.

The paper's scheme: every core broadcasts its Z-pencil of the iterate to its
four fabric neighbors (one outgoing channel, four incoming channels — the
tessellation coloring of Fig. 5), multiplies the four received pencils with
the stored coefficient diagonals, and handles the two Z-shifted terms from a
local loopback.

TPU adaptation: a chip owns a ``(bx, by, Z)`` sub-volume, not a single
pencil, so only the *faces* of the block move.  The four neighbor channels
become four ``jax.lax.ppermute`` shifts (XLA ``collective-permute`` on the
ICI torus); fabric-edge chips receive zeros from ``ppermute``, which is
exactly the zero-Dirichlet boundary.  The CS-1 FIFO/task overlap machinery
is replaced by dataflow: the interior stencil terms do not depend on the
permutes, so XLA's latency-hiding scheduler runs the collectives under the
interior compute (the ``overlap`` schedule makes this explicit by shrinking
the halo-dependent computation to the outer shell of the block).

Stencil-family generalization (:func:`gather_halo`): a radius-r spec moves
slabs of thickness r instead of single faces — the r stacked face shifts of
a depth-r exchange coalesced into one ``ppermute`` message per direction
per axis.  Star stencils exchange the axes independently (all collectives
overlappable); box stencils need edge/corner halo values, obtained by
exchanging the axes *sequentially* on the already-padded block so received
halos ride along to the diagonal neighbors (the standard corner-carrying
trick — no extra diagonal ppermutes on the torus).

All functions here are *local* (rank-per-shard) and must run inside
``jax.shard_map``; :mod:`repro.core.bicgstab` builds the global solver.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.precision import Policy, F32
from repro.core.stencil import StencilCoeffs, padded_apply
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class FabricAxes:
    """Names/sizes of the mesh axes carrying the stencil's X, Y (and Z) dims."""

    x: str = "data"
    nx: int = 1
    y: str = "model"
    ny: int = 1
    z: str | None = None          # pod axis slabs Z when multi-pod
    nz: int = 1

    @classmethod
    def from_mesh(cls, mesh) -> "FabricAxes":
        ax = dict(zip(mesh.axis_names, mesh.devices.shape))
        return cls(
            x="data", nx=ax["data"], y="model", ny=ax["model"],
            z="pod" if "pod" in ax else None, nz=ax.get("pod", 1),
        )

    def spec(self, ndim: int = 3, *, n_batch: int = 0) -> P:
        """PartitionSpec for a mesh-shaped field (X, Y[, Z]).

        ``n_batch`` prepends unsharded (replicated) axes for fields that
        carry a leading batch of right-hand sides: every shard owns its
        block of *all* B RHS, so the batch never moves over the fabric.
        """
        batch = (None,) * n_batch
        if ndim == 2:
            return P(*batch, self.x, self.y)
        return P(*batch, self.x, self.y, self.z)

    def split_info(self, ndim: int = 3) -> list[tuple[int, str | None, int]]:
        """(mesh axis, fabric axis name or None, fabric extent) per dimension."""
        info = [(0, self.x, self.nx), (1, self.y, self.ny)]
        if ndim == 3:
            info.append((2, self.z, self.nz))
        return info


def _exchange(face_lo, face_hi, axis_name: str, n: int):
    """Bidirectional nearest-neighbor exchange of two faces along one axis.

    Returns ``(from_lo, from_hi)``: the lower neighbor's high face and the
    upper neighbor's low face.  Edge shards receive zeros (Dirichlet).
    """
    if n == 1:
        return jnp.zeros_like(face_hi), jnp.zeros_like(face_lo)
    fwd = [(i, i + 1) for i in range(n - 1)]
    bwd = [(i + 1, i) for i in range(n - 1)]
    from_lo = jax.lax.ppermute(face_hi, axis_name, fwd)   # neighbor i-1 sent its high face
    from_hi = jax.lax.ppermute(face_lo, axis_name, bwd)   # neighbor i+1 sent its low face
    return from_lo, from_hi


def _take_slab(v: jax.Array, axis: int, sl: slice) -> jax.Array:
    return v[tuple(sl if i == axis else slice(None) for i in range(v.ndim))]


@obs_trace.scoped("halo")
def gather_halo(
    v: jax.Array,
    fabric: FabricAxes,
    radius: int = 1,
    *,
    corners: bool = False,
    n_batch: int = 0,
) -> jax.Array:
    """The local block padded by ``radius`` on every axis, halos filled.

    This is the communication phase of the paper's SpMV, depth-r: each split
    axis exchanges a slab of thickness r (the r stacked face shifts of a
    depth-r halo coalesced into one ``ppermute`` message per direction).
    Unsplit axes and fabric edges are zero-padded — the global zero-Dirichlet
    boundary.

    ``n_batch`` leading axes of ``v`` are batch (many-RHS) axes: they are
    never padded or split, and each exchanged slab carries all B right-hand
    sides — a depth-r batched exchange moves ``(B, r, ...)`` slabs in the
    *same* number of ppermute messages as a single RHS, amortizing the
    per-message fabric latency across the whole batch.

    ``corners=False`` (star stencils): the axes exchange independently on the
    raw block, so all collectives are mutually independent and overlappable
    with interior compute; the edge/corner halo regions stay zero (a star
    never reads them).

    ``corners=True`` (box stencils): the axes exchange *sequentially* on the
    progressively padded block, so halo values received on earlier axes ride
    along to diagonal neighbors — edge/corner halos arrive without any extra
    diagonal messages on the torus.
    """
    r = radius
    nb = n_batch
    splits = [(ax + nb, name, n)
              for ax, name, n in fabric.split_info(v.ndim - nb)]
    for axis, name, n in splits:
        if name is not None and n > 1 and v.shape[axis] < r:
            raise ValueError(
                f"halo depth {r} exceeds the local block extent {v.shape[axis]} "
                f"on axis {axis}; use fewer shards or a larger mesh")

    if not corners:
        vp = jnp.pad(v, [(0, 0)] * nb + [(r, r)] * (v.ndim - nb))
        for axis, name, n in splits:
            if name is None or n == 1:
                continue
            lo = _take_slab(v, axis, slice(0, r))
            hi = _take_slab(v, axis, slice(v.shape[axis] - r, None))
            from_lo, from_hi = _exchange(lo, hi, name, n)
            idx = lambda sl: tuple(
                slice(None) if i < nb
                else sl if i == axis
                else slice(r, r + v.shape[i]) for i in range(v.ndim))
            vp = vp.at[idx(slice(0, r))].set(from_lo)
            vp = vp.at[idx(slice(r + v.shape[axis], None))].set(from_hi)
        return vp

    vp = v
    for axis, name, n in splits:
        if name is None or n == 1:
            pad = [(0, 0)] * vp.ndim
            pad[axis] = (r, r)
            vp = jnp.pad(vp, pad)
        else:
            m = vp.shape[axis]
            lo = _take_slab(vp, axis, slice(0, r))
            hi = _take_slab(vp, axis, slice(m - r, None))
            from_lo, from_hi = _exchange(lo, hi, name, n)
            vp = jnp.concatenate([from_lo, vp, from_hi], axis=axis)
    return vp


def interior_apply(coeffs: StencilCoeffs, v: jax.Array, *,
                   policy: Policy = F32) -> jax.Array:
    """Zero-Dirichlet local apply in compute dtype — reads nothing a
    collective produced, so it is the work the overlap schedule runs while
    the halo faces are in flight.  Correct everywhere except the depth-r
    boundary ring bordering a split axis (patched afterwards).  ``v`` may
    carry a leading batch axis (shifts act on the trailing mesh dims).

    It is ``core.stencil.padded_apply`` over the zero-padded block, the
    blocking apply's own expression (see there for why it must be)."""
    r = coeffs.spec.radius
    nb = v.ndim - coeffs.ndim
    vp = jnp.pad(v, [(0, 0)] * nb + [(r, r)] * coeffs.ndim)
    return padded_apply(coeffs, vp, v.shape, policy=policy)


def local_apply(
    coeffs: StencilCoeffs,
    v: jax.Array,
    fabric: FabricAxes,
    *,
    policy: Policy = F32,
    schedule=None,
) -> jax.Array:
    """Local shard of u = A v with depth-r halo exchange.  Runs inside
    shard_map and handles every spec in the stencil family (the halo depth,
    and whether corners are exchanged, derive from the coefficient names).

    The communication schedule is pluggable (``core.comm.SCHEDULES``):

    * ``blocking`` is the paper-faithful streaming form: every term reads
      the fully assembled halo'd block (the analogue of the CS-1 fabric
      streams feeding multiply threads).
    * ``overlap`` (default) issues the halo ``ppermute``s first, computes
      the interior while the faces are in flight, and patches only the
      depth-r boundary ring — bit-identical to blocking, with a minimal
      collective-dependent region for the latency-hiding scheduler.

    ``schedule`` is a name or :class:`~repro.core.comm.CommSchedule`.
    """
    from repro.core.comm import scheduled_apply

    return scheduled_apply(coeffs, v, fabric, policy=policy, schedule=schedule)


# Reductions (paper §IV-3: AllReduce for the BiCGStab inner products) live
# with the operator backends — ``core.operator._make_reductions`` builds the
# fused (one psum per sync point) / separate (one psum per dot) schedules;
# the pipelined solvers (core/solvers/pipelined.py) take the schedule down
# to one AllReduce per iteration.


def global_apply(mesh, coeffs: StencilCoeffs, v: jax.Array, *, policy: Policy = F32,
                 schedule=None) -> jax.Array:
    """Convenience wrapper: one distributed SpMV on global arrays."""
    fabric = FabricAxes.from_mesh(mesh)
    nb = v.ndim - coeffs.ndim
    cf_spec = fabric.spec(coeffs.ndim)
    v_spec = fabric.spec(coeffs.ndim, n_batch=nb)

    def fn(cf, vv):
        return local_apply(cf, vv, fabric, policy=policy, schedule=schedule)

    return jax.shard_map(fn, mesh=mesh, in_specs=(cf_spec, v_spec),
                     out_specs=v_spec, check_vma=False)(coeffs, v)
