"""Communication scheduling: when collectives run relative to compute.

The paper's CS-1 wins because its fabric makes the two communication
patterns of an iterative stencil solve — nearest-neighbor halo transfers
and the scalar AllReduce of the inner products — cost almost nothing
(§IV, Table I).  On commodity fabrics those costs are real, and the only
lever software holds is the *schedule*: issue the transfers early, shrink
their dependent region, and let independent compute run underneath
(Jacquelin et al.'s overlapped stencil algorithm; Belli & De Sensi's
schedule study on the WSE).

This module makes that schedule a first-class, pluggable object:

* :class:`CommSchedule` — a named policy carried by
  :class:`~repro.core.operator.LinearOperator` and selected end to end via
  ``--schedule`` (launch drivers), ``SolverOptions.schedule`` (CFD app) and
  the workload cell configs.

  - ``blocking`` is the paper-faithful streaming form: assemble the full
    halo'd block, then compute every term from it — the apply *depends* on
    every collective.
  - ``overlap`` splits the apply: the depth-r halo exchange is *started*
    first (:func:`start_halo_exchange`), the interior — which needs no halo
    — is computed while the faces are in flight, and only the depth-r
    boundary ring is patched from the exchanged block
    (:func:`boundary_ring_apply`).  The collectives' dependent region is
    minimal, so XLA's latency-hiding scheduler runs them under the interior
    work.  The result is bit-identical to ``blocking``: both paths
    accumulate the same terms in the same (canonical spec) order, through
    one expression of the stencil sum (``core.stencil.padded_apply``; the
    XLA interior is it over a zero-padded block), so the compiler
    contracts their multiply-adds the same way.

* :func:`scheduled_apply` — the one composition point: every operator
  backend's SpMV is ``scheduled_apply`` with a backend-specific interior
  (pure-jnp shifts for ``spmd``, the fused Pallas kernel for ``pallas``).

The AllReduce side of the schedule lives with the solvers: the pipelined
Krylov variants (``core/solvers/pipelined.py``) restructure the recurrences
so each iteration has exactly one fused AllReduce, the reduction analogue
of ``overlap``.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.core.halo import FabricAxes, gather_halo, interior_apply
from repro.core.precision import Policy, F32
from repro.core.solvers.common import dot_operands
from repro.core.stencil import StencilCoeffs, padded_apply


@dataclasses.dataclass(frozen=True)
class CommSchedule:
    """A named policy for ordering collectives against compute.

    ``overlap_halo`` selects the split interior/boundary-ring apply;
    schedules are value objects so they can ride in static config
    (``SolverOptions``, workload cells) and be compared by name.
    """

    name: str
    overlap_halo: bool

    def __str__(self) -> str:  # CLI/config round-trip
        return self.name


BLOCKING = CommSchedule("blocking", overlap_halo=False)
OVERLAP = CommSchedule("overlap", overlap_halo=True)

#: schedule name -> schedule; launch drivers and configs key off this.
SCHEDULES = {s.name: s for s in (BLOCKING, OVERLAP)}


def get_schedule(schedule, default: CommSchedule = OVERLAP) -> CommSchedule:
    """Normalize a name / CommSchedule / None (``default``)."""
    if schedule is None:
        return default
    if isinstance(schedule, CommSchedule):
        return schedule
    try:
        return SCHEDULES[schedule]
    except KeyError:
        raise KeyError(
            f"unknown comm schedule {schedule!r}; have {sorted(SCHEDULES)}"
        ) from None


# ---------------------------------------------------------------------------
# Halo-exchange phases
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HaloExchange:
    """An issued depth-r halo exchange.

    Holds the r-padded block with halos filled.  The ``ppermute``s are
    issued when this object is constructed; nothing the interior apply
    computes depends on it, so everything between ``start_halo_exchange``
    and the first read of ``padded`` can run under the transfers.
    """

    padded: jax.Array
    radius: int
    shape: tuple[int, ...]          # the unpadded local *mesh* block shape
    n_batch: int = 0                # leading batch axes riding the exchange


def start_halo_exchange(v: jax.Array, fabric: FabricAxes, radius: int, *,
                        corners: bool = False, n_batch: int = 0) -> HaloExchange:
    """Issue the depth-r slab ``ppermute``s and return the in-flight handle.

    With ``n_batch`` leading batch axes, each ppermute message carries the
    slab of every RHS at once (``(B, r, ...)``) — the message count per
    exchange is independent of the batch size.
    """
    padded = gather_halo(v, fabric, radius, corners=corners, n_batch=n_batch)
    return HaloExchange(padded, radius, v.shape[n_batch:], n_batch)


def boundary_regions(shape: tuple[int, ...], fabric: FabricAxes,
                     radius: int) -> list[tuple[slice, ...]]:
    """The depth-r slabs of the local block that read halo values: two per
    split fabric axis.  Slabs of different axes overlap at edges/corners;
    patching them with ``set()`` is idempotent there."""
    regions = []
    for axis, name, n in fabric.split_info(len(shape)):
        if name is None or n == 1:
            continue
        for side in (slice(0, radius), slice(shape[axis] - radius, None)):
            regions.append(tuple(side if i == axis else slice(None)
                                 for i in range(len(shape))))
    return regions


def ring_pieces(shape: tuple[int, ...], fabric: FabricAxes,
                radius: int) -> list[tuple[slice, ...]]:
    """The points of :func:`boundary_regions` as disjoint boxes: each split
    axis's two slabs, cut to the inner range of the split axes before it."""
    pieces, inner = [], [slice(None)] * len(shape)
    for axis, name, n in fabric.split_info(len(shape)):
        if name is None or n == 1:
            continue
        m = shape[axis]
        for side in (slice(0, radius), slice(max(radius, m - radius), m)):
            pieces.append(tuple(side if i == axis else inner[i]
                                for i in range(len(shape))))
        inner[axis] = slice(radius, m - radius)
    return pieces


def boundary_ring_apply(coeffs: StencilCoeffs, exchange: HaloExchange,
                        u: jax.Array, fabric: FabricAxes, *,
                        policy: Policy = F32) -> jax.Array:
    """Overwrite the boundary ring of ``u`` with halo-correct values.

    ``u`` is an interior apply (zero-Dirichlet); only the depth-r shell
    bordering a split axis can differ, and each of its cells is recomputed
    from the exchanged block with the same term order as the full apply —
    the patched result is bit-identical to the blocking path.
    """
    pre = (slice(None),) * exchange.n_batch
    for reg in boundary_regions(exchange.shape, fabric, exchange.radius):
        u = u.at[pre + reg].set(
            padded_apply(coeffs, exchange.padded, exchange.shape,
                         policy=policy, region=reg).astype(u.dtype))
    return u


# ---------------------------------------------------------------------------
# The composition point
# ---------------------------------------------------------------------------

def scheduled_apply(coeffs: StencilCoeffs, v: jax.Array, fabric: FabricAxes, *,
                    policy: Policy = F32,
                    schedule: CommSchedule | str | None = None,
                    full_fn=None, interior_fn=None,
                    patch_fn=None, fused_fn=None) -> jax.Array:
    """u = A v on the local shard under the given communication schedule.

    This is the one place the schedule's structure lives; backends
    customize only *how* each piece computes, via hooks that default to
    the pure-jnp shifted-window applies:

    * ``full_fn(vp) -> u`` — the blocking apply over the assembled halo'd
      block (the Pallas backend passes its fused kernel);
    * ``interior_fn(v) -> u`` — the zero-Dirichlet local apply run while
      the faces are in flight (no collective inputs allowed; Pallas: the
      kernel on the zero-padded block);
    * ``patch_fn(exchange, u) -> u`` — overwrite the depth-r boundary ring
      from the exchanged block, already cast to the output dtype (Pallas:
      the kernel re-run on the ring slabs, so overlap stays bit-identical
      to its blocking path);
    * ``fused_fn(exchange) -> u`` — the fused boundary-ring epilogue: one
      pass that computes interior *and* ring from the in-flight exchange
      (Pallas: a single kernel launch instead of interior + patches).
      When given, it replaces the interior/patch pair entirely — the
      exchange is still issued first, so the latency-hiding scheduler can
      run independent work (AXPYs, the preconditioner's local sweeps)
      under the transfers even though the SpMV itself now waits on them.
      Selected per-cell by the tuning cache where the autotune sweep says
      it wins (``kernels/stencil_nd/fused.py``).

    For bit-identity across schedules a backend's hooks must accumulate
    terms in the same canonical order (``StencilCoeffs.ordered_items``) as
    each other — the defaults and the Pallas kernel all do, for every
    epilogue form.
    """
    spec = coeffs.spec
    r = spec.radius
    nb = v.ndim - coeffs.ndim       # leading batch (many-RHS) axes
    sched = get_schedule(schedule)

    # the halo exchange names itself (``halo`` scope, in gather_halo); the
    # rest is the SpMV's (``spmv``, opened by the operator around this)
    if not sched.overlap_halo:
        vp = gather_halo(v, fabric, r, corners=spec.needs_corners, n_batch=nb)
        if full_fn is not None:
            return full_fn(vp)
        return padded_apply(coeffs, vp, v.shape,
                            policy=policy).astype(policy.storage)

    exchange = start_halo_exchange(v, fabric, r, corners=spec.needs_corners,
                                   n_batch=nb)
    if fused_fn is not None:
        return fused_fn(exchange)
    if interior_fn is None:
        u = interior_apply(coeffs, v, policy=policy)
    else:
        u = interior_fn(v)
    if patch_fn is not None:
        return patch_fn(exchange, u)
    u = boundary_ring_apply(coeffs, exchange, u, fabric, policy=policy)
    return u.astype(policy.storage)


def overlap_apply_partials(coeffs: StencilCoeffs, v: jax.Array, w, which,
                           fabric: FabricAxes, *, policy: Policy,
                           interior_fn) -> tuple[jax.Array, list]:
    """The overlap schedule's ``u = A v`` with the f32 local partials
    ``<x, u>`` for ``x`` the ``which`` of ``u``, ``v`` and ``w``
    (:func:`~repro.core.solvers.common.dot_operands`).

    ``interior_fn(v, ring) -> (u, partials)`` is the zero-Dirichlet apply
    with partials over the points farther than ``ring[a]`` from either face
    of each axis ``a`` (the radius on a split axis, else 0): the points the
    boundary ring patch rewrites.  Their products are added from the
    patched ``u``, one disjoint piece of the ring at a time.
    """
    spec = coeffs.spec
    r = spec.radius
    exchange = start_halo_exchange(v, fabric, r, corners=spec.needs_corners)
    ring = tuple(r if name is not None and n > 1 else 0
                 for _, name, n in fabric.split_info(v.ndim))
    u, partials = interior_fn(v, ring)
    u = boundary_ring_apply(coeffs, exchange, u, fabric, policy=policy)
    u = u.astype(policy.storage)
    for piece in ring_pieces(v.shape, fabric, r):
        partials = [p + policy.dot(x[piece], u[piece])
                    for p, x in zip(partials, dot_operands(which, u, v, w))]
    return u, partials
