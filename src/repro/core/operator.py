"""The LinearOperator layer: one protocol, three interchangeable backends.

A :class:`LinearOperator` bundles everything a Krylov solver needs from the
matrix side —

* ``apply(v)``            : u = A v (the SpMV, local to this shard);
* ``dots(pairs, policy)`` : fully-reduced inner products (the operator owns
  the reduction schedule: local stack / fused psum / separate psums);
* ``apply_dots(v, w, which)`` : ``u = A v`` and the reduced ``<x, u>`` for
  ``x`` the ``which`` of ``u``, ``v`` and ``w``; where the backend has an
  SpMV that emits the local partials (``apply_partials``) they ride in its
  pass, else ``apply``, then ``dots``;
* ``reduce_partials(ps)`` : AllReduce of *precomputed* f32 local partials
  (the fused-kernel path computes partials inside Pallas epilogues and only
  needs the reduction);
* ``reduce_max(x)``       : fabric-wide max (spectral-bound setup);
* ``fused``               : optional :class:`FusedOps` — the Pallas fused
  update+dot passes that let BiCGStab run one iteration as fused kernels
  plus exactly 3 AllReduces.

Backends (:data:`BACKENDS`):

* ``reference`` — dense-shift oracle in a single address space (tests,
  small examples, the truth everything else is checked against);
* ``spmd``      — depth-r halo-exchange ``local_apply`` + psum reductions;
  must run inside ``shard_map`` (construct it in the mapped function over
  the *local* coefficient shard);
* ``pallas``    — the halo exchange feeding the fused stencil kernel
  (``kernels/stencil_nd``) plus the ``kernels/fused_iter`` vector passes,
  wired into the same shard_map loop.

Operators are built *inside* the shard_map body (they close over local
shards); drivers in ``core/bicgstab.py`` do that wiring.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.comm import (
    CommSchedule, OVERLAP, get_schedule, overlap_apply_partials, scheduled_apply,
)
from repro.core.halo import FabricAxes, interior_apply
from repro.core.precision import Policy, F32
from repro.core.solvers.common import (
    apply_then_dots, local_dots, local_partial,
)
from repro.core.stencil import StencilCoeffs, StencilSpec, apply_ref
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class FusedOps:
    """The fused Pallas iteration passes (see ``kernels/fused_iter``).

    Each callable returns its vector output(s) plus f32 *local* partial dot
    products; the solver batches the partials of one sync point into a
    single ``reduce_partials`` AllReduce.
    """

    dot_partial: Callable      # (a, b) -> f32 partial <a, b>
    update_q_dots: Callable    # (alpha, r, s, y) -> (q, <q,y>, <y,y>)
    update_xr_dots: Callable   # (alpha, omega, x, p, q, y, r0) -> (x, r, <r0,r>, <r,r>)
    update_p: Callable         # (beta, omega, r, p, s) -> p


@dataclasses.dataclass(frozen=True)
class LinearOperator:
    """A shard-local view of ``A`` plus its communication schedule.

    ``schedule`` is the halo-side :class:`~repro.core.comm.CommSchedule`
    the ``apply`` was built with (blocking vs overlapped exchange); the
    reduction side lives in ``dots``/``reduce_partials`` (fused vs separate
    psums) and, one level up, in the solver's recurrence structure (the
    pipelined variants fuse every sync point into one AllReduce).
    """

    name: str
    coeffs: StencilCoeffs
    policy: Policy
    apply: Callable
    dots: Callable
    reduce_partials: Callable
    reduce_max: Callable
    fused: FusedOps | None = None
    schedule: CommSchedule = OVERLAP
    #: (v, w, which) -> (A v, f32 local partials) from one pass, or None
    #: where it has none for these operands (:meth:`apply_dots`)
    apply_partials: Callable | None = None

    @property
    def spec(self):
        return self.coeffs.spec

    def with_apply(self, apply: Callable) -> "LinearOperator":
        """A copy with the SpMV swapped (how right preconditioning wraps);
        its dots are then the generic ones."""
        return dataclasses.replace(self, apply=apply, apply_partials=None)

    def apply_dots(self, v, w, which: tuple[str, ...]):
        """``(u, dots)``: ``u = A v`` and ``<x, u>`` for ``x`` the ``which``
        of ``u``, ``v`` and ``w`` (:func:`~repro.core.solvers.common
        .dot_operands`), reduced in one sync point: from
        ``apply_partials`` where it has a pass for these operands, else
        ``apply`` and then ``dots``."""
        got = None if self.apply_partials is None else self.apply_partials(
            v, w, which)
        if got is None:
            return apply_then_dots(self.apply, self.dots, self.policy)(
                v, w, which)
        u, partials = got
        return u, self.reduce_partials(partials)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

def _identity_reduce(partials):
    return jnp.stack([jnp.asarray(p, jnp.float32) for p in partials])


def _fabric_axis_names(fabric: FabricAxes) -> tuple[str, ...]:
    """Mesh axes that actually carry >1 shard.  Extent-1 axes reduce to the
    identity, and skipping them lets the distributed backends also run
    outside shard_map on a degenerate 1x1 fabric (single-block fused path).
    """
    pairs = ((fabric.x, fabric.nx), (fabric.y, fabric.ny), (fabric.z, fabric.nz))
    return tuple(a for a, n in pairs if a is not None and n > 1)


def _make_reductions(names: tuple[str, ...], fused_reductions: bool,
                     mesh_ndim: int | None = None):
    """(dots, reduce_partials, reduce_max) over the named fabric axes.

    ``mesh_ndim`` enables the batched (many-RHS) path: operands of higher
    rank produce per-RHS ``[B]`` partials, and a fused sync point psums the
    stacked ``[k, B]`` array in ONE AllReduce — the collective count is
    independent of the batch size.
    """
    def psum(x):
        return jax.lax.psum(x, names) if names else x

    if fused_reductions:
        def reduce_partials(ps):
            return psum(_identity_reduce(ps))
    else:
        def reduce_partials(ps):
            return jnp.stack([psum(jnp.asarray(p, jnp.float32)) for p in ps])

    def dots(pairs, policy):
        # local FMAC-style partials (see Policy.dot; per-RHS rows when
        # batched), then one psum per sync point (fused) or per dot
        # (paper-faithful separate)
        return reduce_partials(
            [local_partial(a, b, policy, mesh_ndim=mesh_ndim)
             for a, b in pairs])

    def reduce_max(x):
        return jax.lax.pmax(x, names) if names else x

    return dots, reduce_partials, reduce_max


def reference_operator(coeffs: StencilCoeffs, *, policy: Policy = F32,
                       schedule=None, **_unused) -> LinearOperator:
    """Single-address-space oracle: dense-shift apply, local reductions.

    There is no communication to schedule; ``schedule`` is validated and
    recorded so driver plumbing treats every backend uniformly.
    """
    cf = coeffs.astype(policy.storage)
    return LinearOperator(
        name="reference", coeffs=cf, policy=policy,
        apply=lambda v: apply_ref(cf, v, policy=policy),
        dots=lambda pairs, policy: local_dots(pairs, policy,
                                              mesh_ndim=cf.ndim),
        reduce_partials=_identity_reduce,
        reduce_max=lambda x: x,
        schedule=get_schedule(schedule),
    )


#: storage dtypes the stream kernel takes (it accumulates in f32)
STREAM_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def stream_applies(spec: StencilSpec, operand_ndim: int, dtype,
                   platform: str) -> bool:
    """Whether the spmd interior apply takes the plane-streaming Pallas
    kernel (``kernels/stencil_nd/stream.py``): on a TPU, for a 3-D star
    spec or the radius-1 box (27 points), on a bf16 or f32 operand
    without a leading batch axis.  Everything else — the CPU, wider
    boxes, batched many-RHS operands, f64 — keeps the jnp shifted-window
    apply."""
    return (platform == "tpu" and spec.ndim == 3
            and (spec.pattern == "star" or spec.radius == 1)
            and operand_ndim == spec.ndim
            and jnp.dtype(dtype) in STREAM_DTYPES)


def spmd_interior(coeffs: StencilCoeffs, policy: Policy):
    """The spmd backend's ``interior_fn``: the stream kernel where
    :func:`stream_applies`, else ``core.halo.interior_apply``; each traced
    call counts under ``operator.spmv_interior.<path>``."""
    def interior(v):
        if stream_applies(coeffs.spec, v.ndim, v.dtype,
                          jax.default_backend()):
            # imported here: only a process that runs the kernel pays for
            # importing Pallas
            from repro.kernels.stencil_nd.stream import stream_interior_apply

            obs_metrics.counter("operator.spmv_interior.stream").inc()
            return stream_interior_apply(coeffs, v, policy=policy)
        obs_metrics.counter("operator.spmv_interior.xla").inc()
        return interior_apply(coeffs, v, policy=policy)

    return interior


def stream_dots_apply(policy: Policy, *dtypes) -> bool:
    """Whether the stream kernel's dot epilogue forms ``policy.dot``'s
    products of operands of these dtypes: f32 reduction, and every operand
    exact in the compute dtype (its own dtype, or f32 compute)."""
    f32 = jnp.dtype(jnp.float32)
    return (jnp.dtype(policy.reduce) == f32
            and all(jnp.dtype(policy.compute) in (f32, jnp.dtype(d))
                    for d in dtypes))


def spmd_partials(coeffs: StencilCoeffs, fabric: FabricAxes, policy: Policy,
                  sched: CommSchedule):
    """The spmd backend's ``apply_partials``: where the overlap interior is
    the stream kernel (:func:`stream_applies`) and its epilogue forms
    :func:`stream_dots_apply`'s products, the partials ride in the kernel
    and the boundary ring's are added from the patched output; else None
    (``apply``, then the dots in passes of their own).  Each traced call
    counts under ``operator.spmv_dots.<path>``."""
    def apply_partials(v, w, which):
        if (sched.overlap_halo
                and stream_applies(coeffs.spec, v.ndim, v.dtype,
                                   jax.default_backend())
                and stream_dots_apply(policy, policy.storage, v.dtype,
                                      *([] if w is None else [w.dtype]))):
            from repro.kernels.stencil_nd.stream import stream_interior_partials

            obs_metrics.counter("operator.spmv_dots.stream").inc()
            return overlap_apply_partials(
                coeffs, v, w, which, fabric, policy=policy,
                interior_fn=lambda v, ring: stream_interior_partials(
                    coeffs, v, w, which, policy=policy, ring=ring))
        obs_metrics.counter("operator.spmv_dots.xla").inc()
        return None

    return apply_partials


def spmd_operator(coeffs: StencilCoeffs, fabric: FabricAxes | None = None, *,
                  policy: Policy = F32, schedule=None,
                  fused_reductions: bool = True,
                  **_unused) -> LinearOperator:
    """Halo-exchange SPMD backend (the paper's scheme; runs inside shard_map).

    ``schedule`` picks the halo schedule (``core.comm.SCHEDULES``).
    The overlap schedule's interior is :func:`spmd_interior`, and its
    SpMV-with-dots :func:`spmd_partials`.
    """
    fabric = fabric or FabricAxes()
    cf = coeffs.astype(policy.storage)
    sched = get_schedule(schedule)
    dots, reduce_partials, reduce_max = _make_reductions(
        _fabric_axis_names(fabric), fused_reductions, mesh_ndim=cf.ndim)
    interior = spmd_interior(cf, policy)
    return LinearOperator(
        name="spmd", coeffs=cf, policy=policy,
        apply=lambda v: scheduled_apply(cf, v, fabric, policy=policy,
                                        schedule=sched, interior_fn=interior),
        dots=dots,
        reduce_partials=reduce_partials,
        reduce_max=reduce_max,
        schedule=sched,
        apply_partials=spmd_partials(cf, fabric, policy, sched),
    )


def pallas_operator(coeffs: StencilCoeffs, fabric: FabricAxes | None = None, *,
                    policy: Policy = F32, schedule=None,
                    fused_reductions: bool = True,
                    interpret: bool | None = None,
                    fuse_ring: bool | None = None, **_unused) -> LinearOperator:
    """Pallas-fused backend: halo exchange + fused stencil kernel for the
    SpMV, ``kernels/fused_iter`` passes for the vector updates and dot
    partials.  Runs inside shard_map; one BiCGStab iteration lowers to
    fused kernels + 3 AllReduces end to end.

    Kernel tile shapes resolve through the persistent tuning cache
    (``core/tuning``) at trace time, so a swept {spec x dtype x local
    shape} cell transparently gets its tuned config.  ``fuse_ring``
    overrides the cache's boundary-ring epilogue choice for the overlap
    schedule (None = let the cache decide).
    """
    from repro.kernels import resolve_interpret
    from repro.kernels.fused_iter import (
        dot_mixed, update_p, update_q_dots, update_xr_dots,
    )
    from repro.kernels.stencil_nd.ops import pallas_local_apply

    fabric = fabric or FabricAxes()
    cf = coeffs.astype(policy.storage)
    sched = get_schedule(schedule)
    it = resolve_interpret(interpret)
    _dots, reduce_partials, reduce_max = _make_reductions(
        _fabric_axis_names(fabric), fused_reductions, mesh_ndim=cf.ndim)

    cf_unit = StencilCoeffs(cf.diags)  # the kernel's unit-diagonal contract
    base_apply = lambda v: pallas_local_apply(cf_unit, v, fabric, policy=policy,
                                              schedule=sched, interpret=it,
                                              fuse_ring=fuse_ring)
    if cf.diag is None:
        apply = base_apply
    else:
        # The stencil kernel assumes the family's unit main diagonal; a raw
        # (non-normalized) operator adds its (d - 1) deviation elementwise.
        c = policy.compute
        dcorr = (cf.diag.astype(c) - jnp.asarray(1, c))

        def apply(v):
            return (base_apply(v).astype(c) + dcorr * v.astype(c)).astype(policy.storage)

    # the fused_iter passes switch to their per-RHS-tiled variants whenever
    # an operand carries a leading batch axis (rank above the mesh rank)
    batched = lambda a: a.ndim > cf.ndim
    dot_partial = lambda a, b: dot_mixed(a, b, interpret=it,
                                         batched=batched(a))

    return LinearOperator(
        name="pallas", coeffs=cf, policy=policy,
        apply=apply,
        dots=lambda pairs, policy: reduce_partials(
            [dot_partial(a, b) for a, b in pairs]),
        reduce_partials=reduce_partials,
        reduce_max=reduce_max,
        schedule=sched,
        fused=FusedOps(
            dot_partial=dot_partial,
            update_q_dots=lambda alpha, r, s, y: update_q_dots(
                alpha, r, s, y, interpret=it, batched=batched(r)),
            update_xr_dots=lambda alpha, omega, x, p, q, y, r0: update_xr_dots(
                alpha, omega, x, p, q, y, r0, interpret=it, batched=batched(x)),
            update_p=lambda beta, omega, r, p, s: update_p(
                beta, omega, r, p, s, interpret=it, batched=batched(r)),
        ),
    )


#: backend name -> constructor; launch/solve.py and benchmarks key off this.
BACKENDS = {
    "reference": reference_operator,
    "spmd": spmd_operator,
    "pallas": pallas_operator,
}


def make_operator(backend: str, coeffs: StencilCoeffs,
                  fabric: FabricAxes | None = None, *, policy: Policy = F32,
                  **kwargs) -> LinearOperator:
    """Build a backend by name, each of its layers in its scope
    (:func:`named_layers`).  ``fabric`` is required semantics for the
    distributed backends (pass the shard_map-local view); the reference
    backend ignores it."""
    try:
        ctor = BACKENDS[backend]
    except KeyError:
        raise KeyError(f"unknown backend {backend!r}; have {sorted(BACKENDS)}") from None
    if backend == "reference":
        return named_layers(ctor(coeffs, policy=policy, **kwargs))
    return named_layers(ctor(coeffs, fabric, policy=policy, **kwargs))


def named_layers(op: LinearOperator) -> LinearOperator:
    """``op`` with every layer it carries opened in its scope
    (``obs.trace.SCOPES``), so the compiled solve names its work: the SpMV
    (halo exchange, interior, ring, kernels) in ``spmv``, the inner
    products and their AllReduce in ``dots``, the fused update kernels in
    ``update`` (their dot partials ride in the update's pass, so their
    bytes are the update's).  An SpMV-with-dots is ``spmv``: the partials
    its kernel emits are the SpMV's bytes."""
    spmv, dots, update = (obs_trace.scoped(n) for n in ("spmv", "dots", "update"))
    f = op.fused
    if f is not None:
        f = FusedOps(dot_partial=dots(f.dot_partial),
                     update_q_dots=update(f.update_q_dots),
                     update_xr_dots=update(f.update_xr_dots),
                     update_p=update(f.update_p))
    ap = op.apply_partials
    return dataclasses.replace(op, apply=spmv(op.apply), dots=dots(op.dots),
                               reduce_partials=dots(op.reduce_partials),
                               fused=f, apply_partials=None if ap is None else spmv(ap))
