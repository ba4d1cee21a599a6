"""Stencil-family operators (paper §IV, generalized beyond the 7-point shape).

The paper's matrix ``A`` has seven nonzero diagonals; after diagonal (Jacobi)
preconditioning the main diagonal is all ones, so only the six off-diagonals
are stored (paper: "we only store six other diagonals").  Coefficients are
stored as one mesh-shaped array per diagonal, exactly the per-core layout of
Listing 1 (xp, xm, yp, ym, zp, zm) generalized from one Z-pencil per core to
one sub-volume per chip.

This module generalizes that layout to a stencil *family* parameterized by a
:class:`StencilSpec` — pattern ∈ {star, box} and radius r:

* ``star`` r=1 is the paper's 7-point shape (5-point in 2D);
* ``star`` r=4 is the 25-point shape of Jacquelin et al.'s seismic-RTM
  stencil (8th-order finite differences, 8 points per axis + center);
* ``box``  r=1 is the 27-point shape (corner/edge couplings, e.g. trilinear
  FEM mass matrices and Belli & De Sensi's WSE stencil study).

Each off-diagonal is named canonically (legacy ``xp``/``zm`` names for the
radius-1 star offsets, ``xp2``-style names for deeper star offsets,
``d1_-1_0``-style names for box offsets) so a :class:`StencilCoeffs` is
self-describing: :func:`spec_of` recovers the spec from the diagonal names.

Boundary semantics are zero-Dirichlet: a shift that crosses the mesh edge
contributes zero (on CS-1 this was achieved by zero-padding the local
arrays; here by zero-fill of ``ppermute`` at fabric edges / ``jnp.pad``).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import Policy, F32

# Order matters and is shared with the Pallas kernel and the dense builder.
DIAGS_3D = ("xp", "xm", "yp", "ym", "zp", "zm")
DIAGS_2D = ("xp", "xm", "yp", "ym")

# Offset (in mesh coordinates) of the neighbor each diagonal reads.
OFFSETS = {
    "xp": (1, 0, 0), "xm": (-1, 0, 0),
    "yp": (0, 1, 0), "ym": (0, -1, 0),
    "zp": (0, 0, 1), "zm": (0, 0, -1),
}

_AXES = "xyz"
_STAR_NAME = re.compile(r"^([xyz])([pm])(\d*)$")


def offset_name(off: tuple[int, ...]) -> str:
    """Canonical diagonal name of a neighbor offset.

    Radius-1 star offsets keep the paper's names (``xp`` .. ``zm``); deeper
    star offsets append the distance (``xp2`` reads ``v[i+2,j,k]``); offsets
    touching more than one axis (box stencils) spell the vector out
    (``d1_-1_0`` reads ``v[i+1,j-1,k]``).
    """
    nz = [(i, o) for i, o in enumerate(off) if o != 0]
    if len(nz) == 1:
        ax, o = nz[0]
        base = f"{_AXES[ax]}{'p' if o > 0 else 'm'}"
        return base if abs(o) == 1 else f"{base}{abs(o)}"
    return "d" + "_".join(str(o) for o in off)


def name_offset(name: str, ndim: int = 3) -> tuple[int, ...]:
    """Inverse of :func:`offset_name` (also accepts the legacy names)."""
    if name.startswith("d"):
        off = tuple(int(t) for t in name[1:].split("_"))
        if len(off) != ndim:
            raise ValueError(f"offset name {name!r} is {len(off)}-D, mesh is {ndim}-D")
        return off
    m = _STAR_NAME.match(name)
    if not m:
        raise ValueError(f"unrecognized diagonal name {name!r}")
    ax = _AXES.index(m.group(1))
    dist = int(m.group(3) or 1) * (1 if m.group(2) == "p" else -1)
    if ax >= ndim:
        raise ValueError(f"diagonal {name!r} names axis {ax} on a {ndim}-D mesh")
    return tuple(dist if i == ax else 0 for i in range(ndim))


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """A stencil shape: ``star`` (axis-aligned arms) or ``box`` (full cube).

    ``star`` with radius r couples ``2*ndim*r`` neighbors (r=1 => the paper's
    7-point shape); ``box`` couples ``(2r+1)**ndim - 1`` (r=1 => 27-point).
    The spec carries no coefficients — it is the *shape* contract shared by
    the reference apply, the halo exchange (depth = radius, corners only for
    box), and the fused Pallas kernel.
    """

    pattern: str            # "star" | "box"
    radius: int
    ndim: int = 3

    def __post_init__(self):
        if self.pattern not in ("star", "box"):
            raise ValueError(f"pattern must be 'star' or 'box', got {self.pattern!r}")
        if self.radius < 1:
            raise ValueError(f"radius must be >= 1, got {self.radius}")
        if self.ndim not in (2, 3):
            raise ValueError(f"ndim must be 2 or 3, got {self.ndim}")

    @functools.cached_property
    def offsets(self) -> tuple[tuple[int, ...], ...]:
        """Neighbor offsets (center excluded), in canonical order.

        Star order extends the legacy (xp, xm, yp, ym, zp, zm): axis-major,
        then distance, ``+`` before ``-`` — so radius-1 star names/order are
        bit-identical with the seed's 7-point layout.
        """
        if self.pattern == "star":
            offs = []
            for ax in range(self.ndim):
                for dist in range(1, self.radius + 1):
                    for sign in (+1, -1):
                        offs.append(tuple(sign * dist if i == ax else 0
                                          for i in range(self.ndim)))
            return tuple(offs)
        rng = range(-self.radius, self.radius + 1)
        return tuple(o for o in itertools.product(*([rng] * self.ndim))
                     if any(o))

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(offset_name(o) for o in self.offsets)

    @property
    def n_offsets(self) -> int:
        return len(self.offsets)

    @property
    def n_points(self) -> int:
        """Stencil points including the center (7, 13, 25, 27, ...)."""
        return self.n_offsets + 1

    @property
    def name(self) -> str:
        return f"{self.pattern}{self.n_points}"

    @property
    def needs_corners(self) -> bool:
        """True iff the halo exchange must fill edge/corner halo regions."""
        return self.pattern == "box"


STAR7 = StencilSpec("star", 1, 3)
STAR13 = StencilSpec("star", 2, 3)
STAR25 = StencilSpec("star", 4, 3)
BOX27 = StencilSpec("box", 1, 3)

#: CLI-facing registry; launch/solve.py, configs and benchmarks key off this.
SPECS = {s.name: s for s in (STAR7, STAR13, STAR25, BOX27)}


def get_spec(name: str) -> StencilSpec:
    try:
        return SPECS[name]
    except KeyError:
        raise KeyError(f"unknown stencil {name!r}; have {sorted(SPECS)}") from None


def spec_of(names, ndim: int = 3) -> StencilSpec:
    """Recover the spec a set of diagonal names was generated from.

    Pattern is ``box`` iff any offset touches more than one axis; radius is
    the max offset magnitude.  Used by the halo exchange and the kernels to
    size the halo without threading a spec argument through every call.
    """
    offs = [name_offset(n, ndim) for n in names]
    radius = max(max(abs(o) for o in off) for off in offs)
    box = any(sum(o != 0 for o in off) > 1 for off in offs)
    return StencilSpec("box" if box else "star", radius, ndim)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StencilCoeffs:
    """Off-diagonal coefficient fields of a stencil matrix.

    ``diags[name]`` has the mesh shape; entry ``diags['xp'][i,j,k]`` multiplies
    ``v[i+1,j,k]`` when computing row ``(i,j,k)`` of ``A @ v``.

    ``diag`` is the main diagonal: ``None`` means the family's canonical
    *unit* diagonal (the paper's Jacobi-normalized form — "we only store six
    other diagonals"); a stored array makes this a *raw* operator whose
    diagonal varies per row (e.g. :func:`heterogeneous_poisson`), the case
    where Jacobi preconditioning does real work.
    """

    diags: dict[str, jax.Array]
    diag: jax.Array | None = None

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self.diags)

    @property
    def shape(self) -> tuple[int, ...]:
        return next(iter(self.diags.values())).shape

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return next(iter(self.diags.values())).dtype

    @property
    def spec(self) -> StencilSpec:
        """The :class:`StencilSpec` implied by the diagonal names."""
        return spec_of(self.names, self.ndim)

    def offsets(self) -> dict[str, tuple[int, ...]]:
        """name -> neighbor offset for every stored diagonal."""
        return {n: name_offset(n, self.ndim) for n in self.diags}

    def ordered_items(self) -> list[tuple[str, jax.Array]]:
        """(name, coefficient) pairs in the spec's canonical offset order.

        Pytree boundaries re-sort the ``diags`` dict, so its iteration
        order is not stable.  Every apply path (``apply_ref``, the halo
        interior/padded applies, the Pallas kernel's argument order)
        accumulates terms in THIS order — the single invariant behind the
        cross-schedule bitwise-identity guarantee of ``core/comm.py``.
        """
        return [(n, self.diags[n]) for n in self.spec.names if n in self.diags]

    def astype(self, dtype) -> "StencilCoeffs":
        return StencilCoeffs(
            {k: v.astype(dtype) for k, v in self.diags.items()},
            diag=None if self.diag is None else self.diag.astype(dtype))

    def normalized(self) -> tuple["StencilCoeffs", jax.Array | None]:
        """Left-Jacobi row scaling: ``(unit-diagonal coeffs, diag)``.

        ``D^-1 A`` has unit diagonal and off-diagonals ``cf/diag`` — exactly
        the paper's pre-normalization.  Returns ``(self, None)`` when
        already normalized.
        """
        if self.diag is None:
            return self, None
        d = self.diag
        return StencilCoeffs({k: v / d.astype(v.dtype)
                              for k, v in self.diags.items()}), d

    def tree_flatten(self):
        keys = tuple(sorted(self.diags))
        children = tuple(self.diags[k] for k in keys)
        if self.diag is not None:
            return children + (self.diag,), (keys, True)
        return children, (keys, False)

    @classmethod
    def tree_unflatten(cls, aux, values):
        # pre-diag pickles/callers may pass bare key tuples
        if len(aux) == 2 and isinstance(aux[1], bool):
            keys, has_diag = aux
        else:
            keys, has_diag = aux, False
        if has_diag:
            return cls(dict(zip(keys, values[:-1])), diag=values[-1])
        return cls(dict(zip(keys, values)))


def _window(vp: jax.Array, off: tuple[int, ...], shape: tuple[int, ...],
            r: int, n_batch: int = 0) -> jax.Array:
    """The ``shape``-sized window of the r-padded block shifted by ``off``.

    ``n_batch`` leading axes of ``vp`` are unpadded batch axes, taken whole.
    """
    return vp[(slice(None),) * n_batch
              + tuple(slice(r + o, r + o + n) for o, n in zip(off, shape))]


def padded_apply(
    coeffs: StencilCoeffs,
    vp: jax.Array,
    shape: tuple[int, ...],
    *,
    policy: Policy = F32,
    region: tuple[slice, ...] | None = None,
) -> jax.Array:
    """u = A v from an r-padded local block (halos already in place).

    ``vp`` (and ``shape``) may carry a leading batch axis: the coefficients
    broadcast across it and ``region`` keeps addressing the trailing mesh
    dims only.

    ``region`` restricts the computation to a sub-box of the local block —
    used by the overlap schedule to recompute only the halo-dependent
    boundary ring (``core.comm.boundary_ring_apply``).

    This is the one expression of the stencil sum in XLA: :func:`apply_ref`,
    the overlap interior (``core.halo.interior_apply``), the blocking apply
    and the ring patch all call it, and must.  XLA may contract a product
    and its add into one fused multiply-add, and a sum written another way
    is contracted differently at some points, one ulp apart; the bitwise
    schedule and batch contracts (``tests/test_operator_backends.py``,
    ``tests/test_batched.py``) rest on every path sharing this expression.
    """
    spec = coeffs.spec
    c = policy.compute
    nb = vp.ndim - coeffs.ndim
    mesh_shape = tuple(shape[len(shape) - coeffs.ndim:])
    reg = region if region is not None else tuple(slice(None) for _ in mesh_shape)
    vreg = (slice(None),) * nb + tuple(reg)
    sub = lambda off: _window(vp, off, mesh_shape, spec.radius, nb)[vreg].astype(c)
    center = sub((0,) * coeffs.ndim)
    if coeffs.diag is None:  # unit main diagonal (Jacobi-normalized family)
        u = center
    else:
        u = coeffs.diag[reg].astype(c) * center
    for name, cf in coeffs.ordered_items():   # canonical order — see StencilCoeffs
        u = u + cf[reg].astype(c) * sub(name_offset(name, coeffs.ndim))
    return u


def apply_ref(coeffs: StencilCoeffs, v: jax.Array, *, policy: Policy = F32) -> jax.Array:
    """Reference (single-address-space) u = A v.  Oracle for everything else.

    Works for every stencil in the family: each stored diagonal contributes
    ``coeff * v[idx + offset]`` with zero-Dirichlet shifts.  Follows the
    paper's arithmetic: products and accumulating adds run in
    ``policy.compute`` (Table I counts these as half precision in the mixed
    policy); the unit diagonal contributes ``v`` directly.

    ``v`` may carry a leading batch axis (shape ``(B,) + coeffs.shape``):
    the offsets act on the trailing mesh dims and the coefficients
    broadcast across the batch, so one call applies A to B right-hand
    sides at once (and the ``B=1`` result is bitwise identical to the
    unbatched apply — same elementwise arithmetic, broadcast axis aside).

    Terms accumulate in the canonical order of ``coeffs.ordered_items()``
    — the same order every distributed apply path and the Pallas kernel
    use, which keeps the backends bit-comparable.  It is
    :func:`padded_apply` over the zero-padded block.
    """
    r = coeffs.spec.radius
    nb = v.ndim - coeffs.ndim          # leading batch axes (0 or 1)
    vp = jnp.pad(v, [(0, 0)] * nb + [(r, r)] * coeffs.ndim)
    return padded_apply(coeffs, vp, v.shape, policy=policy).astype(policy.storage)


def to_dense(coeffs: StencilCoeffs) -> np.ndarray:
    """Materialize A as a dense (N, N) float64 matrix (small meshes only)."""
    shape = coeffs.shape
    n = int(np.prod(shape))
    if coeffs.diag is None:
        A = np.eye(n, dtype=np.float64)
    else:
        A = np.diag(np.asarray(coeffs.diag, np.float64).ravel())
    idx = np.arange(n).reshape(shape)
    for name, cf in coeffs.diags.items():
        cf = np.asarray(cf, dtype=np.float64)
        off = name_offset(name, len(shape))
        src = idx
        for ax, o in enumerate(off):
            src = np.roll(src, -o, axis=ax)
        # zero out rows whose neighbor crosses the boundary
        valid = np.ones(shape, dtype=bool)
        for ax, o in enumerate(off):
            sl = [slice(None)] * len(shape)
            if o >= 1:
                sl[ax] = slice(-o, None)
                valid[tuple(sl)] = False
            elif o <= -1:
                sl[ax] = slice(0, -o)
                valid[tuple(sl)] = False
        rows = idx[valid].ravel()
        cols = src[valid].ravel()
        A[rows, cols] += cf[valid].ravel()
    return A


# ---------------------------------------------------------------------------
# Problem generators
# ---------------------------------------------------------------------------

def _default_spec(shape, spec: StencilSpec | None) -> StencilSpec:
    if spec is None:
        return StencilSpec("star", 1, len(shape))
    if spec.ndim != len(shape):
        raise ValueError(f"spec is {spec.ndim}-D but mesh shape {shape} is {len(shape)}-D")
    return spec


def poisson(shape: tuple[int, ...], dtype=jnp.float32,
            spec: StencilSpec | None = None) -> StencilCoeffs:
    """Jacobi-preconditioned constant-coefficient Laplacian-like operator.

    The raw operator has diagonal ``n_offsets`` and off-diagonals ``-1``;
    preconditioning by the diagonal gives unit diagonal and ``-1/n_offsets``
    off-diagonals — symmetric and weakly diagonally dominant for every spec
    (the classic 7-point model problem when ``spec`` is the default star r=1,
    the 27-point "full-neighborhood" Laplacian for ``BOX27``).
    """
    spec = _default_spec(shape, spec)
    c = -1.0 / spec.n_offsets
    return StencilCoeffs({n: jnp.full(shape, c, dtype=dtype) for n in spec.names})


def random_nonsymmetric(
    key: jax.Array,
    shape: tuple[int, ...],
    dtype=jnp.float32,
    *,
    dominance: float = 1.25,
    spec: StencilSpec | None = None,
) -> StencilCoeffs:
    """Random nonsymmetric diagonally-dominant stencil (BiCGStab's habitat).

    Off-diagonal magnitudes sum to ``1/dominance`` per row so the Jacobi-
    preconditioned matrix is strictly diagonally dominant => BiCGStab
    converges.  Signs are random => A is nonsymmetric, like the upwinded
    convection-diffusion systems MFIX produces (paper §VI).  Works for any
    spec in the family (star25 and box27 included).
    """
    names = _default_spec(shape, spec).names
    keys = jax.random.split(key, len(names) + 1)
    mags = {
        n: jax.random.uniform(k, shape, jnp.float32, 0.05, 1.0)
        for n, k in zip(names, keys[:-1])
    }
    total = sum(mags.values())
    signs = {
        n: jnp.where(jax.random.bernoulli(k, 0.5, shape), 1.0, -1.0)
        for n, k in zip(names, jax.random.split(keys[-1], len(names)))
    }
    return StencilCoeffs(
        {n: (signs[n] * mags[n] / (dominance * total)).astype(dtype) for n in names}
    )


def convection_diffusion(
    shape: tuple[int, ...],
    dtype=jnp.float32,
    *,
    peclet: float = 5.0,
) -> StencilCoeffs:
    """Upwinded convection-diffusion operator, Jacobi preconditioned.

    A deterministic nonsymmetric model of the paper's momentum equations:
    diffusion contributes -1 per face; a constant velocity field (1, 0.5,
    0.25) upwinds the convection term with cell Peclet number ``peclet``.
    """
    ndim = len(shape)
    vel = (1.0, 0.5, 0.25)[:ndim]
    names = DIAGS_3D if ndim == 3 else DIAGS_2D
    raw: dict[str, float] = {}
    diag = 0.0
    for ax, name_pair in enumerate(zip(names[0::2], names[1::2])):
        plus, minus = name_pair
        conv = peclet * vel[ax]
        # first-order upwind: flow in +ax direction biases the -ax neighbor
        raw[plus] = -1.0
        raw[minus] = -1.0 - conv
        diag += 2.0 + conv
    return StencilCoeffs(
        {n: jnp.full(shape, raw[n] / diag, dtype=dtype) for n in names}
    )


def heterogeneous_poisson(
    key: jax.Array,
    shape: tuple[int, ...],
    dtype=jnp.float32,
    *,
    contrast: float = 2.0,
    spec: StencilSpec | None = None,
) -> StencilCoeffs:
    """Raw (non-normalized) variable-coefficient diffusion operator.

    A log-normal cell diffusivity ``k = exp(contrast * N(0,1))`` couples
    each pair of neighbors with the face average ``(k_i + k_j)/2``; the
    stored main diagonal is the (variable) row sum of the couplings, with
    edge-replicated boundary faces so every row is weakly dominant.  This
    is the workload where Jacobi preconditioning (``M^-1 = D^-1``) does
    real work — the paper's operators arrive pre-normalized, this one does
    not.
    """
    spec = _default_spec(shape, spec)
    k = jnp.exp(contrast * jax.random.normal(key, shape, jnp.float32))

    def shift_edge(a, off):
        for axis, o in enumerate(off):
            if o == 0:
                continue
            pad = [(0, 0)] * a.ndim
            idx = [slice(None)] * a.ndim
            if o > 0:
                pad[axis] = (0, o)
                idx[axis] = slice(o, None)
            else:
                pad[axis] = (-o, 0)
                idx[axis] = slice(0, o)
            a = jnp.pad(a, pad, mode="edge")[tuple(idx)]
        return a

    couplings = {offset_name(o): (k + shift_edge(k, o)) / 2.0
                 for o in spec.offsets}
    diag = sum(couplings.values())
    return StencilCoeffs(
        {n: (-c).astype(dtype) for n, c in couplings.items()},
        diag=diag.astype(dtype))


# Central-difference second-derivative weights a_k (k = 1..r) of order 2r;
# a_0 is the center weight.  r=4 is the 8th-order arm of Jacquelin et al.'s
# 25-point seismic-RTM stencil.
_FD2_WEIGHTS = {
    1: (-2.0, (1.0,)),
    2: (-5.0 / 2.0, (4.0 / 3.0, -1.0 / 12.0)),
    3: (-49.0 / 18.0, (3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)),
    4: (-205.0 / 72.0, (8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)),
}


def high_order_star(
    shape: tuple[int, ...],
    radius: int = 4,
    dtype=jnp.float32,
    *,
    dominance: float = 1.25,
) -> StencilCoeffs:
    """Seismic-flavored high-order star operator (Jacquelin et al.'s shape).

    Uses the order-2r central-difference second-derivative weights on each
    axis (r=4 => the 25-point star of the seismic RTM stencil), embedded in
    an implicit-timestep operator ``I - theta * Laplacian_2r`` and Jacobi
    preconditioned.  ``theta`` is chosen so the off-diagonal row sum is
    ``1/dominance`` — strictly diagonally dominant, so the solve converges
    while keeping the true sign structure of the FD weights (alternating
    along each arm).
    """
    if radius not in _FD2_WEIGHTS:
        raise ValueError(f"radius must be in {sorted(_FD2_WEIGHTS)}, got {radius}")
    spec = StencilSpec("star", radius, len(shape))
    _, arm = _FD2_WEIGHTS[radius]
    total = len(shape) * 2 * sum(abs(a) for a in arm)
    scale = 1.0 / (dominance * total)
    diags = {}
    for off in spec.offsets:
        dist = max(abs(o) for o in off)
        diags[offset_name(off)] = jnp.full(shape, -arm[dist - 1] * scale, dtype=dtype)
    return StencilCoeffs(diags)


def rhs_for_solution(coeffs: StencilCoeffs, x_true: jax.Array) -> jax.Array:
    """b = A @ x_true in float64-ish (f32) precision, for manufactured tests."""
    return apply_ref(coeffs.astype(jnp.float32), x_true.astype(jnp.float32))


def flops_per_point(ndim: int = 3) -> int:
    """SpMV flops per meshpoint: 6 mul + 6 add (3D, unit diagonal) = 12.

    Matches Table I: Matvec x2 per iteration = 24 of the 44 ops/meshpoint.
    """
    n_off = 2 * ndim
    return 2 * n_off


def words_per_point(ndim: int = 3) -> int:
    """Memory words touched per meshpoint per SpMV: 6 coeffs + v + u."""
    return 2 * ndim + 2


def spec_flops_per_point(spec: StencilSpec) -> int:
    """SpMV flops per meshpoint for any family member: mul+add per offset.

    star7 => 12 (Table I's 24/2), star25 => 48, box27 => 52.
    """
    return 2 * spec.n_offsets


def spec_words_per_point(spec: StencilSpec) -> int:
    """Memory words touched per meshpoint per SpMV: coeffs + v + u."""
    return spec.n_offsets + 2


def halo_words_per_spmv(spec: StencilSpec, block: tuple[int, ...],
                        split_axes: tuple[int, ...] = (0, 1)) -> int:
    """Words exchanged per SpMV by one shard: depth-r slabs on split axes.

    Counts both directions; for box stencils the sequential corner-carrying
    exchange also ships the already-received halo of earlier axes.
    """
    r = spec.radius
    words = 0
    padded = list(block)
    for ax in split_axes:
        slab = r
        for i, n in enumerate(padded):
            if i != ax:
                slab *= n
        words += 2 * slab
        if spec.needs_corners:
            padded[ax] += 2 * r
    return words
