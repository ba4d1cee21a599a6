"""HPCG's multigrid V-cycle (``ComputeMG_ref``): the ``"mg"`` preconditioner.

One application ``z = M^-1 r`` on each of ``levels`` levels, the finest
first, each coarsening halving every axis:

* the iterate starts at zero;
* one symmetric Gauss–Seidel sweep (forward, then backward);
* restriction by injection, ``rc = (r - A x)[2i, 2j, 2k]``;
* the next level's V-cycle on ``rc``;
* prolongation by injection, ``x[2i, 2j, 2k] += xc``;
* one more symmetric sweep.

The coarsest level does one symmetric sweep only.  Each coarse operator
is the fine one's coefficient fields injected at the even points
(``GenerateCoarseProblem``: for HPCG's 27-point matrix, the same matrix on
the coarse grid).

The Gauss–Seidel sweep runs in 8-colour order, colours being the parities
of a point's (x, y, z) index; HPCG allows reordering the sweep so.  The
colour of the coarse points, (0, 0, 0), comes last in the forward sweep
(:data:`FORWARD`) and so first in the backward one.  Had it come last in
the backward sweep, every coarse point would be updated after all its
neighbours, its residual would be zero, and the coarse levels would get
nothing to correct.  The backward sweep reverses the forward colour order,
so the V-cycle is symmetric positive definite and CG may use it.

Two implementations of a level, chosen per level from its operands:

* on a TPU, for a radius-1 spec with a unit diagonal on an f32 or bf16
  block, the ``symgs`` Pallas kernel (``kernels/stencil_nd/symgs.py``)
  sweeps the full-layout block, and the restriction's residual is the
  ``restrict`` kernel (``kernels/stencil_nd/restrict.py``): ``r - A x``
  at the coarse points only, from the next level's fields, which are this
  level's at those points.  Injection keeps what decides the choice (the
  spec, the unit diagonal, the dtype), so every level of a hierarchy is
  one kind or the other;
* elsewhere a jax.numpy sweep in colour-split form: the iterate held as
  its 8 colour sub-grids, each neighbour term a sub-grid shifted by at
  most one point, so a sweep costs one SpMV's terms and the restriction
  is the residual on sub-grid (0, 0, 0) alone.

Everything is local to one block: the V-cycle runs on one device only
(``solve_distributed`` refuses it on a larger mesh).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.halo import FabricAxes
from repro.core.precision import Policy
from repro.core.stencil import StencilCoeffs, name_offset
from repro.obs import metrics as obs_metrics

#: HPCG's constants: levels counting the finest, symmetric sweeps before and
#: after the coarse correction
LEVELS = 4
PRE_SWEEPS = 1
POST_SWEEPS = 1

#: (x parity taken first, the (y, z) parities in order) of each sweep:
#: the forward sweep takes (1, 1, 1) first and (0, 0, 0) last
FORWARD = (1, ((1, 1), (1, 0), (0, 1), (0, 0)))
BACKWARD = (0, ((0, 0), (0, 1), (1, 0), (1, 1)))


def colours(sweep) -> list[tuple[int, int, int]]:
    """The 8 colours in the order ``sweep`` updates them."""
    first, inplane = sweep
    return [(a, b, c) for a in (first, 1 - first) for b, c in inplane]


COARSE = (0, 0, 0)


# ---------------------------------------------------------------------------
# One level
# ---------------------------------------------------------------------------

def sweep_kernel_applies(coeffs: StencilCoeffs, dtype, platform: str) -> bool:
    """Whether a level sweeps with the ``symgs`` kernel: where its SpMV takes
    the stream kernel (``operator.stream_applies``), for a radius-1 spec
    with a unit diagonal."""
    from repro.core.operator import stream_applies

    return (coeffs.spec.radius == 1 and coeffs.diag is None
            and stream_applies(coeffs.spec, coeffs.ndim, dtype, platform))


def inject(coeffs: StencilCoeffs) -> StencilCoeffs:
    """The coarse operator: every field at the even points."""
    even = lambda a: _sub(a, COARSE)
    return StencilCoeffs({n: even(c) for n, c in coeffs.diags.items()},
                         diag=None if coeffs.diag is None else even(coeffs.diag))


def _sub(a, c):
    """The points of colour ``c``: ``a[c0::2, c1::2, c2::2]``."""
    return jax.lax.slice(a, c, a.shape, (2, 2, 2))


@dataclasses.dataclass(frozen=True)
class KernelLevel:
    """A level swept by the ``symgs`` kernel on the full-layout block."""

    op: object                      # LinearOperator of this level

    def start(self, r):
        return r

    def sgs(self, r, x):
        from repro.kernels import resolve_interpret
        from repro.kernels.stencil_nd.symgs import symgs_sweep

        items = self.op.coeffs.ordered_items()
        fields = [c for _, c in items]
        offsets = tuple(name_offset(n, 3) for n, _ in items)
        for first, inplane in (FORWARD, BACKWARD):
            obs_metrics.counter("precond.mg.sweeps").inc()
            x = symgs_sweep(r, x, fields, offsets, first=first, inplane=inplane,
                            out_dtype=self.op.policy.storage,
                            interpret=resolve_interpret(None))
        return x

    def restrict(self, r, x, coarse):
        """``(r - A x)`` at the coarse points, by the ``restrict`` kernel
        from the next level's (``coarse``'s) fields."""
        from repro.kernels import resolve_interpret
        from repro.kernels.stencil_nd.restrict import coarse_residual

        obs_metrics.counter("precond.mg.restrict.coarse").inc()
        pol = self.op.policy
        items = coarse.op.coeffs.ordered_items()
        return coarse_residual(
            r, x, [c for _, c in items],
            tuple(name_offset(n, 3) for n, _ in items),
            apply_dtype=pol.storage, compute=pol.compute,
            out_dtype=pol.storage, interpret=resolve_interpret(None))

    def prolong(self, x, xc):
        c = self.op.policy.compute
        pad = [(0, n - 2 * m + 1, 1) for n, m in zip(x.shape, xc.shape)]
        up = jax.lax.pad(xc.astype(c), jnp.zeros((), c), pad)
        return (x.astype(c) + up).astype(self.op.policy.storage)

    def finish(self, x):
        return x


def _index(c) -> int:
    return 4 * c[0] + 2 * c[1] + c[2]


@dataclasses.dataclass(frozen=True)
class SplitLevel:
    """A level swept in jax.numpy on the 8 colour sub-grids.

    Colour ``c`` holds the points ``(2i + c0, 2j + c1, 2k + c2)``; every
    sub-grid is stacked at ``ceil(n / 2)`` points an axis (a colour with
    fewer on an odd axis gets a zero point past the face, its fields and
    right-hand side zero there, so it stays zero).  The iterate is stacked
    with one zero point around each sub-grid, so the neighbour at offset
    ``o`` of every point of colour ``c`` is a window of colour ``c ^ |o|``
    shifted by at most one point.  A sweep is a loop over its colours."""

    shape: tuple
    fields: jax.Array               # [colour, offset, *sub-grid]
    offsets: tuple
    diag: jax.Array | None          # [colour, *sub-grid], 1 past the faces
    policy: Policy

    @classmethod
    def build(cls, coeffs: StencilCoeffs, policy: Policy) -> "SplitLevel":
        items = [(name_offset(n, 3), c) for n, c in coeffs.ordered_items()]
        split = lambda a, fill=0: _split(a.astype(policy.compute), fill)
        fields = jnp.stack([split(f) for _, f in items], axis=1)
        diag = None if coeffs.diag is None else split(coeffs.diag, 1)
        return cls(coeffs.shape, fields, tuple(o for o, _ in items), diag, policy)

    def _update(self, k, xs, rs):
        """Colour ``k`` (traced) of the stacked, padded iterate ``xs``:
        ``r - sum_o A[p, p + o] x[p + o]`` over its points."""
        size = rs.shape[1:]
        bits = [(k >> (2 - axis)) & 1 for axis in range(3)]
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, k, keepdims=False)
        val = pick(rs)
        fields = pick(self.fields)
        for j, off in enumerate(self.offsets):
            flip = [o & 1 for o in off]
            kn = k ^ (4 * flip[0] + 2 * flip[1] + flip[2])
            # the window of colour kn: points i - 1 + b (o = -1), i + b (o = 1)
            start = [{-1: b, 0: 1, 1: 1 + b}[o] for b, o in zip(bits, off)]
            v = jax.lax.dynamic_slice(xs, [kn] + start, (1,) + size)[0]
            val = val - fields[j] * v
        if self.diag is not None:
            val = val / pick(self.diag)
        return val

    def start(self, r):
        return _split(r.astype(self.policy.compute))

    def sgs(self, rs, xs):
        """One symmetric sweep of the stacked, padded iterate (None: zero)."""
        if xs is None:
            xs = jnp.pad(jnp.zeros_like(rs), [(0, 0)] + [(1, 1)] * 3)
        for sweep in (FORWARD, BACKWARD):
            obs_metrics.counter("precond.mg.sweeps").inc()
            order = jnp.asarray([_index(c) for c in colours(sweep)], jnp.int32)

            def colour(n, xs, order=order):
                k = jax.lax.dynamic_index_in_dim(order, n, keepdims=False)
                v = jnp.pad(self._update(k, xs, rs), [(1, 1)] * 3)
                return jax.lax.dynamic_update_slice(xs, v[None], (k, 0, 0, 0))

            xs = jax.lax.fori_loop(0, 8, colour, xs)
            xs = xs.astype(self.policy.storage).astype(xs.dtype)
        return xs

    def restrict(self, rs, xs, coarse):
        """``(r - A x)`` on colour (0, 0, 0); ``coarse`` is not read."""
        k = _index(COARSE)
        x0 = jax.lax.slice(xs[k], (1, 1, 1), tuple(n + 1 for n in rs.shape[1:]))
        if self.diag is not None:
            x0 = x0 * self.diag[k]
        res = self._update(k, xs, rs) - x0        # (r - (A - D) x - D x) at colour 0
        return res.astype(self.policy.storage)

    def prolong(self, xs, xc):
        k = _index(COARSE)
        up = jnp.pad(xc.astype(xs.dtype), [(1, 1)] * 3)
        return xs.at[k].add(up)

    def finish(self, xs):
        """The full-layout iterate: the sub-grids interleaved."""
        m = xs.shape[1:]
        v = xs[:, 1:-1, 1:-1, 1:-1].reshape((2, 2, 2) + tuple(n - 2 for n in m))
        v = v.transpose(3, 0, 4, 1, 5, 2).reshape(tuple(2 * (n - 2) for n in m))
        return v[:self.shape[0], :self.shape[1], :self.shape[2]].astype(
            self.policy.storage)


def _split(a, fill=0):
    """``a``'s 8 colour sub-grids stacked in colour order, each at
    ``ceil(n / 2)`` points an axis, ``fill`` past the faces."""
    half = [(n + 1) // 2 for n in a.shape]
    subs = []
    for k in range(8):
        c = ((k >> 2) & 1, (k >> 1) & 1, k & 1)
        sub = _sub(a, c)
        subs.append(jnp.pad(sub, [(0, h - n) for h, n in zip(half, sub.shape)],
                            constant_values=fill))
    return jnp.stack(subs)


# ---------------------------------------------------------------------------
# The hierarchy and the cycle
# ---------------------------------------------------------------------------

def build_levels(op, levels: int = LEVELS) -> tuple:
    """The V-cycle's levels over the shard-local operator ``op`` (built
    inside the solve's ``shard_map``): the finest is ``op`` itself, each
    coarser one its fields injected, with an operator of the same backend."""
    from repro.core.operator import make_operator

    shape = op.coeffs.shape
    spec = op.coeffs.spec
    if spec.ndim != 3 or spec.radius != 1:
        raise ValueError(f"mg takes a 3-D radius-1 stencil (star7, box27); "
                         f"got {spec.name}")
    step = 2 ** (levels - 1)
    if any(n % step for n in shape):
        raise ValueError(f"mg with {levels} levels needs a local block divisible "
                         f"by {step} on every axis; got {shape}")
    obs_metrics.counter("precond.mg.levels").inc(levels)
    out = []
    for depth in range(levels):
        if depth:
            op = make_operator(op.name, inject(op.coeffs), FabricAxes(),
                               policy=op.policy, schedule=op.schedule)
        if sweep_kernel_applies(op.coeffs, op.policy.storage, jax.default_backend()):
            out.append(KernelLevel(op))
        else:
            out.append(SplitLevel.build(op.coeffs, op.policy))
    return tuple(out)


def vcycle(levels, r, depth: int = 0):
    """``z = M^-1 r``: HPCG's V-cycle from level ``depth`` down."""
    lev = levels[depth]
    rs = lev.start(r)
    x = None
    for _ in range(PRE_SWEEPS):
        x = lev.sgs(rs, x)
    if depth + 1 < len(levels):
        rc = lev.restrict(rs, x, levels[depth + 1])
        x = lev.prolong(x, vcycle(levels, rc, depth + 1))
        for _ in range(POST_SWEEPS):
            x = lev.sgs(rs, x)
    return lev.finish(x)


@dataclasses.dataclass(frozen=True)
class MGPrecond:
    """HPCG's V-cycle as a preconditioner (``precond="mg"``)."""

    levels: tuple
    name: str = "mg"

    def apply(self, v):
        if v.ndim != 3:
            raise ValueError(f"mg takes one right-hand side at a time; got a "
                             f"{v.ndim}-D operand")
        return vcycle(self.levels, v)
