"""star7 upwinded convection-diffusion with per-point fields.

A copy of ``core/stencil.convection_diffusion`` (diffusion -1 per face, the
convection term upwinded onto the -axis neighbour with cell Peclet number
``peclet * velocity[axis]``, Jacobi-normalised to a unit diagonal), with the
constant diffusivity and velocity replaced by smooth fields drawn from the
configuration's ``case_seed``:

* diffusivity D(p) in ``diffusivity_range``, log-smooth; each face carries
  the mean of its two cells' D;
* speed w(p) in ``speed_range`` scales the velocity, and the convective
  flux is ``peclet * velocity[axis] * w(p) * D_face``, so the cell Peclet
  number stays within ``peclet * velocity[axis] <= 5`` everywhere.

The off-diagonal magnitudes of a row sum to its diagonal, as in the copied
generator (weakly dominant inside, strictly at the zero-Dirichlet edges).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from bench import fields


def offsets(cfg: dict) -> tuple:
    """(+x, -x, +y, -y, +z, -z), the order of the copied generator."""
    return ((1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1))


def draw(cfg: dict, rng) -> dict:
    return {name: fields.draw_modes(rng, cfg["modes"], cfg["max_wavenumber"])
            for name in ("diffusivity", "speed")}


def coefficients(cfg: dict, params: dict, shape) -> dict:
    """The normalised off-diagonal fields, float32, keyed by offset."""
    xyz = fields.coords(shape)
    lo, hi = cfg["diffusivity_range"]
    mid, half = 0.5 * math.log(lo * hi), 0.5 * math.log(hi / lo)

    def diffusivity(shift):
        return jnp.exp(mid + half * fields.smooth(params["diffusivity"], xyz, shape, shift))

    slo, shi = cfg["speed_range"]
    speed = slo + 0.5 * (shi - slo) * (1.0 + fields.smooth(params["speed"], xyz, shape))
    d0 = diffusivity((0, 0, 0))
    raw = {}
    diag = jnp.zeros(shape, jnp.float32)
    for ax in range(3):
        e = tuple(1 if i == ax else 0 for i in range(3))
        me = tuple(-i for i in e)
        d_plus = 0.5 * (d0 + diffusivity(e))
        d_minus = 0.5 * (d0 + diffusivity(me))
        conv = cfg["peclet"] * cfg["velocity"][ax] * speed * d_minus
        raw[e] = -d_plus
        raw[me] = -(d_minus + conv)
        diag = diag + d_plus + d_minus + conv
    return {off: raw[off] / diag for off in offsets(cfg)}
