"""star25 implicit-timestep seismic operator with a per-point velocity model.

A copy of ``core/stencil.high_order_star`` at radius 4: the 8th-order
central-difference second-derivative weights on each axis, in the operator
``I - theta * Laplacian_8``, Jacobi-normalised to a unit diagonal, with
theta set so that the off-diagonal magnitudes of a row sum to
``1 / dominance``.  Here theta is scaled at every coupling by the squared
velocity of the medium, averaged over the two points it couples,
``(v(p)^2 + v(p + o)^2) / (2 v_max^2)``.  The velocity model
v(p) = v_min (v_max / v_min)^((g + 1) / 2) rises with depth (the z axis) and
carries a smooth lateral perturbation drawn from ``case_seed``,
g = (2 z / (Z - 1) - 1) / 2 + f(p) / 2.  Rows where v < v_max are more
dominant than the copied generator's; none is less.
"""

from __future__ import annotations

from bench import fields

# a_1..a_4 of the order-8 central second difference (a_0 = -205/72)
ARM = (8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)


def offsets(cfg: dict) -> tuple:
    """Axis-major, then distance, + before -: the copied generator's order."""
    r = cfg["stencil"]["radius"]
    return tuple(tuple(sign * d if i == ax else 0 for i in range(3))
                 for ax in range(3) for d in range(1, r + 1) for sign in (1, -1))


def draw(cfg: dict, rng) -> dict:
    return {"velocity": fields.draw_modes(rng, cfg["modes"], cfg["max_wavenumber"])}


def coefficients(cfg: dict, params: dict, shape) -> dict:
    """The normalised off-diagonal fields, float32, keyed by offset."""
    xyz = fields.coords(shape)
    v_min, v_max = cfg["velocity_range"]
    r = cfg["stencil"]["radius"]
    total = 3 * 2 * sum(abs(a) for a in ARM[:r])
    scale = 1.0 / (cfg["dominance"] * total)

    def v2(shift):
        depth = 2.0 * (xyz[2] + shift[2]) / (shape[2] - 1) - 1.0
        g = 0.5 * depth + 0.5 * fields.smooth(params["velocity"], xyz, shape, shift)
        return ((v_min / v_max) ** ((1.0 - g) / 2.0)) ** 2    # (v / v_max)^2

    v2_here = v2((0, 0, 0))
    out = {}
    for off in offsets(cfg):
        dist = max(abs(o) for o in off)
        out[off] = (-ARM[dist - 1] * scale * 0.5) * (v2_here + v2(off))
    return out
