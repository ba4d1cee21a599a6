"""HPCG's 27-point operator (``GenerateProblem``): diagonal 26, every
off-diagonal -1, zero-Dirichlet faces, divided by its diagonal.

Each of the 26 off-diagonal fields is written as a stored per-point array
of -1/26, unit diagonal: HPCG forbids exploiting that the values are
constant, so the program reads them as it reads any coefficient field.
Nothing is drawn from the seed: HPCG's matrix is fixed.
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp


def offsets(cfg: dict) -> tuple:
    """The 26 neighbours of the 3x3x3 box, x slowest."""
    r = cfg["stencil"]["radius"]
    span = range(-r, r + 1)
    return tuple(o for o in itertools.product(span, span, span) if any(o))


def draw(cfg: dict, rng) -> dict:
    return {}


def coefficients(cfg: dict, params: dict, shape) -> dict:
    """The unit-diagonal fields, float32, keyed by offset."""
    c = -1.0 / len(offsets(cfg))
    return {off: jnp.full(shape, c, jnp.float32) for off in offsets(cfg)}
