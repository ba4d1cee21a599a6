"""Smooth per-point fields drawn from a seed, evaluated on the device.

A field is a sum of a few cosine modes over the mesh,

    f(p) = sum_m a_m cos(2 pi k_m . p / N + phi_m) / sum_m |a_m|,

which lies in [-1, 1].  The modes are drawn on the host from the seed (a
handful of numbers) and passed to the jitted function as arrays, so every
seed runs the same compiled program.  ``f`` at a shifted point is another
evaluation, not a shifted array, so a coefficient that couples a point with
its neighbour stays elementwise and needs no full-size temporary.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax


def draw_modes(rng: np.random.Generator, n_modes: int, max_wavenumber: int) -> dict:
    """Wave vectors, phases and amplitudes of one field."""
    k = rng.integers(0, max_wavenumber + 1, size=(n_modes, 3))
    k[~k.any(axis=1), 0] = 1          # no constant mode
    return {
        "k": k.astype(np.float32),
        "phase": rng.uniform(0.0, 2 * np.pi, size=n_modes).astype(np.float32),
        "amp": rng.uniform(0.5, 1.0, size=n_modes).astype(np.float32),
    }


def coords(shape) -> tuple:
    """The mesh coordinates (i, j, k) as float32 arrays of ``shape``."""
    return tuple(lax.broadcasted_iota(jnp.float32, shape, ax) for ax in range(3))


def smooth(modes: dict, xyz, shape, shift=(0, 0, 0)) -> jnp.ndarray:
    """The field at every point ``p + shift``, in [-1, 1]."""
    total = jnp.zeros(shape, jnp.float32)
    n = len(modes["phase"])
    for m in range(n):
        arg = modes["phase"][m]
        for ax in range(3):
            arg = arg + (2 * np.pi / shape[ax]) * modes["k"][m, ax] * (xyz[ax] + shift[ax])
        total = total + modes["amp"][m] * jnp.cos(arg)
    return total / jnp.sum(jnp.abs(modes["amp"]))
