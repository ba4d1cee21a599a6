"""Faults planted in the program under test.

They give the readings that a broken program sets against the correctness
limits (``bench/control.py --fault <name>``, on the chip) and let the tests
see ``correct`` come out false.  ``plant(set_attr)`` patches the program
with ``set_attr(owner, name, value)``: ``setattr`` in a process that only
reads, ``monkeypatch.setattr`` in a test.  A fault takes effect in every
program traced after it is planted.
"""

from __future__ import annotations

import jax.numpy as jnp

#: the coupling distance beyond which ``truncate`` drops the stencil's terms
KEEP = 2


def exchange(set_attr) -> None:
    """The halo exchange between chips left out: every face arrives as zeros."""
    from repro.core import halo

    def no_exchange(face_lo, face_hi, axis_name, n):
        return jnp.zeros_like(face_hi), jnp.zeros_like(face_lo)

    set_attr(halo, "_exchange", no_exchange)


def truncate(set_attr) -> None:
    """Part of the operator left out: every SpMV drops the terms that couple
    points farther apart than ``KEEP`` (the rings at 3 and 4 of star25)."""
    from repro.core.stencil import StencilCoeffs, name_offset

    real = StencilCoeffs.ordered_items

    def near_items(self):
        return [(n, c) for n, c in real(self)
                if max(abs(o) for o in name_offset(n, self.ndim)) <= KEEP]

    set_attr(StencilCoeffs, "ordered_items", near_items)


def early_exit(set_attr) -> None:
    """The solve stops at five times the configuration's tolerance and
    reports convergence."""
    from repro.core.solvers import bicgstab

    real = bicgstab.convergence_test
    set_attr(bicgstab, "convergence_test", lambda tol, bnorm2: real(5.0 * tol, bnorm2))


FAULTS = {f.__name__: f for f in (exchange, truncate, early_exit)}
