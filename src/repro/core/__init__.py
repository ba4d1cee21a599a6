"""The paper's primary contribution: distributed stencil BiCGStab.

Layers: stencil operators (stencil.py), fabric halo exchange (halo.py),
the pluggable operator backends (operator.py), the solver registry
(solvers/), right preconditioning (precond.py), precision policies
(precision.py), the drivers gluing them together (bicgstab.py), the
device peak table (perfmodel.py) and the SIMPLE CFD re-export
(simple_cfd.py).
"""

from repro.core import (  # noqa: F401
    bicgstab, halo, operator, precision, precond, solvers, stencil,
)
