"""Pallas kernels for the solver's hot spots (the fused stencil SpMV and
the fused BiCGStab update+dot passes), written for the TPU's Mosaic
lowering."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` (every production call site) means: interpret if and only if
    the default backend is the CPU, which has no Mosaic target.  On a TPU
    the kernels always compile.  An explicit bool is honoured — the
    interpret-mode tests pass True, and the ahead-of-time compile tests
    pass False to lower for a described TPU from a CPU-only process.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
