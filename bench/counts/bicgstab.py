"""Least HBM traffic of one BiCGStab iteration, in storage words per meshpoint.

The iteration (paper Alg. 1) with a unit-diagonal stencil operator A that
stores ``n_offsets`` coefficient fields:

    s = A p;      <r0, s>                 (global reduction 1)
    q = r - a s;  y = A q;  <q, y>, <y, y>  (global reduction 2)
    x += a p + w q;  r = q - w y;  <r0, r>, <r, r>   (global reduction 3)
    p = r + b (p - w s)

A global reduction is a barrier: nothing after it can start before every
point's contribution to it is known.  The two SpMVs lie on opposite sides
of reduction 1 (``y = A q`` needs ``a``, which needs all of ``s``), so each
coefficient field is streamed once per SpMV, unless it stays in on-chip
memory between them.  At the sizes this count is used for, one field alone
exceeds the chip's on-chip memory (608^3 bf16 is 450 MB per field; v5e has
128 MiB of VMEM), so no implementation keeps them there:

* every coefficient field is read twice:          2 * n_offsets words;
* x, r and p carry from one iteration to the next, so each is read and
  written at least once:                           6 words;
* r0 is read for <r0, s> (before reduction 1) and again for <r0, r>
  (after reduction 2), on opposite sides of a barrier: 2 words.

s, q and y are not counted at all, although the schedules in this
repository write and re-read them: an implementation could recompute them.
So the count is a floor that any implementation of the iteration has to
move, whatever its backend, fusion or tiling, and a share of the HBM
roofline built on it cannot exceed 100 % unless the time is undercounted.
It is below the perf model's 42 words (``core/perfmodel.SOLVER_COMMS``) and
its 28-word fused schedule.

The argument needs one chip's coefficient fields to exceed its on-chip
memory.  A cell small enough to keep them on chip states its own count.
"""

from __future__ import annotations


def least_words_per_point(n_offsets: int) -> int:
    """Storage words per meshpoint that one iteration has to move."""
    return 2 * n_offsets + 6 + 2
