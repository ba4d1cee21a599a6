"""Least HBM traffic of one preconditioned CG iteration with HPCG's
multigrid V-cycle (``precond="mg"``), in storage words per meshpoint of the
finest level.

The iteration (``CG_ref``) with a unit-diagonal stencil operator A that
stores ``n_offsets`` coefficient fields:

    Ap = A p;  <p, Ap>                                  (global reduction 1)
    x += a p;  r -= a Ap;  z = M^-1 r;  <r, z>, <r, r>  (global reduction 2)
    p = z + b p

A global reduction is a barrier, and the V-cycle ``M^-1`` has one inside
it for any implementation whose on-chip memory cannot span its reach:

* ``A p`` lies before reduction 1, the V-cycle after it (it needs ``r``,
  which needs ``a``), and ``p``'s update after reduction 2: the fields are
  read once for ``A p``.
* The V-cycle's pre-smoother and post-smoother at the same fine plane lie
  on either side of the whole coarse cycle: the coarse correction at a
  fine plane depends on the fine residual at least 2 planes further on
  for every level, 16 planes for 4 levels, and more through the coarse
  sweeps.  Sixteen planes of the fine fields (26 fields of 384x384 f32 are
  15.3 MB a plane, 245 MB for 16) exceed the chip's on-chip memory (v5e:
  128 MiB of VMEM), so the fields are read once for the pre-smoother and
  once again for the post-smoother.
* A symmetric sweep's forward and backward halves, and the restriction's
  residual, need only a few planes between them and may share one read.

So the fields are read at least 3 times: ``3 * n_offsets`` words.  The
vectors (counted as in ``counts/bicgstab.py``; ``x += a p`` can wait for
the next read of ``p``):

* after reduction 2: ``z`` and ``p`` read, ``p`` written, ``x`` read and
  written, ``A p`` written (recomputing it would read the fields again):
  6 words;
* after reduction 1: ``r`` and ``A p`` read, ``r`` written, the
  pre-smoothed iterate written for the post-smoother: 4 words;
* the post-smoother: ``r`` and that iterate read, ``z`` written: 3 words.

The coarse levels count zero: their fields are an eighth of the fine
level's and less, and at some sizes fit on chip.  So the count is a floor
that no implementation of the iteration can undercut, and a share of the
HBM roofline built on it cannot exceed 100 % unless the time is
undercounted.  The argument needs 16 planes of one chip's fine fields to
exceed its on-chip memory; a cell with smaller planes states its own
count.  Without a preconditioner CG needs less; no cell runs it.
"""

from __future__ import annotations


def least_words_per_point(n_offsets: int) -> int:
    """Storage words per finest-level meshpoint that one MG-PCG iteration
    has to move."""
    return 3 * n_offsets + 6 + 4 + 3
