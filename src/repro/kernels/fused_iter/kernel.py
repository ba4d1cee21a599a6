"""Fused BiCGStab vector-update + inner-product Pallas kernels.

The paper's iteration sweeps the per-core state ~13 times (2 SpMV reads x 8
vectors, 6 AXPYs, 4 dots).  On TPU the memory roofline term is exactly
proportional to those sweeps, so we fuse each "update then dot" pair into a
single pass (the CS-1 analogue: its AXPYs and dot products were separate
tensor instructions but all operands already lived in SRAM; on TPU the state
lives in HBM and fusion is where the paper's SRAM-residency advantage must be
re-earned — DESIGN.md §2).

All kernels run on a ``(B, rows, 128)`` tiling — one (rows, 128)
flattening of the mesh block per right-hand side (B = 1 for a single-RHS
solve) — with grid ``(B, rows // bm)``.  The per-RHS scalar coefficients
ride in SMEM (a ``(B, k)`` f32 table read at the batch coordinate), and
each RHS accumulates its own f32 dot partials into its own ``(1, 1)``
output block across the sequential row sweep (TPU grid iterations execute
in order, so += into the block is sound; same semantics in interpret
mode).  A single-RHS solve is the B = 1 case of the same kernels.

Precision: products in the storage dtype (bf16), accumulation in f32 — the
paper's FMAC discipline (Table I mixed column).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_spec(bm):
    return pl.BlockSpec((None, bm, 128), lambda b, i: (b, i, 0))


def _scalars_spec():
    # the whole (B, k) coefficient table, read as scalars at row b
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _partial_spec():
    return pl.BlockSpec((None, 1, 1), lambda b, i: (b, 0, 0))


def _partial_shape(B):
    return jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)


def _coef(tab_ref, col, like_ref):
    """Row b, column ``col`` of the SMEM scalar table, in ``like_ref``'s
    storage dtype (the product then runs in storage precision)."""
    c = tab_ref[pl.program_id(0), col]
    return jnp.full((1, 1), c, jnp.float32).astype(like_ref.dtype)


def _acc_init(*refs):
    @pl.when(pl.program_id(1) == 0)
    def _():
        for r in refs:
            r[...] = jnp.zeros_like(r)


def _call(kernel, name, B, M, bm, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(kernel, grid=(B, M // bm), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret, name=name)


# --- q = r - alpha*s ; partials <q,y>, <y,y> ------------------------------

def _update_q_kernel(alpha_ref, r_ref, s_ref, y_ref, q_ref, qy_ref, yy_ref):
    _acc_init(qy_ref, yy_ref)
    q = r_ref[...] - _coef(alpha_ref, 0, r_ref) * s_ref[...]
    q_ref[...] = q
    yf = y_ref[...].astype(jnp.float32)
    qy_ref[...] += jnp.sum(q.astype(jnp.float32) * yf).reshape(1, 1)
    yy_ref[...] += jnp.sum(yf * yf).reshape(1, 1)


def update_q_dots_pallas(alpha, r, s, y, *, bm: int, interpret: bool = True):
    """``alpha``: (B,) f32; vectors: (B, M, 128)."""
    B, M, _ = r.shape
    row = _row_spec(bm)
    return _call(
        _update_q_kernel, "update_q_dots", B, M, bm,
        [_scalars_spec(), row, row, row],
        [row, _partial_spec(), _partial_spec()],
        [jax.ShapeDtypeStruct(r.shape, r.dtype), _partial_shape(B),
         _partial_shape(B)],
        interpret,
    )(alpha.reshape(B, 1).astype(jnp.float32), r, s, y)


# --- x += alpha*p + omega*q ; r = q - omega*y ; <r0,r>, <r,r> --------------

def _update_xr_kernel(ab_ref, x_ref, p_ref, q_ref, y_ref, r0_ref,
                      xo_ref, ro_ref, r0r_ref, rr_ref):
    _acc_init(r0r_ref, rr_ref)
    alpha = _coef(ab_ref, 0, x_ref)
    omega = _coef(ab_ref, 1, x_ref)
    q = q_ref[...]
    xo_ref[...] = x_ref[...] + alpha * p_ref[...] + omega * q
    r = q - omega * y_ref[...]
    ro_ref[...] = r
    rf = r.astype(jnp.float32)
    r0r_ref[...] += jnp.sum(r0_ref[...].astype(jnp.float32) * rf).reshape(1, 1)
    rr_ref[...] += jnp.sum(rf * rf).reshape(1, 1)


def update_xr_dots_pallas(alpha, omega, x, p, q, y, r0, *, bm: int,
                          interpret: bool = True):
    """``alpha``/``omega``: (B,) f32; vectors: (B, M, 128)."""
    B, M, _ = x.shape
    ab = jnp.stack([alpha.reshape(B), omega.reshape(B)],
                   axis=-1).astype(jnp.float32)              # (B, 2)
    row = _row_spec(bm)
    return _call(
        _update_xr_kernel, "update_xr_dots", B, M, bm,
        [_scalars_spec()] + [row] * 5,
        [row, row, _partial_spec(), _partial_spec()],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(x.shape, x.dtype),
         _partial_shape(B), _partial_shape(B)],
        interpret,
    )(ab, x, p, q, y, r0)


# --- p = r + beta*(p - omega*s) -------------------------------------------

def _update_p_kernel(bo_ref, r_ref, p_ref, s_ref, po_ref):
    beta = _coef(bo_ref, 0, p_ref)
    omega = _coef(bo_ref, 1, p_ref)
    po_ref[...] = r_ref[...] + beta * (p_ref[...] - omega * s_ref[...])


def update_p_pallas(beta, omega, r, p, s, *, bm: int, interpret: bool = True):
    """``beta``/``omega``: (B,) f32; vectors: (B, M, 128)."""
    B, M, _ = r.shape
    bo = jnp.stack([beta.reshape(B), omega.reshape(B)],
                   axis=-1).astype(jnp.float32)              # (B, 2)
    row = _row_spec(bm)
    return _call(
        _update_p_kernel, "update_p", B, M, bm,
        [_scalars_spec()] + [row] * 3, row,
        jax.ShapeDtypeStruct(r.shape, r.dtype),
        interpret,
    )(bo, r, p, s)


# --- plain mixed-precision dot --------------------------------------------

def _dot_kernel(a_ref, b_ref, o_ref):
    _acc_init(o_ref)
    prod = (a_ref[...] * b_ref[...]).astype(jnp.float32)   # bf16 multiply, f32 add
    o_ref[...] += jnp.sum(prod).reshape(1, 1)


def dot_mixed_pallas(a, b, *, bm: int, interpret: bool = True):
    """Per-RHS partials ``(B, 1, 1)`` of <a, b> for (B, M, 128) operands."""
    B, M, _ = a.shape
    row = _row_spec(bm)
    return _call(_dot_kernel, "dot_partial", B, M, bm, [row, row], _partial_spec(),
                 _partial_shape(B), interpret)(a, b)
