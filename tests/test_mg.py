"""HPCG's multigrid V-cycle (``precond="mg"``, core/multigrid.py), its
``symgs`` sweep kernel and the textbook PCG (core/solvers/cg.py), against
the plain reference ``bench/reference_mg.py``.

Tolerances: the program and the reference sum each row's 26 terms in
different orders in float32 (u = 2^-24), and Gauss-Seidel contracts
errors, so a sweep or a whole V-cycle agrees to a few u of the largest
value (2.4e-7 measured at these sizes).  ``VCYCLE_RTOL`` = 1e-5 leaves 40
times that, and is 2000 times below what the program in bf16 storage
gives (2.1e-2), so a lower precision cannot pass.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bicgstab, halo, multigrid, operator, precision, stencil
from repro.core.multigrid import (
    BACKWARD, COARSE, FORWARD, MGPrecond, build_levels, colours, vcycle,
)
from repro.core.precond import IdentityPrecond, PrecondConfig, build_precond
from repro.core.solvers import cg as solver_cg
from repro.kernels.stencil_nd import stream
from repro.kernels.stencil_nd.restrict import coarse_residual
from repro.kernels.stencil_nd.symgs import plane_order, symgs_sweep
from repro.obs import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import reference_mg  # noqa: E402

VCYCLE_RTOL = 1e-5
SHAPES = [(16, 16, 32), (32, 32, 16)]


def _random_box27(shape, seed=11):
    """Seeded random 27-point fields, each row's off-diagonals summing to
    between -1/2 and -1 against the unit diagonal, and a random vector."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 28)
    fields = {o: -jax.random.uniform(k, shape, jnp.float32, 0.5, 1.0) / 26
              for o, k in zip(stencil.BOX27.offsets, keys)}
    coeffs = stencil.StencilCoeffs({stencil.offset_name(o): f
                                    for o, f in fields.items()})
    return fields, coeffs, jax.random.normal(keys[-1], shape, jnp.float32)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _program_vcycle(coeffs, r, policy=precision.F32):
    def apply(cf, v):
        op = operator.make_operator("spmd", cf.astype(policy.storage),
                                    policy=policy, schedule="overlap")
        return vcycle(build_levels(op), v.astype(policy.storage))
    return jax.jit(apply)(coeffs, r)


@pytest.fixture
def as_tpu(monkeypatch):
    """The platform read as a TPU, kernels interpreted: the chip's path."""
    import repro.kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(repro.kernels, "resolve_interpret", lambda i=None: True)
    monkeypatch.setattr(stream, "resolve_interpret", lambda i=None: True)


def test_colour_orders():
    fwd = colours(FORWARD)
    assert sorted(fwd) == [(a, b, c) for a in (0, 1) for b in (0, 1) for c in (0, 1)]
    assert colours(BACKWARD) == fwd[::-1]
    assert fwd[-1] == (0, 0, 0) and tuple(fwd) == reference_mg.FORWARD


@pytest.mark.parametrize("X", [8, 7])
@pytest.mark.parametrize("first", [0, 1])
def test_plane_order_visits_every_plane_once(X, first):
    order = [int(plane_order(s, X, first)) for s in range(X)]
    assert sorted(order) == list(range(X))
    done = set()
    for u in order:
        # a plane of the second parity sees both neighbours updated
        if u % 2 != first:
            assert {u - 1, u + 1} & set(range(X)) <= done
        else:
            assert not {u - 1, u + 1} & done
        done.add(u)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_vcycle_matches_reference(shape):
    fields, coeffs, r = _random_box27(shape)
    got = _program_vcycle(coeffs, r)
    want = reference_mg.apply_vcycle(fields, r)
    assert _rel(got, want) <= VCYCLE_RTOL
    counters = metrics.snapshot()["counters"]
    assert counters["precond.mg.levels"] == 4
    assert counters["precond.mg.sweeps"] == 3 * 4 + 2


def test_vcycle_in_bf16_fails_the_tolerance():
    fields, coeffs, r = _random_box27(SHAPES[0])
    got = _program_vcycle(coeffs, r, precision.MIXED)
    assert _rel(got, reference_mg.apply_vcycle(fields, r)) > 100 * VCYCLE_RTOL


def test_vcycle_on_the_kernel_path(as_tpu):
    """The chip's path: every level swept by ``symgs``, the restriction's
    residual by the ``restrict`` kernel at the coarse points only; no SpMV
    of the full level."""
    shape = SHAPES[0]
    fields, coeffs, r = _random_box27(shape)
    got = _program_vcycle(coeffs, r)
    assert _rel(got, reference_mg.apply_vcycle(fields, r)) <= VCYCLE_RTOL
    counters = metrics.snapshot()["counters"]
    assert counters["kernels.symgs.traced_calls"] == 3 * 4 + 2
    assert counters["precond.mg.restrict.coarse"] == 3
    assert counters.get("operator.spmv_interior.stream", 0) == 0


def _bf16_values(key, shape, scale=1.0):
    x = scale * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("shape", [(8, 72, 40), (16, 16, 16), (8, 16, 264),
                                   (8, 16, 384)], ids=str)
@pytest.mark.parametrize("specname", ["box27", "star7"])
def test_coarse_residual_equals_the_full_residual(specname, shape):
    """``restrict`` equals the residual at every point cut to the coarse
    ones, bit for bit: 36 coarse rows (a row tail), 20 and 8 lanes; 132
    and 192 lanes, a plane of three 128-lane groups (the last partial)
    split as two pairs, the second half zero lanes.  The
    operands hold bf16 values, so every product is exact in f32 and the
    comparison does not depend on whether a compiler fuses a multiply and
    an add.  Random x puts a nonzero term past every face of the block,
    which a wrong face would add."""
    spec = stencil.get_spec(specname)
    keys = jax.random.split(jax.random.PRNGKey(9), spec.n_offsets + 2)
    coeffs = stencil.StencilCoeffs({n: _bf16_values(k, shape, 0.2)
                                    for n, k in zip(spec.names, keys)})
    x, r = _bf16_values(keys[-2], shape), _bf16_values(keys[-1], shape)
    want = multigrid._sub(r - halo.interior_apply(coeffs, x, policy=precision.F32),
                          COARSE)
    items = multigrid.inject(coeffs).ordered_items()
    got = coarse_residual(r, x, [c for _, c in items],
                          tuple(stencil.name_offset(n, 3) for n, _ in items),
                          interpret=True)
    assert got.shape == tuple(n // 2 for n in shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert metrics.snapshot()["counters"]["kernels.restrict.traced_calls"] == 1


def test_coarse_residual_refuses_odd_blocks():
    v = jnp.zeros((4, 8, 7), jnp.float32)
    with pytest.raises(ValueError, match="even extents"):
        coarse_residual(v, v, [], (), interpret=True)


@pytest.mark.parametrize("start", ["zero", "given"])
@pytest.mark.parametrize("sweep", [FORWARD, BACKWARD], ids=["forward", "backward"])
def test_symgs_sweep_matches_reference(sweep, start):
    # 7 planes (odd), 72 rows (a row tail), 20 lanes
    shape = (7, 72, 20)
    fields, _, r = _random_box27(shape, seed=5)
    x = (None if start == "zero"
         else jax.random.normal(jax.random.PRNGKey(6), shape, jnp.float32))
    offsets = stencil.BOX27.offsets
    got = symgs_sweep(r, x, [fields[o] for o in offsets], offsets,
                      first=sweep[0], inplane=sweep[1], interpret=True)
    want = reference_mg.apply_sweep(fields, r, jnp.zeros(shape) if x is None else x,
                                    tuple(colours(sweep)))
    assert _rel(got, want) <= VCYCLE_RTOL


def test_symgs_refuses_wider_offsets():
    v = jnp.zeros((4, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="radius-1"):
        symgs_sweep(v, None, [v], ((2, 0, 0),), first=0,
                    inplane=FORWARD[1], interpret=True)


def test_vcycle_is_symmetric():
    """<u, M^-1 v> = <M^-1 u, v> to float32 rounding for HPCG's symmetric
    matrix: CG may use it."""
    shape = SHAPES[0]
    coeffs = stencil.poisson(shape, spec=stencil.BOX27)
    u, v = (jax.random.normal(jax.random.PRNGKey(k), shape, jnp.float32)
            for k in (1, 2))
    mu, mv = _program_vcycle(coeffs, u), _program_vcycle(coeffs, v)
    a, b = float(jnp.vdot(u, mv)), float(jnp.vdot(mu, v))
    assert abs(a - b) <= 1e-5 * max(abs(a), abs(b))
    assert float(jnp.vdot(u, mu)) > 0


def test_restricted_residual_is_not_zero():
    """The coarse points' colour is not the last one the pre-smoother
    updates, so the coarse levels get a residual to correct."""
    shape = SHAPES[0]
    fields, coeffs, r = _random_box27(shape)
    op = operator.make_operator("reference", coeffs, policy=precision.F32)
    fine, coarse = build_levels(op)[:2]
    rs = fine.start(r)
    rc = fine.restrict(rs, fine.sgs(rs, None), coarse)
    assert float(jnp.linalg.norm(rc)) > 1e-2 * float(jnp.linalg.norm(r[::2, ::2, ::2]))


def test_pcg_matches_reference():
    """Iterations within one and the residual history of HPCG's PCG."""
    shape = (16, 16, 32)
    cf = stencil.poisson(shape, spec=stencil.BOX27)
    fields = {stencil.name_offset(n): c for n, c in cf.diags.items()}
    x_true = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32)
    b = stencil.rhs_for_solution(cf, x_true)
    res = bicgstab.solve_ref(cf, b, solver="cg", precond="mg", tol=1e-6,
                             maxiter=40, policy=precision.F32, record_history=True)
    x, it, hist = reference_mg.pcg(fields, b, tol=1e-6, maxiter=40)
    n, m = int(res.iterations), int(it)
    assert bool(res.converged) and abs(n - m) <= 1, (n, m)
    k = min(n, m) - 1
    np.testing.assert_allclose(np.asarray(res.history)[:k], np.asarray(hist)[:k],
                               rtol=1e-3)
    assert _rel(res.x, x) <= 1e-4


def test_coarse_levels_cut_iterations():
    shape = (16, 16, 32)
    cf = stencil.poisson(shape, spec=stencil.BOX27)
    b = stencil.rhs_for_solution(
        cf, jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32))
    op = operator.make_operator("reference", cf, policy=precision.F32)

    def iterations(levels):
        M = MGPrecond(build_levels(op, levels))
        return int(jax.jit(lambda v: solver_cg.cg_solver(
            op, v, tol=1e-6, maxiter=200, policy=precision.F32,
            precond=M).iterations)(b))

    its = {levels: iterations(levels) for levels in (1, 4)}
    assert its[4] < its[1], its


@pytest.mark.parametrize("precond", [None, IdentityPrecond()], ids=["none", "identity"])
def test_cg_without_preconditioner_is_the_plain_loop(precond):
    """Bit for bit the unpreconditioned CG loop."""
    shape = (8, 8, 16)
    cf = stencil.poisson(shape, spec=stencil.BOX27)
    b = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    op = operator.make_operator("reference", cf, policy=precision.F32)
    got = solver_cg.cg_solver(op, b, tol=1e-7, maxiter=60, policy=precision.F32,
                              precond=precond)
    want = solver_cg.cg_loop(op.apply, op.dots, b, tol=1e-7, maxiter=60,
                             policy=precision.F32)
    assert int(got.iterations) == int(want.iterations)
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))


def test_pipelined_cg_refuses_mg():
    shape = (8, 8, 16)
    cf = stencil.poisson(shape, spec=stencil.BOX27)
    with pytest.raises(ValueError, match="textbook PCG"):
        bicgstab.solve_ref(cf, jnp.ones(shape), solver="pipelined_cg", precond="mg")


@pytest.mark.parametrize("shape,specname,match", [
    ((12, 16, 16), "box27", "divisible by 8"),
    ((16, 16, 16), "star25", "radius-1"),
])
def test_mg_refuses_unfit_blocks(shape, specname, match):
    cf = stencil.poisson(shape, spec=stencil.get_spec(specname))
    op = operator.make_operator("reference", cf, policy=precision.F32)
    with pytest.raises(ValueError, match=match):
        build_precond(PrecondConfig(name="mg"), op)


def test_mg_refuses_a_mesh_of_two(subproc):
    subproc("""
        import jax.numpy as jnp, pytest
        from repro.core import bicgstab, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(2)
        shape = (16, 16, 16)
        cf = stencil.poisson(shape, spec=stencil.BOX27)
        with pytest.raises(ValueError, match="one device only"):
            bicgstab.solve_distributed(mesh, cf, jnp.ones(shape), solver="cg",
                                       precond="mg")
    """, n_devices=2)


def test_levels_are_the_injected_fields():
    shape = (16, 16, 32)
    _, coeffs, _ = _random_box27(shape)
    coarse = multigrid.inject(coeffs)
    for n, c in coeffs.diags.items():
        np.testing.assert_array_equal(np.asarray(coarse.diags[n]),
                                      np.asarray(c)[::2, ::2, ::2])
