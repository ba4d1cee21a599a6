"""Operator-layer tests: the three backends are interchangeable, the
Pallas-fused backend keeps the 3-AllReduce schedule end to end, and the
comm-scheduling layer (blocking vs overlap halo exchange) is bit-identical
with an unchanged collective count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision, stencil
from repro.core.comm import SCHEDULES, get_schedule
from repro.core.operator import BACKENDS, make_operator


def _problem(shape, seed=0, spec=None):
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(seed), shape, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(seed + 1), shape, jnp.float32)
    return cf, v


def test_registry_contents():
    assert set(BACKENDS) == {"reference", "spmd", "pallas"}
    with pytest.raises(KeyError, match="unknown backend"):
        make_operator("cuda", stencil.poisson((4, 4, 4)))


def test_schedule_registry_and_operator_carry():
    assert set(SCHEDULES) == {"blocking", "overlap"}
    assert get_schedule(None).name == "overlap"        # default
    for spelling in (False, True):                     # no boolean spelling
        with pytest.raises(KeyError, match="unknown comm schedule"):
            get_schedule(spelling)
    with pytest.raises(KeyError, match="unknown comm schedule"):
        get_schedule("eager")
    cf = stencil.poisson((4, 4, 4))
    for backend in sorted(BACKENDS):
        op = make_operator(backend, cf, schedule="blocking")
        assert op.schedule.name == "blocking", backend


@pytest.mark.parametrize("backend", ["reference", "spmd", "pallas"])
def test_backend_apply_matches_oracle(backend):
    """On a 1x1 fabric every backend is the same operator."""
    cf, v = _problem((8, 8, 8))
    u_ref = stencil.apply_ref(cf, v)
    op = make_operator(backend, cf, policy=precision.F32)
    np.testing.assert_allclose(np.asarray(op.apply(v)), np.asarray(u_ref),
                               rtol=1e-5, atol=1e-5)
    d = op.dots([(v, v), (v, u_ref)], precision.F32)
    np.testing.assert_allclose(np.asarray(d[0]), float(jnp.vdot(v, v)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d[1]), float(jnp.vdot(v, u_ref)),
                               rtol=1e-4, atol=1e-4)


def test_pallas_backend_raw_diag_correction():
    """The fused kernel keeps its unit-diagonal contract; the operator adds
    the raw diagonal's deviation outside the kernel."""
    cf = stencil.heterogeneous_poisson(jax.random.PRNGKey(2), (6, 6, 8))
    v = jax.random.normal(jax.random.PRNGKey(3), (6, 6, 8), jnp.float32)
    u_ref = stencil.apply_ref(cf, v)
    op = make_operator("pallas", cf, policy=precision.F32)
    np.testing.assert_allclose(np.asarray(op.apply(v)), np.asarray(u_ref),
                               rtol=1e-5, atol=1e-5)


def test_overlap_apply_bit_identical_and_same_ppermutes(subproc):
    """Acceptance (ISSUE 5): on a 2x2 fabric the overlap schedule's apply is
    bit-identical to blocking for both distributed backends across the
    stencil family, and lowers to exactly the same collective-permute count
    — overlap changes *when* halos move, never how many messages."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from repro.core import precision, stencil
        from repro.core.halo import FabricAxes, global_apply
        from repro.core.operator import make_operator
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)      # 2x2 fabric
        fabric = FabricAxes.from_mesh(mesh)
        pspec = fabric.spec(3)
        for name in ('star7', 'star25', 'box27'):
            spec = stencil.get_spec(name)
            shape = (16, 16, 6) if name == 'star25' else (8, 8, 6)
            cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape,
                                             spec=spec)
            v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
            u_ref = stencil.apply_ref(cf, v)
            # spmd: bitwise + collective-permute parity from lowered HLO
            outs, pp = {}, {}
            for schedule in ('blocking', 'overlap'):
                f = jax.jit(lambda c, vv, s=schedule: global_apply(
                    mesh, c, vv, schedule=s))
                outs[schedule] = np.asarray(f(cf, v))
                text = f.lower(cf, v).as_text()
                pp[schedule] = (text.count('collective_permute')
                                + text.count('collective-permute'))
            assert np.array_equal(outs['blocking'], outs['overlap']), name
            assert pp['blocking'] == pp['overlap'] > 0, (name, pp)
            np.testing.assert_allclose(outs['overlap'], np.asarray(u_ref),
                                       rtol=1e-5, atol=1e-5, err_msg=name)
            # pallas: interior through the fused kernel, ring re-run through
            # the same kernel on the exchanged slabs — still bitwise
            pouts = {}
            for schedule in ('blocking', 'overlap'):
                def f(c, vv, s=schedule):
                    op = make_operator('pallas', c, fabric,
                                       policy=precision.F32, schedule=s)
                    return op.apply(vv)
                pouts[schedule] = np.asarray(shard_map(
                    f, mesh=mesh, in_specs=(pspec, pspec), out_specs=pspec,
                    check_vma=False)(cf, v))
            assert np.array_equal(pouts['blocking'], pouts['overlap']), name
            np.testing.assert_allclose(pouts['overlap'], np.asarray(u_ref),
                                       rtol=1e-5, atol=1e-5, err_msg=name)
        print('OK')
    """, n_devices=4)


def test_overlap_solve_bit_identical(subproc):
    """Whole distributed solves are bit-identical across halo schedules
    (mixed precision included)."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)
        shape = (8, 8, 6)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        b = stencil.rhs_for_solution(
            cf, jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32))
        for policy, bb in ((precision.F32, b),
                           (precision.MIXED, b.astype(jnp.bfloat16))):
            xs = {}
            for schedule in ('blocking', 'overlap'):
                res = bicgstab.solve_distributed(
                    mesh, cf, bb, tol=1e-6, maxiter=40, policy=policy,
                    schedule=schedule)
                xs[schedule] = np.asarray(res.x, np.float32)
            assert np.array_equal(xs['blocking'], xs['overlap']), policy.name
        print('OK')
    """, n_devices=4)


@pytest.mark.slow
def test_distributed_pallas_matches_spmd_trajectory(subproc):
    """Acceptance: the Pallas-fused distributed backend reproduces the SPMD
    backend's residual trajectory to policy tolerance (f32 tight, bf16
    loose), and converges to the manufactured solution."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (8, 8, 6)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        # (policy, trajectory rtol, iterations compared): bf16's nonlinear
        # rounding feedback decorrelates long trajectories, so the mixed
        # policy is held to a loose tolerance over the early iterations
        for policy, traj_tol, depth in ((precision.F32, 1e-4, 40),
                                        (precision.MIXED, 0.15, 6)):
            bs = b.astype(policy.storage)
            runs = {}
            for backend in ("spmd", "pallas"):
                runs[backend] = bicgstab.solve_distributed(
                    mesh, cf, bs, tol=1e-5, maxiter=40, policy=policy,
                    backend=backend, record_history=True)
            h_spmd = np.asarray(runs["spmd"].history)
            h_pal = np.asarray(runs["pallas"].history)
            n = min(int(runs["spmd"].iterations), int(runs["pallas"].iterations),
                    depth)
            assert n > 0
            np.testing.assert_allclose(h_pal[:n], h_spmd[:n],
                                       rtol=traj_tol, atol=traj_tol)
        res = bicgstab.solve_distributed(mesh, cf, b, tol=1e-8, maxiter=300,
                                         policy=precision.F32, backend="pallas")
        assert bool(res.converged) and not bool(res.breakdown)
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                   rtol=2e-4, atol=2e-4)
        print('OK')
    """)


def test_fused_backend_allreduce_count_is_3(subproc):
    """Acceptance: one fused-backend iteration lowers to exactly 3 AllReduces
    (and the same 8 collective-permutes as the SPMD halo path)."""
    subproc("""
        import jax, jax.numpy as jnp
        from repro.core import bicgstab, precision, stencil
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)
        shape = (8, 8, 8)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        structs = [jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cf)]
        f32 = jax.ShapeDtypeStruct(shape, jnp.float32)
        structs += [f32, f32, f32, f32, jax.ShapeDtypeStruct((), jnp.float32)]
        for backend in ("spmd", "pallas"):
            it = bicgstab.make_iteration_fn(mesh, policy=precision.F32,
                                            backend=backend,
                                            fused_reductions=True)
            text = jax.jit(it).lower(*structs).as_text()
            n_ar = text.count("all_reduce") + text.count("all-reduce")
            n_pp = text.count("collective_permute") + text.count("collective-permute")
            assert n_ar == 3, (backend, n_ar)
            assert n_pp == 8, (backend, n_pp)
        print('OK')
    """, n_devices=4)


@pytest.mark.parametrize("policy", ["f32", "bf16_mixed"])
@pytest.mark.parametrize("backend,schedule", [
    ("reference", None), ("spmd", "blocking"), ("spmd", "overlap"),
    ("pallas", "blocking"), ("pallas", "overlap")])
def test_iteration_fn_is_the_solvers_step(backend, schedule, policy):
    """One call of ``make_iteration_fn`` from the solve's start (x = 0,
    r = p = r0 = b, rho = <b, b>) gives, bit for bit, the x that
    ``solve_distributed`` returns after one iteration: the unit the dry run
    lowers is the step the solve runs."""
    from repro.core import bicgstab
    from repro.launch.mesh import make_mesh_for_devices

    pol = precision.get_policy(policy)
    mesh = make_mesh_for_devices(1)
    cf, v = _problem((8, 8, 8))
    cf, b = cf.astype(pol.storage), v.astype(pol.storage)
    res = bicgstab.solve_distributed(
        mesh, cf, b, tol=0.0, maxiter=1, policy=pol, backend=backend,
        schedule=schedule)
    assert int(res.iterations) == 1
    it = bicgstab.make_iteration_fn(mesh, policy=pol, backend=backend,
                                    schedule=schedule)
    # <b, b> as the backend's own dots form it (pallas: its dot kernel)
    rho = jax.jit(lambda c, u: make_operator(backend, c, policy=pol).dots(
        [(u, u)], pol)[0])(cf, b)
    # jitted, as the dry run lowers it (op by op, XLA fuses no multiply-add)
    x, *_ = jax.jit(it)(cf, jnp.zeros_like(b), b, b, b, rho)
    assert x.dtype == res.x.dtype
    np.testing.assert_array_equal(np.asarray(x, np.float32),
                                  np.asarray(res.x, np.float32))
